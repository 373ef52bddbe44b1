"""The hybrid decoder's configuration, cell and metric files: the contract,
the operation count against values worked by hand, a toy-size rehearsal of
the cell's job on the CPU (``DT_FORCE_CPU=1``) through the real runner, the
timed path broken underneath, and every new metric file against the scope
paths of the job's own step.  The numbers a rehearsal prints are written
nowhere."""

import os
import re
import sys

import pytest

import bench_toy
import contract
from bench_toy import BENCH, REPO, load

sys.path.insert(0, BENCH)
import hybrid_opcount  # noqa: E402
import opcount  # noqa: E402
import readers  # noqa: E402

MANIFEST = load(os.path.join(REPO, "BENCHMARK.json"))
CELL = "granite4hm-b2-seq4096"
CONFIG = "granite-4.0-h-micro"
CFG = load(os.path.join(BENCH, "configs", CONFIG + ".json"))
#: the cell's own per-layer metrics (PR 28), by name: each lists the cell,
#: and a later cell may list itself too (``model.remat_ms_per_step``)
NEW_METRICS = [
    "model.ssm_mixer_ms_per_step", "model.ssm_scan_ms_per_step",
    "model.ssm_scan_bwd_ms_per_step", "model.ssm_conv_ms_per_step",
    "model.gqa_attn_ms_per_step", "model.mlp_ms_per_step",
    "model.remat_ms_per_step", "kernel.flash_fwd_ms_per_step.gqa",
    "kernel.flash_fwd_roofline.gqa"]

#: the published configuration at widths in the tens, through the same job
TOY = {"name": "granite-toy", "hidden_size": 32,
       "shared_intermediate_size": 48, "intermediate_size": 48,
       "vocab_size": 64, "num_hidden_layers": 2,
       "layer_types": ["mamba", "attention"], "num_attention_heads": 4,
       "num_key_value_heads": 2, "mamba_n_heads": 4, "mamba_d_head": 16,
       "mamba_d_state": 8, "mamba_chunk_size": 32, "dtype": "float32",
       "source": "toy-size copy of granite-4.0-h-micro for the CPU rehearsal",
       "published": CFG["published"]}
TOY_TRAFFIC = {"generator": "traffic:uniform_tokens", "what": "toy",
               "batch": 2, "seq_len": 128, "distinct_batches": 3,
               "steps_per_reading": 1, "warm_steps": 0, "trace_last_s": 0.6}
BROKEN = {"toy-hybrid-decay": "broken_hybrid_drivers:SlowDecayJob",
          "toy-hybrid-residual": "broken_hybrid_drivers:UnitResidualJob"}


def test_entry_and_file_meet_the_contract_and_no_width_differs():
    entry = next(e for e in MANIFEST["configs"] if e["name"] == CONFIG)
    contract.check_config(entry, CFG)
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "vocab_size"]
    assert CFG["published"]["num_hidden_layers"] == 40
    assert CFG["published"]["vocab_size"] == 100352 == 8 * CFG["vocab_size"]
    # one period of the published pattern, whole, in the published ratio
    period = CFG["published"]["layer_types"][:10]
    assert CFG["layer_types"] == period and period.count("attention") == 1
    assert CFG["published"]["layer_types"] == period * 4
    assert CFG["num_hidden_layers"] == len(CFG["layer_types"]) == 10
    # the published widths, under the source's own keys
    assert {k: CFG[k] for k in (
        "hidden_size", "shared_intermediate_size", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
        "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_expand",
        "mamba_n_groups", "mamba_chunk_size")} == {
        "hidden_size": 2048, "shared_intermediate_size": 8192,
        "intermediate_size": 8192, "num_attention_heads": 32,
        "num_key_value_heads": 8, "mamba_n_heads": 64, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_chunk_size": 256}
    assert (CFG["embedding_multiplier"], CFG["residual_multiplier"],
            CFG["logits_scaling"], CFG["attention_multiplier"]) == (
        12, 0.22, 8, 0.015625)
    assert CFG["tie_word_embeddings"] is True
    for key in ("source", "reduced", "published", "deployment", "assumed",
                "departures"):
        assert CFG[key], key
    assert not any(contract.WIDTH.search(k) for k in CFG["reduced"])


def manifest_assertions(manifest):
    """What this file says of ``BENCHMARK.json``, of the one here or of a
    copy that later PRs have appended to."""
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "tokens_b2_s4096", 1)
    mine = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert {"tokens_per_s_per_chip", "setup_s", "model.mfu_pct.lm",
            "model.unscoped_pct.lm", "loop.metric_device_steps_pct.lm",
            "compile.in_window.lm"} <= mine
    # each of the nine is there once and lists the cell
    assert len(NEW_METRICS) == 9 and set(NEW_METRICS) <= mine
    names = [m["name"] for m in manifest["per_layer"]]
    assert all(names.count(n) == 1 for n in NEW_METRICS)
    # the names the first LM cell's kernel readers hold are not this model's
    assert not {"kernel.flash_fwd_ms_per_step",
                "kernel.flash_fwd_roofline"} & mine


def test_the_cell_reports_what_the_lm_cell_reports_and_its_own():
    manifest_assertions(MANIFEST)
    traffic = load(os.path.join(BENCH, "traffic", "tokens_b2_s4096.json"))
    assert (traffic["batch"], traffic["seq_len"]) == (2, 4096)
    assert traffic["generator"] == "traffic:uniform_tokens"


def test_operations_per_token_by_hand():
    d, ff, v = 2048, 8192, 12544
    mamba = d * (4096 + 4096 + 2 * 128 + 64) + 4096 * d     # in_proj, out_proj
    attn = d * (2048 + 512 + 512) + 2048 * d                # q, k, v, o
    weights = 9 * mamba + attn + 10 * 3 * d * ff + d * v
    assert hybrid_opcount.hybrid_matmul_params(CFG) == weights == 771883008
    traffic = {"seq_len": 4096}
    scan = 9 * 4 * 64 * 64 * 128        # update and read-out of 64 x (64 x 128)
    attention = 2 * (2 * 4096 * 2048) // 2
    assert hybrid_opcount.hybrid_train_flops_per_item(CFG, traffic) == \
        6 * weights + 3 * (attention + scan)
    # the flash forward at this shape: 32 heads over 4,096 positions
    ops, nbytes = opcount.flash_forward_ops_bytes(2, 32, 4096, 64, 2)
    assert ops == 2 * 32 * 2 * (2 * 4096 * 4096 * 64) // 2
    assert nbytes == 2 * 32 * 4096 * (4 * 64 * 2 + 4)


# -- the rehearsal: a copy with the toy cells added as files ----------------

@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    path = bench_toy.make_copy(str(tmp_path_factory.mktemp("hybrid")))
    bench = os.path.join(os.path.dirname(path), "benchmark")
    man = load(path)
    toy = {**CFG, **TOY, "assumed": {}, "departures": ["toy size"],
           "check": {**CFG["check"], "limits": dict(bench_toy.TOY_LIMITS),
                     "limits_set_from": "tests/benchmark/bench_toy.py"}}
    configs = {"granite-toy": toy}
    for cell, driver in BROKEN.items():
        configs["granite-" + cell] = {**toy, "name": "granite-" + cell,
                                      "driver": driver}
    for name, cfg in configs.items():
        bench_toy.dump(cfg, os.path.join(bench, "configs", name + ".json"))
        man["configs"].append({
            "name": name, "source": "toy", "reduced": cfg["reduced"],
            "why": "toy", "file": f"benchmark/configs/{name}.json"})
    bench_toy.dump(TOY_TRAFFIC,
                   os.path.join(bench, "traffic", "tokens_b2_s128u.json"))
    for cell, config in [("toy-hybrid", "granite-toy")] + [
            (c, "granite-" + c) for c in BROKEN]:
        man["workloads"].append({"name": cell, "config": config,
                                 "traffic": "tokens_b2_s128u", "chips": 1,
                                 "why": "toy"})
        for m in man["end_to_end"] + man["per_layer"]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append(cell)
    bench_toy.dump(man, path)
    return path


def test_the_toy_copys_cut_configuration_meets_the_contract(manifest):
    man = load(manifest)
    entry = next(e for e in man["configs"] if e["name"] == "granite-toy")
    cfg = load(os.path.join(os.path.dirname(manifest), entry["file"]))
    contract.check_config(entry, cfg)
    assert cfg["driver"] == "hybrid_drivers:HybridLMJob"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cells_job_is_correct(manifest, trace):
    rc, last, out = bench_toy.run_cell(manifest, "toy-hybrid", trace=trace)
    assert rc == 0 and last is not None, out[-3000:]
    assert last["correct"] is True, out[-3000:]
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0 and last["failed"] == 0
    assert len(last["compared"]) == 8
    got = set(last["metrics"])
    if not trace:
        assert got == {"tokens_per_s_per_chip", "setup_s"}
        return
    values = {k: last["metrics"][k]["value"] for k in got}
    assert values["loop.metric_device_steps_pct.lm"] == 100.0
    assert values["compile.in_window.lm"] == 0
    # the CPU's trace has no device plane: every reader of the device's
    # time finds nothing, returns nothing, and the line leaves it out
    assert got.isdisjoint(NEW_METRICS)
    assert "scopes_missing" not in out


@pytest.mark.parametrize("cell", sorted(BROKEN))
def test_a_broken_decay_or_residual_comes_out_not_correct(manifest, cell):
    rc, last, out = bench_toy.run_cell(manifest, cell)
    assert rc == 0 and last is not None, out[-3000:]
    assert last["correct"] is False
    assert " OVER" in out


def test_the_float8_control_fails_a_limit_at_toy_size(manifest):
    """``benchmark/control.py`` on the toy cell: the program within every
    limit, the reference in float8 in its place over at least one."""
    import json
    import subprocess
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"), "--manifest",
         manifest, "--workload", "toy-hybrid", "--seeds", "11", "--control",
         "1"], capture_output=True, text=True, timeout=600,
        env=bench_toy.rehearsal_env(), preexec_fn=bench_toy.two_cores)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    row = json.loads([ln for ln in proc.stdout.splitlines()
                      if ln.startswith("{")][-1])
    limits = bench_toy.TOY_LIMITS

    def limit(number):
        return limits["loss_rel" if number.startswith("loss.") else
                      number.replace(".", "_")]

    assert all(v <= limit(k) for k, v in row["program"].items()), row
    assert any(v > limit(k) for k, v in row["control"].items()), row


# -- every new metric file against the job's own scope paths -----------------

@pytest.fixture(scope="module")
def step_scopes():
    """The scope path of every operation of the toy job's train step, as
    jax writes it into the program it hands the compiler (the device trace
    carries the same strings, PERF.md section 3), with blocks
    rematerialised as in the cell."""
    import jax
    import jax.numpy as jnp
    import hybrid_drivers
    from dt_tpu.training import metrics as metrics_lib
    from dt_tpu.training.train_state import TrainState
    cfg = {**CFG, **TOY, "attention": None}
    job = hybrid_drivers.HybridLMJob(cfg, TOY_TRAFFIC, 1, 0)
    mod = job.mod
    mod._metric_stats = metrics_lib.device_form(metrics_lib.create("ce"))
    mod._build_steps()
    tokens = jnp.zeros((2, 128), jnp.int32)
    state = jax.eval_shape(lambda: TrainState.create(
        mod.model.apply, mod.model.init(jax.random.PRNGKey(0),
                                        tokens)["params"], mod.tx, {}))
    text = mod._train_step.lower(state, tokens, tokens,
                                 jax.random.PRNGKey(0)).as_text(
                                     debug_info=True)
    return sorted(set(re.findall(r'"(jit\(train_step\)/[^"]*)"', text)))


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_resolves_its_reader_and_finds_its_scope(step_scopes,
                                                            name):
    path = readers.metric_file(BENCH, name)
    assert os.path.basename(path) == name + ".json"
    on_file = load(path)
    reader = readers.resolve(on_file["reader"])
    if name.startswith("kernel."):
        # the flash forward's events carry the attention module's name
        # (on the chip: ``attn.N``); read here from made-up operations:
        # two steps, in each one event in the forward pass and one in the
        # block's recomputation
        ctx = {"trace": {"steps": 2,
                         "op_seconds": {"attn.2": 0.004, "attn.3": 0.004,
                                        "fusion.1": 1.0},
                         "op_events": {"attn.2": 2, "attn.3": 2,
                                       "fusion.1": 9}},
               "traffic": {"batch": 2, "seq_len": 4096}, "cfg": CFG,
               "rehearsal": False, "device_kind": "TPU v5 lite",
               "bench_dir": BENCH}
        value = reader(ctx, on_file)
        if "roofline" in name:
            ops, _ = opcount.flash_forward_ops_bytes(2, 32, 4096, 64, 2)
            peak = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
            assert value == pytest.approx(
                100 * 2 * ops / peak["bf16_flops_per_s"] / 0.004)
        else:
            assert value == pytest.approx(4.0)
        return
    # a trace in which every operation of the step took one millisecond
    trace = {"steps": 1, "devices": 1, "busy_s": 1e-3 * len(step_scopes),
             "scope_seconds": {s: 1e-3 for s in step_scopes}}
    value = reader({"trace": trace}, on_file)
    assert value is not None and value > 0, on_file["args"]
    # and a program without these scopes reads as nothing to count
    bare = {**trace, "scope_seconds": {
        "jit(train_step)/jvp(forward)/block0/mlp_in/dot_general": 1.0},
        "busy_s": 1.0}
    assert reader({"trace": bare}, on_file) == 0


def test_the_mixers_parts_lie_inside_it_and_the_rest_is_the_head(step_scopes):
    """The scopes the metric files hold tell the mixer, the attention, the
    feed-forward and the recomputation apart: no operation is in two."""
    import xplane
    split = [load(readers.metric_file(BENCH, n))["args"] for n in (
        "model.ssm_mixer_ms_per_step", "model.gqa_attn_ms_per_step",
        "model.mlp_ms_per_step", "model.remat_ms_per_step")]
    inner = [load(readers.metric_file(BENCH, n))["args"] for n in (
        "model.ssm_scan_ms_per_step", "model.ssm_scan_bwd_ms_per_step",
        "model.ssm_conv_ms_per_step")]
    counts = [0] * len(split)
    for scope in step_scopes:
        hits = [xplane.scope_matches(scope, a["holds"], a.get("lacks", ()))
                for a in split]
        assert sum(hits) <= 1, scope
        counts = [c + h for c, h in zip(counts, hits)]
        if any(xplane.scope_matches(scope, a["holds"], a.get("lacks", ()))
               for a in inner):
            assert hits[0], scope
        if not any(hits):    # the ends, the norms between, the optimizer
            assert not re.search(r"/(mamba|attn|mlp)/", scope), scope
    assert all(counts), counts
