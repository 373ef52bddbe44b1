"""The sparse-expert block-diffusion decoder's configuration, cell and metric
files: the contract, the operation counts against values worked by hand, the
traffic against the program's own noising transform, a toy-size rehearsal of
the cell's job on the CPU (``DT_FORCE_CPU=1``) through the real runner with
the float8 control, an overflowing buffer coming out not correct, and every
new metric file against the scope paths of the job's own step.  The numbers
a rehearsal prints are written nowhere."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import bench_toy
import contract
from bench_toy import BENCH, REPO, load

sys.path.insert(0, BENCH)
import readers  # noqa: E402
import sdar_opcount  # noqa: E402
import sdar_readers  # noqa: E402
import sdar_traffic  # noqa: E402

MANIFEST = load(os.path.join(REPO, "BENCHMARK.json"))
CELL = "sdar30b-ep8share-bd4-seq4096"
CONFIG = "sdar-30b-a3b-chat"
TRAFFIC = "bdtokens_b2_s4096_blk4"
CFG = load(os.path.join(BENCH, "configs", CONFIG + ".json"))
#: the cell's own per-layer metrics, by name (they waited in a file beside
#: the manifest from PR 34 to PR 36)
NEW_METRICS = [
    "model.bd_attn_ms_per_step", "model.moe_ms_per_step",
    "model.moe_route_ms_per_step", "model.moe_dispatch_ms_per_step",
    "model.moe_experts_ms_per_step", "kernel.flash_fwd_ms_per_step.bd",
    "kernel.flash_fwd_roofline.bd", "kernel.flash_bwd_ms_per_step.bd",
    "kernel.flash_bwd_roofline.bd", "kernel.gmm_ms_per_step",
    "kernel.gmm_roofline", "moe.held_load_share_pct",
    "moe.fullest_over_mean_load", "moe.buffer_fill_pct",
    "moe.overflow_assignments"]
#: the cells accepted before this one: in a list that holds this cell's
#: name they stand before it
EARLIER = ["resnet50-synth", "gpt2m-seq1024", "granite4hm-b2-seq4096"]
#: the catalog row's config (guide, architectures.jsonl), key for key
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}

#: the published configuration at widths in the tens, through the same job
TOY = {"name": "sdar-toy", "hidden_size": 32, "head_dim": 16,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "moe_intermediate_size": 24, "num_experts_per_tok": 2,
       "num_experts": 4, "held_experts_first": 2, "num_hidden_layers": 2,
       "vocab_size": 64, "mask_token_id": 63, "buffer_rows": 1024,
       "dtype": "float32",
       "source": "toy-size copy of sdar-30b-a3b-chat for the CPU rehearsal",
       "published": {**CFG["published"], "num_experts": 8}}
TOY_TRAFFIC = {"generator": "sdar_traffic:block_diffusion_tokens",
               "what": "toy", "batch": 2, "seq_len": 128, "block_length": 4,
               "t_min": 0.001, "distinct_batches": 3, "steps_per_reading": 1,
               "warm_steps": 0, "trace_last_s": 0.6}


def test_entry_and_file_meet_the_contract_and_no_width_differs():
    entry = next(e for e in MANIFEST["configs"] if e["name"] == CONFIG)
    contract.check_config(entry, CFG)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert CFG["published"] == {k: PUBLISHED[k] for k in entry["reduced"]}
    # every key of the source, with its value unless it is a reduced one
    for key, value in PUBLISHED.items():
        if key not in entry["reduced"]:
            assert CFG[key] == value, key
    assert (CFG["num_hidden_layers"], CFG["num_experts"],
            CFG["vocab_size"]) == (6, 16, 18992)
    assert 8 * CFG["vocab_size"] == PUBLISHED["vocab_size"]
    assert 8 * CFG["num_experts"] == PUBLISHED["num_experts"]
    assert CFG["mask_token_id"] == CFG["vocab_size"] - 1
    assert CFG["deployment"].startswith("each layer's 128 experts over 8")
    for key in ("source", "reduced", "published", "deployment", "assumed",
                "departures"):
        assert CFG[key], key
    for key in ("block_length", "noise_schedule", "mask_token_id",
                "aux_loss_coef", "optimizer", "initial_values",
                "buffer_rows", "batch"):
        assert key in CFG["assumed"], key
    assert not any(contract.WIDTH.search(k) for k in CFG["reduced"])
    assert CFG["check"]["limits_set_from"]


def manifest_assertions(manifest):
    """What this file says of ``BENCHMARK.json``, of the one here or of a
    copy that later PRs have appended to."""
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    mine = {m["name"] for m in metrics if CELL in m.get("workloads", [CELL])}
    assert {"tokens_per_s_per_chip", "setup_s", "model.mfu_pct.lm",
            "model.device_ms_per_step.lm", "model.unscoped_pct.lm",
            "model.forward_ms_per_step.lm", "model.backward_ms_per_step.lm",
            "loop.metric_device_steps_pct.lm", "compile.in_window.lm",
            "device.idle_pct.lm", "device.peak_hbm_gb.lm",
            "model.remat_ms_per_step"} <= mine
    # the other cells' kernel names are not this one's
    assert not {"kernel.flash_fwd_ms_per_step", "kernel.flash_bwd_roofline",
                "kernel.flash_fwd_roofline.gqa"} & mine
    # in every list the cell's name stands once, after the cells accepted
    # before it; what a later PR appends after it is that PR's
    for m in metrics:
        if CELL in m.get("workloads", []):
            assert contract.stands_once_after(m["workloads"], CELL, EARLIER), m
    # the fifteen of its own: each there once, in the manifest's form, the
    # cell first in its list (no accepted cell reads them)
    names = [m["name"] for m in manifest["per_layer"]]
    by_name = dict(zip(names, manifest["per_layer"]))
    assert len(NEW_METRICS) == 15 and set(NEW_METRICS) <= mine
    # the metric shared with the hybrid cell lists that cell, then this one
    assert by_name["model.remat_ms_per_step"]["workloads"][:2] == [
        "granite4hm-b2-seq4096", CELL]
    for name in NEW_METRICS:
        m = by_name[name]
        assert names.count(name) == 1
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"][0] == CELL
        assert m["moves"] == "tokens_per_s_per_chip"
        assert ("roofline" in name) == (m["unit"] == "%"
                                        and name[:6] == "kernel")
        assert m["layer"] == {
            "model.": "model step: dt_tpu/models, optim",
            "kernel": "kernels: ops/pallas",
            "moe.": "expert routing: parallel/moe.py"}[
                next(k for k in ("model.", "kernel", "moe.")
                     if name.startswith(k))]
        assert m["source"] == ("program_counter" if name.startswith("moe.")
                               else "device_trace")


def test_the_cell_reports_what_the_lm_cells_report_and_its_own():
    manifest_assertions(MANIFEST)
    traffic = load(os.path.join(BENCH, "traffic", TRAFFIC + ".json"))
    assert (traffic["batch"], traffic["seq_len"], traffic["block_length"],
            traffic["t_min"], traffic["distinct_batches"],
            traffic["steps_per_reading"], traffic["warm_steps"]) == (
        2, 4096, 4, 0.001, 3, 1, 0)
    assert traffic["block_length"] == CFG["block_length"]
    assert traffic["generator"] == "sdar_traffic:block_diffusion_tokens"


def test_the_cells_own_metrics_are_in_the_manifest():
    """Fifteen entries, each with a file of its own name and the cell first
    in its list (a later routed cell may follow it there: "alone" would be a
    pin of the list's length), and the metric shared with the hybrid cell:
    the line a traced run prints holds what the LM cells report and these
    sixteen."""
    for name in NEW_METRICS:
        path = readers.metric_file(BENCH, name)
        assert os.path.basename(path) == name + ".json"
    line = [m["name"] for m in MANIFEST["per_layer"]
            if CELL in m.get("workloads", [CELL])]
    assert len(line) >= 23 + 15 + 1
    assert set(NEW_METRICS) | {"model.remat_ms_per_step"} <= set(line)


def test_operations_per_token_by_hand():
    d = 2048
    attn = d * (4096 + 512 + 512) + 4096 * d                # q, k, v, o
    router = d * 128
    expert = 3 * d * 768
    # 8 of 128 a token, 16 held: one expert of this chip's share a position
    assert sdar_opcount.layer_matmul_params(CFG) == attn + router + expert \
        == 23855104
    traffic = {"seq_len": 4096, "block_length": 4}
    # the mask's pairs, counted one by one at a small size
    for length, block in ((16, 4), (24, 3), (8, 8)):
        pos = np.arange(2 * length)
        noisy, blk = pos < length, (pos % length) // block
        q, k = np.ix_(pos, pos)
        seen = np.where(noisy[k], noisy[q] & (blk[q] == blk[k]),
                        np.where(noisy[q], blk[k] < blk[q],
                                 blk[k] <= blk[q]))
        assert sdar_opcount.mask_pairs(length, block) == seen.sum() \
            == length * (length + block)
    pairs = 4096 * 4100
    weights = 2 * 6 * 23855104 + d * 18992
    flops = sdar_opcount.sdar_train_flops_per_item(CFG, traffic)
    assert flops == pytest.approx(
        6 * weights + 3 * 6 * (pairs / 4096) * 4 * 32 * 128)
    # a step of 8,192 tokens: about 26 TFLOP (ISSUE 34's arithmetic)
    assert 25.5e12 < flops * 8192 < 26.5e12
    ops, nbytes = sdar_opcount.bd_flash_forward_ops_bytes(2, 32, 4096, 4,
                                                          128, 2)
    assert ops == 2 * 32 * pairs * 2 * 2 * 128
    assert nbytes == 2 * 32 * 8192 * (4 * 128 * 2 + 4)
    ops_b, nbytes_b = sdar_opcount.bd_flash_backward_ops_bytes(
        2, 32, 4096, 4, 128, 2)
    assert ops_b == 5 * ops // 2
    assert nbytes_b == 2 * 32 * 8192 * (8 * 128 * 2 + 4)


def test_the_traffic_is_the_programs_own_noising_transform():
    """The benchmark's generator is plain numpy; the transform a user puts
    before ``fit`` (``dt_tpu.data.block_diffusion_noise``) makes the same
    batch from the same draws, so what ``correct`` trains on is what the
    program's users get."""
    from dt_tpu import data as dt_data
    cfg = {"vocab_size": 64, "mask_token_id": 63}
    data, labels = sdar_traffic.block_diffusion_tokens(
        np.random.default_rng(5), TOY_TRAFFIC, cfg)
    rng = np.random.default_rng(5)
    x0 = rng.integers(0, 63, (2, 128))
    mine = dt_data.block_diffusion_noise(x0, 4, 63, rng, t_min=0.001)
    np.testing.assert_array_equal(data, mine[0])
    np.testing.assert_array_equal(labels, mine[1])
    assert data.dtype == np.int32 and data.shape == (2, 256)
    assert labels.dtype == np.float32 and labels.shape == (2, 128, 2)
    assert (data[:, 128:] == x0).all() and (x0 < 63).all()
    masked = data[:, :128] == 63
    assert ((labels[..., 1] > 0) == masked).all()
    assert (labels[..., 0] == x0).all()


# -- the rehearsal: a copy with the toy cells added as files ----------------

@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    path = bench_toy.make_copy(str(tmp_path_factory.mktemp("sdar")))
    bench = os.path.join(os.path.dirname(path), "benchmark")
    man = load(path)
    toy = {**CFG, **TOY, "assumed": {}, "departures": ["toy size"],
           "check": {**CFG["check"], "limits": dict(bench_toy.TOY_LIMITS),
                     "limits_set_from": "tests/benchmark/bench_toy.py"}}
    configs = {"sdar-toy": toy,
               # a buffer the held assignments do not fit: some are dropped
               "sdar-toy-overflow": {**toy, "name": "sdar-toy-overflow",
                                     "buffer_rows": 128}}
    for name, cfg in configs.items():
        bench_toy.dump(cfg, os.path.join(bench, "configs", name + ".json"))
        man["configs"].append({
            "name": name, "source": "toy", "reduced": cfg["reduced"],
            "why": "toy", "file": f"benchmark/configs/{name}.json"})
    bench_toy.dump(TOY_TRAFFIC,
                   os.path.join(bench, "traffic", "bdtokens_b2_s128.json"))
    for cell, config in (("toy-sdar", "sdar-toy"),
                         ("toy-sdar-overflow", "sdar-toy-overflow")):
        man["workloads"].append({"name": cell, "config": config,
                                 "traffic": "bdtokens_b2_s128", "chips": 1,
                                 "why": "toy"})
        for m in man["end_to_end"] + man["per_layer"]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append(cell)
    bench_toy.dump(man, path)
    return path


def test_the_toy_copys_cut_configuration_meets_the_contract(manifest):
    man = load(manifest)
    entry = next(e for e in man["configs"] if e["name"] == "sdar-toy")
    cfg = load(os.path.join(os.path.dirname(manifest), entry["file"]))
    contract.check_config(entry, cfg)
    assert cfg["driver"] == "sdar_drivers:BlockDiffusionMoEJob"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cells_job_is_correct(manifest, trace):
    rc, last, out = bench_toy.run_cell(manifest, "toy-sdar", trace=trace)
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
    # the counters reached the host with the metric's statistics
    assert values["moe.overflow_assignments"] == 0
    assert 0 < values["moe.held_load_share_pct"] < 100
    assert 0 < values["moe.buffer_fill_pct"] <= 100
    assert values["moe.fullest_over_mean_load"] >= 1
    # the CPU's trace has no device plane: every reader of the device's
    # time finds nothing, returns nothing, and the line leaves it out
    assert got.isdisjoint(n for n in NEW_METRICS if not n.startswith("moe."))
    assert "scopes_missing" not in out


def test_an_overflowing_buffer_is_counted_and_comes_out_not_correct(manifest):
    rc, last, out = bench_toy.run_cell(manifest, "toy-sdar-overflow",
                                       trace=1)
    assert rc == 0 and last is not None, out[-3000:]
    assert last["metrics"]["moe.overflow_assignments"]["value"] > 0
    assert last["metrics"]["moe.buffer_fill_pct"]["value"] == 100.0
    assert last["correct"] is False
    assert " OVER" in out


def test_the_float8_control_fails_a_limit_at_toy_size(manifest):
    """``benchmark/control.py`` on the toy cell: the program within every
    limit, the reference in float8 in its place over at least one."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"), "--manifest",
         manifest, "--workload", "toy-sdar", "--seeds", "11", "--control",
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


def test_readers_of_the_counters_find_nothing_on_a_program_without_them():
    """The parent's ``Module`` has no ``step_counters``: each reader returns
    nothing and the line leaves the metric out."""
    class Job:
        class mod:
            pass
    for name in NEW_METRICS:
        if name.startswith("moe."):
            on_file = load(readers.metric_file(BENCH, name))
            reader = readers.resolve(on_file["reader"])
            assert reader({"job": Job(), "cfg": CFG}, on_file) is None
            assert reader({"job": object(), "cfg": CFG}, on_file) is None


def test_readers_of_the_counters_by_hand():
    # two layers, two steps each: [expert 0, expert 1, held, overflow, made]
    class Job:
        class mod:
            step_counters = {
                "block0/moe/moe": {"sum": np.array([30, 10, 40, 0, 400]),
                                   "max": np.array([20, 6, 25, 0, 200]),
                                   "steps": 2},
                "block1/moe/moe": {"sum": np.array([20, 40, 60, 4, 400]),
                                   "max": np.array([12, 25, 37, 4, 200]),
                                   "steps": 2}}
    ctx = {"job": Job(), "cfg": {"buffer_rows": 28}}
    assert sdar_readers.held_load_share_pct(ctx, {}) == 100 * 100 / 800
    assert sdar_readers.fullest_over_mean_load(ctx, {}) == 30 / 20
    assert sdar_readers.overflow_assignments(ctx, {}) == 4.0
    assert sdar_readers.buffer_fill_pct(ctx, {}) == pytest.approx(
        100 * (40 + 56) / (28 * 4))


# -- every new metric file against the job's own scope paths -----------------

@pytest.fixture(scope="module")
def step_scopes():
    """The scope path of every operation of the toy job's train step, as
    jax writes it into the program it hands the compiler (the device trace
    carries the same strings, PERF.md section 3), with blocks
    rematerialised as in the cell."""
    import jax
    import jax.numpy as jnp
    import sdar_drivers
    from dt_tpu.training import metrics as metrics_lib
    from dt_tpu.training.train_state import TrainState
    cfg = {**CFG, **TOY, "attention": None}
    job = sdar_drivers.BlockDiffusionMoEJob(cfg, TOY_TRAFFIC, 1, 0)
    mod = job.mod
    mod._metric_stats = metrics_lib.device_form(
        metrics_lib.create("weighted-ce"))
    mod._build_steps()
    tokens = jnp.zeros((2, 256), jnp.int32)
    labels = jnp.zeros((2, 128, 2), jnp.float32)
    state = jax.eval_shape(lambda: TrainState.create(
        mod.model.apply, mod.model.init(jax.random.PRNGKey(0),
                                        tokens)["params"], mod.tx, {}))
    text = mod._train_step.lower(state, tokens, labels,
                                 jax.random.PRNGKey(0)).as_text(
                                     debug_info=True)
    return sorted(set(re.findall(r'"(jit\(train_step\)/[^"]*)"', text)))


@pytest.mark.parametrize("name", [n for n in NEW_METRICS
                                  if not n.startswith("moe.")])
def test_new_metric_resolves_its_reader_and_finds_its_scope(step_scopes,
                                                            name):
    path = readers.metric_file(BENCH, name)
    assert os.path.basename(path) == name + ".json"
    on_file = load(path)
    reader = readers.resolve(on_file["reader"])
    if name.startswith("kernel.gmm"):
        # XLA's pass names each grouped product's event ragged-dot-none.N
        # (66 a step since PR 35, under names of their own or not)
        ctx = {"trace": {"steps": 2, "op_seconds": {
            "ragged-dot-none.70": 0.03, "ragged-dot-none.7": 0.05,
            "ragged-dot-metadata.1": 1.0, "fusion.1": 1.0}, "op_events": {
            "ragged-dot-none.70": 60, "ragged-dot-none.7": 72,
            "ragged-dot-metadata.1": 12, "fusion.1": 2}},
            "traffic": {"batch": 2, "seq_len": 4096}, "cfg": CFG,
            "rehearsal": False, "device_kind": "TPU v5 lite",
            "bench_dir": BENCH}
        value = reader(ctx, on_file)
        if "roofline" in name:
            rows = CFG["buffer_rows"]
            ops, nbytes = sdar_opcount.grouped_ops_bytes(rows, 2048, 768,
                                                         16, 2)
            assert ops == 2 * rows * 2048 * 768
            assert nbytes == rows * 2048 * 2 + 16 * 2048 * 768 * 2 \
                + rows * 768 * 4
            peak = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
            assert value == pytest.approx(
                100 * 66 * ops / peak["bf16_flops_per_s"] / 0.04)
        else:
            assert value == pytest.approx(40.0)
        return
    if name.startswith("kernel."):
        # the kernels' events carry the names their pallas_calls give
        # them under the mask rule; read here from made-up operations
        bwd = "flash_bwd" in name
        # six calls a step of each: the forward under two names, the
        # backward under one (as under a ``while``)
        ctx = {"trace": {"steps": 2, "op_seconds": {
            "flash_fwd_bd.2": 0.004, "flash_fwd_bd.3": 0.006,
            "flash_bwd_bd.1": 0.02, "flash_bwd.7": 5.0, "attn.1": 3.0,
            "fusion.1": 1.0}, "op_events": {
            "flash_fwd_bd.2": 6, "flash_fwd_bd.3": 6, "flash_bwd_bd.1": 12,
            "flash_bwd.7": 48, "attn.1": 4, "fusion.1": 2}},
            "traffic": {"batch": 2, "seq_len": 4096, "block_length": 4},
            "cfg": CFG, "rehearsal": False, "device_kind": "TPU v5 lite",
            "bench_dir": BENCH}
        value = reader(ctx, on_file)
        per_step = 0.01 if bwd else 0.005
        if "roofline" in name:
            ops, _ = (sdar_opcount.bd_flash_backward_ops_bytes if bwd else
                      sdar_opcount.bd_flash_forward_ops_bytes)(
                2, 32, 4096, 4, 128, 2)
            peak = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
            calls = 6
            assert value == pytest.approx(
                100 * calls * ops / peak["bf16_flops_per_s"] / per_step)
        else:
            assert value == pytest.approx(1e3 * per_step)
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


def test_the_layers_parts_lie_inside_it_and_tell_each_other_apart(
        step_scopes):
    """The scopes the metric files hold tell the attention, the routed
    layer and the recomputation apart, and the routed layer's three parts
    are disjoint and add up to it: no operation is in two."""
    import xplane
    args = lambda n: load(readers.metric_file(BENCH, n))["args"]  # noqa: E731
    split = [args(n) for n in ("model.bd_attn_ms_per_step",
                               "model.moe_ms_per_step",
                               "model.remat_ms_per_step")]
    inner = [args(n) for n in ("model.moe_route_ms_per_step",
                               "model.moe_dispatch_ms_per_step",
                               "model.moe_experts_ms_per_step")]
    match = lambda s, a: xplane.scope_matches(  # noqa: E731
        s, a["holds"], a.get("lacks", ()))
    counts, parts = [0] * len(split), [0] * len(inner)
    for scope in step_scopes:
        hits = [match(scope, a) for a in split]
        assert sum(hits) <= 1, scope
        counts = [c + h for c, h in zip(counts, hits)]
        inside = [match(scope, a) for a in inner]
        assert sum(inside) == hits[1], scope    # in the layer: in one part
        parts = [c + h for c, h in zip(parts, inside)]
        if not any(hits):    # the ends, the norms between, the optimizer
            assert not re.search(r"/(attn|moe)/", scope), scope
    assert all(counts) and all(parts), (counts, parts)
    # the scopes the issue names are all in some operation's path
    for part in ("attn/rope", "moe/route", "moe/dispatch", "moe/experts",
                 "moe/combine", "lm_head", "embed"):
        assert any(part in s for s in step_scopes), part
