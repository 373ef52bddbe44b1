"""The sparse-attention expert decoder's configuration, cell and metric files:
the contract, the operation counts against values worked by hand, a toy-size
rehearsal of the cell's job on the CPU (``DT_FORCE_CPU=1``) through the real
runner with the float8 control, the readers of the layers' counters, and
every new metric file against the scope paths of the job's own step.  The
numbers a rehearsal prints are written nowhere."""

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
import keye_opcount  # noqa: E402
import keye_readers  # noqa: E402
import readers  # noqa: E402
import sdar_opcount  # noqa: E402

MANIFEST = load(os.path.join(REPO, "BENCHMARK.json"))
CELL = "keye30b-ep8share-dsa2048-seq16384"
CONFIG = "keye-vl-2.0-30b-a3b"
TRAFFIC = "tokens_b1_s16384"
CFG = load(os.path.join(BENCH, "configs", CONFIG + ".json"))
#: the cell's own per-layer metrics, by name
NEW_METRICS = [
    "model.dsa_attn_ms_per_step", "model.indexer_ms_per_step",
    "model.select_ms_per_step", "model.indexer_kl_ms_per_step",
    "kernel.flash_fwd_ms_per_step.sel", "kernel.flash_fwd_roofline.sel",
    "kernel.flash_bwd_ms_per_step.sel", "kernel.flash_bwd_roofline.sel",
    "dsa.selected_pairs_pct", "dsa.tiles_run_pct", "dsa.indexer_kl"]
#: the routed cell's metrics that this cell reports too
SHARED = [
    "model.remat_ms_per_step", "model.moe_ms_per_step",
    "model.moe_route_ms_per_step", "model.moe_dispatch_ms_per_step",
    "model.moe_experts_ms_per_step", "kernel.gmm_ms_per_step",
    "kernel.gmm_roofline", "moe.held_load_share_pct",
    "moe.fullest_over_mean_load", "moe.buffer_fill_pct",
    "moe.overflow_assignments"]
#: the cells accepted before this one: in a list that holds this cell's
#: name they stand before it
EARLIER = ["resnet50-synth", "gpt2m-seq1024", "granite4hm-b2-seq4096",
           "sdar30b-ep8share-bd4-seq4096"]
#: the catalog row's config (guide, architectures.jsonl), key for key
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}

#: the published configuration at widths in the tens, through the same job
TOY = {"name": "keye-toy", "hidden_size": 32, "head_dim": 16,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "moe_intermediate_size": 24, "num_experts_per_tok": 2,
       "num_experts": 4, "num_local_experts": 4, "held_experts_first": 2,
       "num_hidden_layers": 2, "vocab_size": 64, "buffer_rows": 512,
       "dtype": "float32",
       "rope_scaling": {**CFG["rope_scaling"], "mrope_section": [2, 3, 3]},
       "sa_config": {**CFG["sa_config"], "indexer_head_dim": 8,
                     "indexer_num_heads": 4, "topk": 32, "q_chunk_size": 128,
                     "kv_chunk_size": 128},
       "source": "toy-size copy of keye-vl-2.0-30b-a3b for the CPU rehearsal",
       "published": {**CFG["published"], "num_experts": 8,
                     "num_local_experts": 8}}
TOY_TRAFFIC = {"generator": "traffic:uniform_tokens", "what": "toy",
               "batch": 2, "seq_len": 128, "distinct_batches": 3,
               "steps_per_reading": 1, "warm_steps": 0, "trace_last_s": 0.6}


def test_entry_and_file_meet_the_contract_and_no_width_differs():
    entry = next(e for e in MANIFEST["configs"] if e["name"] == CONFIG)
    contract.check_config(entry, CFG)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "num_local_experts", "vocab_size"]
    assert CFG["published"] == {k: PUBLISHED[k] for k in entry["reduced"]}
    # every key of the source, with its value unless it is a reduced one;
    # the nested groups whole: the index's sizes and topk among them
    for key, value in PUBLISHED.items():
        if key not in entry["reduced"]:
            assert CFG[key] == value, key
    assert (CFG["num_hidden_layers"], CFG["num_experts"],
            CFG["num_local_experts"], CFG["vocab_size"]) == (6, 16, 16, 18992)
    assert 8 * CFG["vocab_size"] == PUBLISHED["vocab_size"]
    assert 8 * CFG["num_experts"] == PUBLISHED["num_experts"]
    assert CFG["deployment"].startswith("each layer's 128 experts over 8")
    for key in ("source", "reduced", "published", "deployment", "assumed",
                "departures"):
        assert CFG[key], key
    for key in ("qk_norm", "index_key_norm", "index_rotary",
                "index_weight_scale", "indexer_kl_weight", "aux_loss_coef", "optimizer",
                "initial_values", "buffer_rows", "batch", "positions"):
        assert key in CFG["assumed"], key
    assert any("vision" in d for d in CFG["departures"])
    assert any("index scores" in d for d in CFG["departures"])
    assert not any(contract.WIDTH.search(k) for k in CFG["reduced"])
    assert CFG["check"]["limits_set_from"]
    # the same deployment and recipe as the routed cell's configuration
    sdar = load(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat.json"))
    for key in ("optimizer", "dtype", "aux_loss_coef", "initializer_range",
                "residual_out_initializer_range", "held_experts_first",
                "remat_blocks", "hidden_size", "moe_intermediate_size"):
        assert CFG[key] == sdar[key], key


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
            "device.idle_pct.lm", "device.peak_hbm_gb.lm"} <= mine
    assert set(SHARED) <= mine and set(NEW_METRICS) <= mine
    # the other cells' kernel names and attention scope are not this one's
    assert not {"kernel.flash_fwd_ms_per_step", "kernel.flash_bwd_roofline",
                "kernel.flash_fwd_roofline.gqa", "model.bd_attn_ms_per_step",
                "kernel.flash_fwd_roofline.bd"} & mine
    # in every list the cell's name stands once, after the cells accepted
    # before it; what a later PR appends after it is that PR's
    for m in metrics:
        if CELL in m.get("workloads", []):
            assert contract.stands_once_after(m["workloads"], CELL, EARLIER), m
    # its own: each there once, in the manifest's form, the cell first in
    # its list (no accepted cell reads them)
    names = [m["name"] for m in manifest["per_layer"]]
    by_name = dict(zip(names, manifest["per_layer"]))
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
            "dsa.": "learned key selection: ops/sparse_index.py"}[
                next(k for k in ("model.", "kernel", "dsa.")
                     if name.startswith(k))]
        assert m["source"] == ("program_counter" if name.startswith("dsa.")
                               else "device_trace")
    # the routed cell's that it shares list that cell, then this one
    for name in SHARED:
        assert contract.stands_once_after(
            by_name[name]["workloads"], CELL,
            ["sdar30b-ep8share-bd4-seq4096"]), name


def test_the_cell_reports_what_the_lm_cells_report_and_its_own():
    manifest_assertions(MANIFEST)
    traffic = load(os.path.join(BENCH, "traffic", TRAFFIC + ".json"))
    assert (traffic["batch"], traffic["seq_len"], traffic["distinct_batches"],
            traffic["steps_per_reading"], traffic["warm_steps"]) == (
        1, 16384, 3, 1, 0)
    assert traffic["generator"] == "traffic:uniform_tokens"
    for name in NEW_METRICS:
        path = readers.metric_file(BENCH, name)
        assert os.path.basename(path) == name + ".json"
    assert len(MANIFEST["workloads"]) >= 5
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"])


def test_operations_per_token_by_hand():
    d, s, k = 2048, 16384, 2048
    # the pairs, counted one by one at a small size
    for seq, top in ((16, 4), (24, 24), (8, 100)):
        t = np.arange(seq)
        assert keye_opcount.selected_pairs(seq, top) == \
            np.minimum(t + 1, top).sum()
        assert keye_opcount.causal_pairs(seq) == (t + 1).sum()
    assert keye_opcount.selected_pairs(s, k) == 31458304      # 31.5M
    assert keye_opcount.causal_pairs(s) == 134225920          # 134M
    index = d * (16 * 64 + 64 + 16)
    assert keye_opcount.index_params(CFG) == index == 2260992
    layer = sdar_opcount.layer_matmul_params(CFG)
    assert layer == 23855104                    # as the routed cell's
    traffic = {"seq_len": s, "batch": 1}
    weights = 6 * (layer + index) + d * 18992
    flops = keye_opcount.keye_train_flops_per_item(CFG, traffic)
    assert flops == pytest.approx(
        6 * weights + 3 * 6 * (31458304 / s) * 4 * 32 * 128
        + 3 * 6 * (134225920 / s) * 2 * 16 * 65)
    # a step of 16,384 tokens: 19.2 TFLOP of matrix products, 9.3 of
    # attention over the selected pairs, 5.0 of index scores (ISSUE 37)
    assert 33e12 < flops * s < 34e12
    assert 19.0e12 < 6 * weights * s < 19.5e12
    ops, nbytes = keye_opcount.sel_flash_forward_ops_bytes(1, 32, s, k, 128,
                                                           2)
    assert ops == 32 * 31458304 * 2 * 2 * 128
    assert nbytes == 32 * s * (4 * 128 * 2 + 4) + s * s // 8
    ops_b, nbytes_b = keye_opcount.sel_flash_backward_ops_bytes(
        1, 32, s, k, 128, 2)
    assert ops_b == 5 * ops // 2
    assert nbytes_b == 32 * s * (8 * 128 * 2 + 4) + s * s // 8
    # a kernel that computes every causal pair reads under a quarter of
    # what it would read of its own work
    assert 0.23 < 31458304 / 134225920 < 0.24


# -- the rehearsal: a copy with the toy cell added as files -----------------

@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    path = bench_toy.make_copy(str(tmp_path_factory.mktemp("keye")))
    bench = os.path.join(os.path.dirname(path), "benchmark")
    man = load(path)
    toy = {**CFG, **TOY, "assumed": {}, "departures": ["toy size"],
           "check": {**CFG["check"], "limits": dict(bench_toy.TOY_LIMITS),
                     "limits_set_from": "tests/benchmark/bench_toy.py"}}
    bench_toy.dump(toy, os.path.join(bench, "configs", "keye-toy.json"))
    man["configs"].append({
        "name": "keye-toy", "source": "toy", "reduced": toy["reduced"],
        "why": "toy", "file": "benchmark/configs/keye-toy.json"})
    bench_toy.dump(TOY_TRAFFIC,
                   os.path.join(bench, "traffic", "tokens_b2_s128k.json"))
    man["workloads"].append({"name": "toy-keye", "config": "keye-toy",
                             "traffic": "tokens_b2_s128k", "chips": 1,
                             "why": "toy"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy-keye")
    bench_toy.dump(man, path)
    return path


def test_the_toy_copys_cut_configuration_meets_the_contract(manifest):
    man = load(manifest)
    entry = next(e for e in man["configs"] if e["name"] == "keye-toy")
    cfg = load(os.path.join(os.path.dirname(manifest), entry["file"]))
    contract.check_config(entry, cfg)
    assert cfg["driver"] == "keye_drivers:SparseIndexMoEJob"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cells_job_is_correct(manifest, trace):
    rc, last, out = bench_toy.run_cell(manifest, "toy-keye", trace=trace)
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
    each = np.minimum(np.arange(128) + 1, 32).sum() / (128 * 129 / 2)
    assert 100 * each <= values["dsa.selected_pairs_pct"] < 105 * each
    assert values["dsa.tiles_run_pct"] == 100.0
    assert 0.01 < values["dsa.indexer_kl"] < 5
    # the CPU's trace has no device plane: every reader of the device's
    # time finds nothing, returns nothing, and the line leaves it out
    assert got.isdisjoint(n for n in NEW_METRICS if not n.startswith("dsa."))
    assert "scopes_missing" not in out


def test_the_float8_control_fails_a_limit_at_toy_size(manifest):
    """``benchmark/control.py`` on the toy cell: the program within every
    limit, the reference in float8 in its place over at least one."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"), "--manifest",
         manifest, "--workload", "toy-keye", "--seeds", "11", "--control",
         "1"], capture_output=True, text=True, timeout=900,
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
    """The parent's ``Module`` has no ``step_counters``, and the routed
    cell's has none of these layers: each reader returns nothing and the
    line leaves the metric out."""
    class Job:
        class mod:
            pass

    class Routed:
        class mod:
            step_counters = {"block0/moe/moe": {
                "sum": np.array([1, 2, 3, 0, 4]), "steps": 1}}
    for name in NEW_METRICS:
        if name.startswith("dsa."):
            on_file = load(readers.metric_file(BENCH, name))
            reader = readers.resolve(on_file["reader"])
            for job in (Job(), object(), Routed()):
                assert reader({"job": job, "cfg": CFG,
                               "traffic": {"batch": 1}}, on_file) is None


def test_readers_of_the_counters_by_hand():
    # two layers, two steps each of a batch of two: DSA_COUNTERS' columns
    class Job:
        class mod:
            step_counters = {
                "block0/attn/dsa": {"sum": np.array(
                    [400, 1000, 30, 40, 90, 100, 2_000_000]), "steps": 2},
                "block1/attn/dsa": {"sum": np.array(
                    [200, 1000, 40, 40, 100, 100, 6_000_000]), "steps": 2},
                "block1/moe/moe": {"sum": np.array([1, 2, 3, 0, 4]),
                                   "steps": 2}}
    ctx = {"job": Job(), "traffic": {"batch": 2}}
    assert keye_readers.selected_pairs_pct(ctx, {}) == 100 * 600 / 2000
    assert keye_readers.tiles_run_pct(ctx, {}) == 100 * 260 / 280
    # summed over the layers, the mean over rows of the batch and steps
    assert keye_readers.indexer_kl(ctx, {}) == pytest.approx(
        (2.0 + 6.0) / (2 * 2))
    from dt_tpu.models import routed_lm
    assert len(routed_lm.DSA_COUNTERS) == 7


# -- every new metric file against the job's own scope paths -----------------

@pytest.fixture(scope="module")
def step_scopes():
    """The scope path of every operation of the toy job's train step, as
    jax writes it into the program it hands the compiler (the device trace
    carries the same strings, PERF.md section 3), with blocks
    rematerialised as in the cell."""
    import jax
    import jax.numpy as jnp
    import keye_drivers
    from dt_tpu.training import metrics as metrics_lib
    from dt_tpu.training.train_state import TrainState
    cfg = {**CFG, **TOY, "attention": None}
    job = keye_drivers.SparseIndexMoEJob(cfg, TOY_TRAFFIC, 1, 0)
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


@pytest.mark.parametrize("name", [n for n in NEW_METRICS
                                  if not n.startswith("dsa.")])
def test_new_metric_resolves_its_reader_and_finds_its_scope(step_scopes,
                                                            name):
    path = readers.metric_file(BENCH, name)
    assert os.path.basename(path) == name + ".json"
    on_file = load(path)
    reader = readers.resolve(on_file["reader"])
    if name.startswith("kernel."):
        # the kernels' events carry the names their pallas_calls give them
        # under the selection rule; read here from made-up operations
        bwd = "flash_bwd" in name
        ctx = {"trace": {"steps": 2, "op_seconds": {
            "flash_fwd_sel.2": 0.04, "flash_fwd_sel.3": 0.06,
            "flash_bwd_sel.1": 0.3, "flash_bwd.7": 5.0,
            "flash_fwd_bd.1": 3.0, "fusion.1": 1.0}, "op_events": {
            "flash_fwd_sel.2": 6, "flash_fwd_sel.3": 6, "flash_bwd_sel.1": 12,
            "flash_bwd.7": 48, "flash_fwd_bd.1": 4, "fusion.1": 2}},
            "traffic": {"batch": 1, "seq_len": 16384}, "cfg": CFG,
            "rehearsal": False, "device_kind": "TPU v5 lite",
            "bench_dir": BENCH}
        value = reader(ctx, on_file)
        per_step = 0.15 if bwd else 0.05
        if "roofline" in name:
            ops, _ = (keye_opcount.sel_flash_backward_ops_bytes if bwd else
                      keye_opcount.sel_flash_forward_ops_bytes)(
                1, 32, 16384, 2048, 128, 2)
            peak = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
            assert value == pytest.approx(
                100 * 6 * ops / peak["bf16_flops_per_s"] / per_step)
            assert value < 100
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
    layer and the recomputation apart, and the index's three parts are
    disjoint and inside the attention: no operation is in two."""
    import xplane
    args = lambda n: load(readers.metric_file(BENCH, n))["args"]  # noqa: E731
    split = [args(n) for n in ("model.dsa_attn_ms_per_step",
                               "model.moe_ms_per_step",
                               "model.remat_ms_per_step")]
    inner = [args(n) for n in ("model.indexer_ms_per_step",
                               "model.select_ms_per_step",
                               "model.indexer_kl_ms_per_step")]
    match = lambda s, a: xplane.scope_matches(  # noqa: E731
        s, a["holds"], a.get("lacks", ()))
    counts, parts = [0] * len(split), [0] * len(inner)
    for scope in step_scopes:
        hits = [match(scope, a) for a in split]
        assert sum(hits) <= 1, scope
        counts = [c + h for c, h in zip(counts, hits)]
        inside = [match(scope, a) for a in inner]
        assert sum(inside) <= hits[0], scope   # in one part, in the layer
        parts = [c + h for c, h in zip(parts, inside)]
        if not any(hits):    # the ends, the norms between, the optimizer
            assert not re.search(r"/(attn|moe)/", scope), scope
    assert all(counts) and all(parts), (counts, parts)
    # the projections and the scores are the index's, the counting passes
    # and the bitmaps the selection's, and the KL term has its own
    indexer, select, kl = (
        [s for s in step_scopes if match(s, a)] for a in inner)
    assert any("index_q/dot_general" in s for s in indexer)
    assert any("index_k_norm" in s for s in indexer)
    assert any("indexer/closed_call" in s for s in indexer)   # the scores
    assert any("select/closed_call" in s for s in select)     # the passes
    assert any("select/shift_left" in s for s in select)      # the packing
    assert any("indexer_kl/vmap()/closed_call" in s for s in kl)
    # an operation's own name (select_n) is no scope
    assert not any(s.endswith("rope/select_n") for s in select)
    # the scopes the issue names are all in some operation's path
    for part in ("attn/rope", "indexer", "select", "indexer_kl", "moe/route",
                 "moe/experts", "lm_head", "embed"):
        assert any(part in s for s in step_scopes), part
