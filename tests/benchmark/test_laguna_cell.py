"""The mixed-attention expert decoder's configuration, cell and metric files:
the contract, the operation counts against values worked by hand, a toy-size
rehearsal of the cell's job on the CPU (``DT_FORCE_CPU=1``) through the real
runner, the readers of the windowed layers' counters,
and every new metric file against the scope paths of the job's own step.  The
numbers a rehearsal prints are written nowhere."""

import json
import os
import re
import sys

import numpy as np
import pytest

import bench_toy
import contract
from bench_toy import BENCH, REPO, load

sys.path.insert(0, BENCH)
import laguna_opcount  # noqa: E402
import laguna_readers  # noqa: E402
import readers  # noqa: E402

MANIFEST = load(os.path.join(REPO, "BENCHMARK.json"))
CELL = "laguna-xs2-ep8share-swa512-seq8192"
CONFIG = "laguna-xs.2"
TRAFFIC = "tokens_b2_s8192"
CFG = load(os.path.join(BENCH, "configs", CONFIG + ".json"))
#: the cell's own per-layer metrics, by name
NEW_METRICS = [
    "model.win_attn_ms_per_step", "model.full_attn_ms_per_step",
    "model.attn_gate_ms_per_step", "model.moe_shared_ms_per_step",
    "model.dense_mlp_ms_per_step",
    "kernel.flash_fwd_ms_per_step.win", "kernel.flash_fwd_roofline.win",
    "kernel.flash_bwd_ms_per_step.win", "kernel.flash_bwd_roofline.win",
    "kernel.flash_fwd_ms_per_step.full", "kernel.flash_fwd_roofline.full",
    "kernel.flash_bwd_ms_per_step.full", "kernel.flash_bwd_roofline.full",
    "win.needed_pairs_pct", "win.tiles_run_pct"]
#: the routed cells' metrics that this cell reports too
SHARED = [
    "model.remat_ms_per_step", "model.moe_ms_per_step",
    "model.moe_route_ms_per_step", "model.moe_dispatch_ms_per_step",
    "model.moe_experts_ms_per_step", "kernel.gmm_ms_per_step.pl",
    "kernel.gmm_roofline.pl", "moe.held_load_share_pct",
    "moe.fullest_over_mean_load", "moe.buffer_fill_pct",
    "moe.overflow_assignments"]
#: the cells accepted before this one: in a list that holds this cell's
#: name they stand before it
EARLIER = ["resnet50-synth", "gpt2m-seq1024", "granite4hm-b2-seq4096",
           "sdar30b-ep8share-bd4-seq4096",
           "keye30b-ep8share-dsa2048-seq16384"]
KINDS = ["full_attention"] + ["sliding_attention"] * 3
#: the catalog row's config (guide, architectures.jsonl), key for key
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": KINDS * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}

#: the published configuration at widths in the tens, through the same job
TOY = {"name": "laguna-toy", "hidden_size": 32, "head_dim": 16,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
       "intermediate_size": 48, "moe_intermediate_size": 24,
       "shared_expert_intermediate_size": 24, "num_experts_per_tok": 2,
       "num_experts": 4, "held_experts_first": 2, "sliding_window": 40,
       "vocab_size": 64, "buffer_rows": 512, "dtype": "float32",
       "rope_parameters": {
           **CFG["rope_parameters"], "full_attention": {
               **CFG["rope_parameters"]["full_attention"],
               "original_max_position_embeddings": 32, "factor": 8.0,
               "beta_fast": 4}},
       "source": "toy-size copy of laguna-xs.2 for the CPU rehearsal",
       "published": {**CFG["published"], "num_experts": 8}}
TOY_TRAFFIC = {"generator": "traffic:uniform_tokens", "what": "toy",
               "batch": 2, "seq_len": 128, "distinct_batches": 3,
               "steps_per_reading": 1, "warm_steps": 0, "trace_last_s": 0.6}


def test_entry_and_file_meet_the_contract_and_no_width_differs():
    entry = next(e for e in MANIFEST["configs"] if e["name"] == CONFIG)
    contract.check_config(entry, CFG)
    assert entry["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size"]
    assert CFG["published"] == {k: PUBLISHED[k] for k in entry["reduced"]}
    # every key of the source, with its value unless it is a reduced one;
    # the nested group whole: both rotary rules as published
    for key, value in PUBLISHED.items():
        if key not in entry["reduced"]:
            assert CFG[key] == value, key
    assert (CFG["num_hidden_layers"], CFG["num_experts"],
            CFG["vocab_size"]) == (5, 32, 12544)
    # the lists cut to the leading dense layer and one whole period
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert CFG[key] == PUBLISHED[key][:5], key
    assert CFG["layer_types"][1:] == KINDS[1:] + KINDS[:1]
    assert CFG["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert 8 * CFG["vocab_size"] == PUBLISHED["vocab_size"]
    assert 8 * CFG["num_experts"] == PUBLISHED["num_experts"]
    assert CFG["deployment"].startswith("each layer's 256 experts over 8")
    for key in ("source", "reduced", "published", "deployment", "assumed",
                "departures"):
        assert CFG[key], key
    for key in ("gating", "qk_norm", "router", "norm_topk_prob",
                "shared_expert", "aux_loss_coef", "optimizer", "dtype",
                "initial_values", "held_experts_first", "buffer_rows",
                "batch", "positions", "remat_blocks"):
        assert key in CFG["assumed"], key
    assert not any(contract.WIDTH.search(k) for k in CFG["reduced"])
    assert CFG["check"]["limits_set_from"]
    # the same deployment and recipe as the routed cells' configurations
    keye = load(os.path.join(BENCH, "configs", "keye-vl-2.0-30b-a3b.json"))
    for key in ("optimizer", "dtype", "aux_loss_coef", "initializer_range",
                "residual_out_initializer_range", "held_experts_first",
                "remat_blocks", "hidden_size", "head_dim"):
        assert CFG[key] == keye[key], key


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
    # the other cells' kernel names and attention scopes are not this
    # one's, nor the pair that reads XLA's kernel, off the path since PR 38
    assert not {"kernel.flash_fwd_ms_per_step", "kernel.flash_bwd_roofline",
                "kernel.flash_fwd_roofline.gqa", "model.bd_attn_ms_per_step",
                "kernel.flash_fwd_roofline.bd", "model.dsa_attn_ms_per_step",
                "kernel.flash_bwd_roofline.sel", "kernel.gmm_ms_per_step",
                "kernel.gmm_roofline"} & mine
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
        assert m["layer"] == ("model step: dt_tpu/models, optim"
                              if name.startswith("model.")
                              else "kernels: ops/pallas")
        assert m["source"] == ("program_counter" if name.startswith("win.")
                               else "device_trace")
    # the routed cells' that it shares list those cells, then this one
    for name in SHARED:
        assert contract.stands_once_after(
            by_name[name]["workloads"], CELL,
            ["sdar30b-ep8share-bd4-seq4096",
             "keye30b-ep8share-dsa2048-seq16384"]), name


def test_the_cell_reports_what_the_lm_cells_report_and_its_own():
    manifest_assertions(MANIFEST)
    traffic = load(os.path.join(BENCH, "traffic", TRAFFIC + ".json"))
    assert (traffic["batch"], traffic["seq_len"], traffic["distinct_batches"],
            traffic["steps_per_reading"], traffic["warm_steps"]) == (
        2, 8192, 3, 1, 0)
    assert traffic["generator"] == "traffic:uniform_tokens"
    for name in NEW_METRICS:
        path = readers.metric_file(BENCH, name)
        assert os.path.basename(path) == name + ".json"
    assert len(MANIFEST["workloads"]) >= 6
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"])
    # twice the original context: the YaRN schedule is in its scaled range
    rope = CFG["rope_parameters"]["full_attention"]
    assert traffic["seq_len"] == 2 * rope["original_max_position_embeddings"]
    assert traffic["seq_len"] == 16 * CFG["sliding_window"]
    # the kernel metrics' files read the configuration by these keys
    gmm = load(readers.metric_file(BENCH, "kernel.gmm_roofline.pl"))
    assert [CFG[v] for v in gmm["args"]["shape"].values()
            if isinstance(v, str)] == [CFG["buffer_rows"], 2048, 512, 32]


def test_parameters_and_operations_by_hand():
    d, s = 2048, 8192
    # the issue's table, part by part
    full = d * 6144 + 2 * d * 1024 + 6144 * d + d * 48
    sliding = d * 8192 + 2 * d * 1024 + 8192 * d + d * 64
    assert (full, sliding) == (29458432, 37879808)
    assert laguna_opcount.attention_params(CFG, 48) == full
    assert laguna_opcount.attention_params(CFG, 64) == sliding
    dense = 3 * d * 8192
    assert laguna_opcount.feed_forward_params(CFG, "dense") == dense
    # the experts held, the shared one, the router
    held = 32 * 3 * d * 512 + 3 * d * 512 + d * 256
    assert held == 104333312
    total = (full + dense) + 3 * (sliding + held) + (full + held) \
        + 2 * 12544 * d
    norms = 5 * 2 * d + d
    assert total + norms == 691623936        # 691.6M: 11.07 GB at 16 bytes
    assert 11.06e9 < 16 * (total + norms) < 11.07e9
    # a token meets one held expert's worth on average: 8 of 256, 32 held
    met = d * 256 + 3 * d * 512 + 3 * d * 512 * 8 * 32 / 256
    assert laguna_opcount.feed_forward_params(CFG, "sparse") == met
    # the pairs, counted one by one at a small size
    for seq, window in ((16, 4), (24, 24), (8, 100)):
        t = np.arange(seq)
        assert laguna_opcount.band_pairs(seq, window) == \
            np.minimum(t + 1, window).sum()
        assert laguna_opcount.causal_pairs(seq) == (t + 1).sum()
    assert laguna_opcount.band_pairs(s, 512) == 4063488         # 4.06M
    assert laguna_opcount.causal_pairs(s) == 33558528           # 33.56M
    traffic = {"seq_len": s, "batch": 2}
    weights = 2 * (full) + 3 * sliding + dense + 4 * met + d * 12544
    attn = (2 * 33558528 * 48 + 3 * 4063488 * 64) / s * 4 * 128
    flops = laguna_opcount.laguna_train_flops_per_item(CFG, traffic)
    assert flops == pytest.approx(6 * weights + 3 * attn)
    # a step of 16,384 tokens: 27.1 TFLOP of matrix products (ISSUE 41
    # reckoned 28), 9.9 of full attention and 2.4 of windowed attention
    assert 27.0e12 < 6 * weights * 2 * s < 27.2e12
    assert 39.3e12 < flops * 2 * s < 39.5e12
    assert 9.8e12 < 3 * 2 * 33558528 * 48 * 512 * 2 < 10.0e12
    ops, nbytes = laguna_opcount.win_flash_forward_ops_bytes(2, 64, s, 512,
                                                             128, 2)
    assert ops == 2 * 64 * 4063488 * 2 * 2 * 128
    assert nbytes == 2 * 64 * s * (4 * 128 * 2 + 4)
    ops_b, nbytes_b = laguna_opcount.win_flash_backward_ops_bytes(
        2, 64, s, 512, 128, 2)
    assert ops_b == 5 * ops // 2
    assert nbytes_b == 2 * 64 * s * (8 * 128 * 2 + 4)
    # the band is an eighth of the triangle
    assert 0.12 < 4063488 / 33558528 < 0.125


# -- the rehearsal: a copy with the toy cell added as files -----------------

@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    path = bench_toy.make_copy(str(tmp_path_factory.mktemp("laguna")))
    bench = os.path.join(os.path.dirname(path), "benchmark")
    man = load(path)
    toy = {**CFG, **TOY, "assumed": {}, "departures": ["toy size"],
           "check": {**CFG["check"], "limits": dict(bench_toy.TOY_LIMITS),
                     "limits_set_from": "tests/benchmark/bench_toy.py"}}
    bench_toy.dump(toy, os.path.join(bench, "configs", "laguna-toy.json"))
    man["configs"].append({
        "name": "laguna-toy", "source": "toy", "reduced": toy["reduced"],
        "why": "toy", "file": "benchmark/configs/laguna-toy.json"})
    bench_toy.dump(TOY_TRAFFIC,
                   os.path.join(bench, "traffic", "tokens_b2_s128w.json"))
    man["workloads"].append({"name": "toy-laguna", "config": "laguna-toy",
                             "traffic": "tokens_b2_s128w", "chips": 1,
                             "why": "toy"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy-laguna")
    bench_toy.dump(man, path)
    return path


def test_the_toy_copys_cut_configuration_meets_the_contract(manifest):
    man = load(manifest)
    entry = next(e for e in man["configs"] if e["name"] == "laguna-toy")
    cfg = load(os.path.join(os.path.dirname(manifest), entry["file"]))
    contract.check_config(entry, cfg)
    assert cfg["driver"] == "laguna_drivers:MixedAttentionMoEJob"


def test_rehearsal_of_the_cells_job_is_correct(manifest):
    """One traced run (a run without a trace takes the same steps and
    prints the two end-to-end metrics alone: ``readers.collect``)."""
    rc, last, out = bench_toy.run_cell(manifest, "toy-laguna", trace=1)
    assert rc == 0 and last is not None, out[-3000:]
    assert last["correct"] is True, out[-3000:]
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0 and last["failed"] == 0
    assert len(last["compared"]) == 8
    got = set(last["metrics"])
    values = {k: last["metrics"][k]["value"] for k in got}
    assert values["loop.metric_device_steps_pct.lm"] == 100.0
    assert values["compile.in_window.lm"] == 0
    # the counters reached the host with the metric's statistics: the four
    # routed layers' (the dense layer sows none) and the three bands'
    assert values["moe.overflow_assignments"] == 0
    assert 0 < values["moe.held_load_share_pct"] < 100
    assert 0 < values["moe.buffer_fill_pct"] < 100
    pairs = np.minimum(np.arange(128) + 1, 40).sum()
    assert values["win.needed_pairs_pct"] == pytest.approx(
        100 * pairs / (128 * 128))
    assert values["win.tiles_run_pct"] == 100.0      # one tile of 128
    # the CPU's trace has no device plane: every reader of the device's
    # time finds nothing, returns nothing, and the line leaves it out
    assert got.isdisjoint(n for n in NEW_METRICS if not n.startswith("win."))
    assert "scopes_missing" not in out


def test_readers_of_the_counters_by_hand_and_on_a_program_without_them():
    # two layers, two steps each of a batch of two: WIN_COUNTERS' columns
    class Job:
        class mod:
            step_counters = {
                "block1/attn/win": {"sum": np.array(
                    [400, 1000, 1000, 30, 40, 90, 100]), "steps": 2},
                "block2/attn/win": {"sum": np.array(
                    [400, 800, 1200, 40, 40, 100, 100]), "steps": 2},
                "block1/moe/moe": {"sum": np.array([1, 2, 3, 0, 4]),
                                   "steps": 2}}
    ctx = {"job": Job(), "traffic": {"batch": 2}}
    assert laguna_readers.needed_pairs_pct(ctx, {}) == 100 * 1600 / 4000
    assert laguna_readers.tiles_run_pct(ctx, {}) == 100 * 260 / 280
    from dt_tpu.models import routed_lm
    assert len(routed_lm.WIN_COUNTERS) == 7

    # the parent's Module has no step_counters, and the routed cells' none
    # of these layers: each reader returns nothing, the line leaves it out
    class Parent:
        class mod:
            pass

    class Routed:
        class mod:
            step_counters = {"block0/moe/moe": {
                "sum": np.array([1, 2, 3, 0, 4]), "steps": 1}}
    for name in ("win.needed_pairs_pct", "win.tiles_run_pct"):
        on_file = load(readers.metric_file(BENCH, name))
        reader = readers.resolve(on_file["reader"])
        for job in (Parent(), object(), Routed()):
            assert reader({"job": job, "cfg": CFG,
                           "traffic": {"batch": 1}}, on_file) is None


# -- every new metric file against the job's own scope paths -----------------

@pytest.fixture(scope="module")
def step_scopes():
    """The scope path of every operation of the toy job's train step, as
    jax writes it into the program it hands the compiler (the device trace
    carries the same strings, PERF.md section 3), with blocks
    rematerialised as in the cell."""
    import jax
    import jax.numpy as jnp
    import laguna_drivers
    from dt_tpu.training import metrics as metrics_lib
    from dt_tpu.training.train_state import TrainState
    cfg = {**CFG, **TOY, "attention": None}
    job = laguna_drivers.MixedAttentionMoEJob(cfg, TOY_TRAFFIC, 1, 0)
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
                                  if not n.startswith("win.")])
def test_new_metric_resolves_its_reader_and_finds_its_scope(step_scopes,
                                                            name):
    path = readers.metric_file(BENCH, name)
    assert os.path.basename(path) == name + ".json"
    on_file = load(path)
    reader = readers.resolve(on_file["reader"])
    if name.startswith("kernel."):
        # the band's kernels carry the names their pallas_calls give them,
        # the plain causal backward its own, the plain causal forward its
        # caller's; read here from made-up operations
        bwd, win = "flash_bwd" in name, name.endswith(".win")
        ctx = {"trace": {"steps": 2, "op_seconds": {
            "flash_win_fwd.2": 0.01, "flash_win_fwd.3": 0.014,
            "flash_win_bwd.1": 0.03, "flash_bwd.7": 0.12,
            "attn._causal.4": 0.05,
            "flash_fwd_bd.1": 3.0, "fusion.1": 1.0}, "op_events": {
            "flash_win_fwd.2": 6, "flash_win_fwd.3": 6, "flash_win_bwd.1": 6,
            "flash_bwd.7": 4, "attn._causal.4": 4, "flash_fwd_bd.1": 4,
            "fusion.1": 2}},
            "traffic": {"batch": 2, "seq_len": 8192}, "cfg": CFG,
            "rehearsal": False, "device_kind": "TPU v5 lite",
            "bench_dir": BENCH}
        value = reader(ctx, on_file)
        per_step, calls = {(False, True): (0.012, 6), (True, True): (0.015, 3),
                           (False, False): (0.025, 2),
                           (True, False): (0.06, 2)}[(bwd, win)]
        if "roofline" in name:
            import flash_bwd
            import opcount
            if win:
                ops, _ = (laguna_opcount.win_flash_backward_ops_bytes if bwd
                          else laguna_opcount.win_flash_forward_ops_bytes)(
                    2, 64, 8192, 512, 128, 2)
            else:
                ops, _ = (flash_bwd.flash_backward_ops_bytes if bwd else
                          opcount.flash_forward_ops_bytes)(2, 48, 8192, 128,
                                                           2)
            peak = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
            assert value == pytest.approx(
                100 * calls * ops / peak["bf16_flops_per_s"] / per_step)
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
    """The scopes the metric files hold tell the two kinds of attention,
    the routed layers, the dense layer and the recomputation apart; the
    gate lies inside an attention layer and the shared expert inside a
    routed one: no operation is in two of the five."""
    import xplane
    args = lambda n: load(readers.metric_file(BENCH, n))["args"]  # noqa: E731
    split = [args(n) for n in ("model.win_attn_ms_per_step",
                               "model.full_attn_ms_per_step",
                               "model.moe_ms_per_step",
                               "model.dense_mlp_ms_per_step",
                               "model.remat_ms_per_step")]
    gate, shared = (args(n) for n in ("model.attn_gate_ms_per_step",
                                      "model.moe_shared_ms_per_step"))
    match = lambda s, a: xplane.scope_matches(  # noqa: E731
        s, a["holds"], a.get("lacks", ()))
    counts, gates, shares = [0] * len(split), 0, 0
    for scope in step_scopes:
        hits = [match(scope, a) for a in split]
        assert sum(hits) <= 1, scope
        counts = [c + h for c, h in zip(counts, hits)]
        assert match(scope, gate) <= (hits[0] or hits[1]), scope
        assert match(scope, shared) <= hits[2], scope
        gates, shares = gates + match(scope, gate), shares + match(scope,
                                                                   shared)
        if not any(hits):    # the ends, the norms between, the optimizer
            assert not re.search(r"/(attn|moe|mlp)/", scope), scope
    assert all(counts) and gates and shares, (counts, gates, shares)
    # layer by layer, as the configuration's lists say: no layer is named
    # by number in a metric's file, the kind's scope tells them apart
    for i, kind in enumerate(["full", "window", "window", "window", "full"]):
        mine = [s for s in step_scopes if f"/block{i}/attn/" in s]
        assert mine and all(f"/attn/{kind}/" in s for s in mine), (i, kind)
        assert any(f"/attn/{kind}/gate/gate_proj/dot_general" in s
                   for s in mine)
        assert any(f"/attn/{kind}/rope/" in s for s in mine)
    assert any("/block0/mlp/gate/dot_general" in s for s in step_scopes)
    assert not any("/block0/moe/" in s for s in step_scopes)
    for part in ("moe/route", "moe/dispatch", "moe/experts", "moe/combine",
                 "moe/shared/shared_gate", "moe/shared/shared_down",
                 "lm_head", "embed"):
        assert any(part in s for s in step_scopes), part
    for name in NEW_METRICS:    # no file names a layer by its number
        assert "block" not in json.dumps(load(readers.metric_file(
            BENCH, name)).get("args", {}))
