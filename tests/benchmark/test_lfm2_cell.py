"""The short-convolution expert decoder's configuration, cell and metric
files: the contract, the operation counts against values worked by hand, a
toy-size rehearsal of the cell's job on the CPU (``DT_FORCE_CPU=1``) through
the real runner with the selection biases among the compared leaves, the
reader of the bias's counter, and every new metric file against the scope
paths of the job's own step.  The numbers a rehearsal prints are written
nowhere."""

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
import lfm2_opcount  # noqa: E402
import lfm2_readers  # noqa: E402
import readers  # noqa: E402

MANIFEST = load(os.path.join(REPO, "BENCHMARK.json"))
CELL = "lfm2-8b-a1b-ep4share-seq16384"
CONFIG = "lfm2-8b-a1b"
TRAFFIC = "tokens_b1_s16384_v16k"
CFG = load(os.path.join(BENCH, "configs", CONFIG + ".json"))
#: the cell's own per-layer metrics, by name
NEW_METRICS = [
    "model.short_conv_ms_per_step", "model.short_conv_taps_ms_per_step",
    "moe.bias_moved_assignments_pct"]
#: the accepted cells' metrics that this cell reports too, with the cells
#: that stand before it in each one's list
ROUTED = ["sdar30b-ep8share-bd4-seq4096",
          "keye30b-ep8share-dsa2048-seq16384",
          "laguna-xs2-ep8share-swa512-seq8192"]
SHARED = {
    **{name: ROUTED for name in (
        "model.moe_ms_per_step", "model.moe_route_ms_per_step",
        "model.moe_dispatch_ms_per_step", "model.moe_experts_ms_per_step",
        "kernel.gmm_ms_per_step.pl", "kernel.gmm_roofline.pl",
        "moe.held_load_share_pct", "moe.fullest_over_mean_load",
        "moe.buffer_fill_pct", "moe.overflow_assignments")},
    "model.remat_ms_per_step": ["granite4hm-b2-seq4096"] + ROUTED,
    "model.dense_mlp_ms_per_step": ROUTED[2:],
    # its attention layer runs under the scope the mixed-attention cell's
    # full layers run under: one name for one reading
    "model.full_attn_ms_per_step": ROUTED[2:],
    "kernel.flash_bwd_ms_per_step": ["gpt2m-seq1024",
                                     "granite4hm-b2-seq4096"],
    "kernel.flash_bwd_roofline": ["gpt2m-seq1024", "granite4hm-b2-seq4096"],
    "kernel.flash_fwd_ms_per_step.gqa": ["granite4hm-b2-seq4096"],
    "kernel.flash_fwd_roofline.gqa": ["granite4hm-b2-seq4096"]}
#: the cells accepted before this one: in a list that holds this cell's
#: name they stand before it
EARLIER = ["resnet50-synth", "gpt2m-seq1024", "granite4hm-b2-seq4096"] + ROUTED
PERIOD = ["full_attention", "conv", "conv", "conv"]
#: the catalog row's config (guide, architectures.jsonl), key for key
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv"] + PERIOD * 4 + PERIOD[:3]
    + ["full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}

#: the published configuration at widths in the tens, through the same job;
#: a speed at which three steps change the selection
TOY = {"name": "lfm2-toy", "hidden_size": 32, "num_attention_heads": 4,
       "num_key_value_heads": 2, "intermediate_size": 48,
       "moe_intermediate_size": 24, "num_experts_per_tok": 2,
       "num_experts": 4, "held_experts_first": 2, "vocab_size": 64,
       "buffer_rows": 512, "dtype": "float32",
       "expert_bias_update_speed": 0.02,
       "source": "toy-size copy of lfm2-8b-a1b for the CPU rehearsal",
       "published": {**CFG["published"], "num_experts": 8}}
TOY_TRAFFIC = {"generator": "traffic:uniform_tokens", "what": "toy",
               "batch": 2, "seq_len": 128, "distinct_batches": 3,
               "steps_per_reading": 1, "warm_steps": 0, "trace_last_s": 0.6}


def test_entry_and_file_meet_the_contract_and_no_width_differs():
    entry = next(e for e in MANIFEST["configs"] if e["name"] == CONFIG)
    contract.check_config(entry, CFG)
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "num_dense_layers", "num_experts",
                                "vocab_size"]
    assert len(PUBLISHED["layer_types"]) == 24
    assert PUBLISHED["layer_types"].count("full_attention") == 6
    assert CFG["published"] == {k: PUBLISHED[k] for k in entry["reduced"]}
    # every key of the source, with its value unless it is a reduced one
    for key, value in PUBLISHED.items():
        if key not in entry["reduced"]:
            assert CFG[key] == value, key
    assert (CFG["num_hidden_layers"], CFG["num_dense_layers"],
            CFG["num_experts"], CFG["vocab_size"]) == (5, 1, 8, 16384)
    # one of the two leading dense layers, then one whole period
    assert CFG["layer_types"] == ["conv"] + PERIOD
    assert CFG["layer_types"] == [PUBLISHED["layer_types"][i]
                                  for i in (0, 2, 3, 4, 5)]
    assert 4 * CFG["vocab_size"] == PUBLISHED["vocab_size"]
    assert 4 * CFG["num_experts"] == PUBLISHED["num_experts"]
    assert CFG["deployment"].startswith(
        "each routed layer's 32 experts over 4 chips, 8 here")
    for key in ("source", "reduced", "published", "deployment", "assumed",
                "departures"):
        assert CFG[key], key
    for key in ("tie_word_embeddings", "head_dim", "intermediate_size",
                "router", "norm_topk_eps", "expert_bias_update",
                "expert_bias_initial_std", "aux_loss_coef", "positions",
                "held_experts_first", "optimizer", "dtype", "initial_values",
                "remat_blocks", "attention", "buffer_rows", "batch"):
        assert key in CFG["assumed"], key
    assert not any(contract.WIDTH.search(k) for k in CFG["reduced"])
    assert CFG["check"]["limits_set_from"]
    assert (CFG["aux_loss_coef"], CFG["expert_bias_update_speed"],
            CFG["expert_bias_initial_std"], CFG["norm_topk_eps"],
            CFG["tie_word_embeddings"]) == (0.0, 0.001, 0.01, 1e-6, True)
    # the same recipe as the routed cells' configurations
    laguna = load(os.path.join(BENCH, "configs", "laguna-xs.2.json"))
    for key in ("optimizer", "dtype", "initializer_range",
                "residual_out_initializer_range", "held_experts_first",
                "remat_blocks", "hidden_size", "buffer_rows"):
        assert CFG[key] == laguna[key], key


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
    assert not {"kernel.flash_fwd_ms_per_step", "model.gqa_attn_ms_per_step",
                "model.ssm_conv_ms_per_step", "model.win_attn_ms_per_step",
                "kernel.flash_fwd_roofline.full", "model.bd_attn_ms_per_step",
                "kernel.flash_bwd_roofline.sel", "kernel.gmm_ms_per_step",
                "kernel.gmm_roofline", "win.tiles_run_pct"} & mine
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
        assert (m["layer"], m["source"], m["unit"]) == (
            ("model step: dt_tpu/models, optim", "device_trace", "ms")
            if name.startswith("model.") else
            ("expert routing: parallel/moe.py", "program_counter", "%"))
    # the accepted cells' that it shares list those cells, then this one
    for name, before in SHARED.items():
        assert contract.stands_once_after(by_name[name]["workloads"], CELL,
                                          before), name


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
    assert len(MANIFEST["workloads"]) >= 7
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"])
    # the tokens are drawn from the rows held, and the even held load is a
    # step's tokens: 2,048 rows an expert
    assert traffic["seq_len"] == CFG["vocab_size"]
    even = traffic["seq_len"] * CFG["num_experts_per_tok"] \
        * CFG["num_experts"] // CFG["published"]["num_experts"]
    assert (even, CFG["buffer_rows"]) == (16384, 24576)
    # the accepted kernel metrics' files read this configuration by these
    # keys: the grouped products' shape, the flash kernels' heads of 64
    gmm = load(readers.metric_file(BENCH, "kernel.gmm_roofline.pl"))
    assert [CFG[v] for v in gmm["args"]["shape"].values()
            if isinstance(v, str)] == [CFG["buffer_rows"], 2048, 1792, 8]
    fwd = load(readers.metric_file(BENCH, "kernel.flash_fwd_roofline.gqa"))
    assert fwd["args"]["shape"]["heads"] == "num_attention_heads"
    bwd = load(readers.metric_file(BENCH, "kernel.flash_bwd_roofline"))
    assert "num_attention_heads" in bwd["args"]["heads"] \
        and "n_head" not in CFG
    for m in (fwd, bwd):
        assert m["args"]["shape"]["head_dim"] == \
            CFG["hidden_size"] // CFG["num_attention_heads"] == 64


def test_parameters_and_operations_by_hand():
    d, s = 2048, 16384
    # the issue's table, part by part
    conv = d * 6144 + d * d
    attn = 2 * d * d + 2 * d * 512
    assert (conv + 3 * d, attn + 2 * 64) == (16783360, 10485888)
    assert lfm2_opcount.mixer_params(CFG, "conv") == conv
    assert lfm2_opcount.mixer_params(CFG, "full_attention") == attn
    dense = 3 * d * 7168
    assert lfm2_opcount.feed_forward_params(CFG, True) == dense == 44040192
    held = 8 * 3 * d * 1792 + d * 32
    assert held == 88145920
    norms = 2 * d
    layer0 = conv + 3 * d + dense + norms
    layer2 = attn + 2 * 64 + held + norms
    layer3 = conv + 3 * d + held + norms
    assert (layer0, layer2, layer3) == (60827648, 98635904, 104933376)
    total = layer0 + layer2 + 3 * layer3 + 16384 * d + d
    assert total == 507820160               # 507.8M: 8.13 GB at 16 bytes
    assert 8.12e9 < 16 * total < 8.13e9
    # a token meets one held expert's worth on average: 4 of 32, 8 held
    met = d * 32 + 3 * d * 1792 * 4 * 8 / 32
    assert lfm2_opcount.feed_forward_params(CFG, False) == met
    t = np.arange(24)
    assert lfm2_opcount.causal_pairs(24) == (t + 1).sum()
    assert lfm2_opcount.causal_pairs(s) == 134225920          # 134.2M
    traffic = {"seq_len": s, "batch": 1}
    weights = 4 * conv + attn + dense + 4 * met + d * 16384
    elementwise = 4 * d * (2 + 2 * 3)
    pairs = 134225920 / s * 4 * d
    flops = lfm2_opcount.lfm2_train_flops_per_item(CFG, traffic)
    assert flops == pytest.approx(6 * weights + 3 * (elementwise + pairs))
    # a step of 16,384 tokens: 19.6 TFLOP of matrix products of which the
    # four conv mixers are 6.6 (a third) and the routed layers 4.4 (a
    # quarter less), and 3.3 of attention's pairs: 22.9 as needed
    assert 19.5e12 < 6 * weights * s < 19.7e12
    assert 6.5e12 < 6 * 4 * conv * s < 6.7e12
    assert 4.3e12 < 6 * 4 * met * s < 4.5e12
    assert 3.2e12 < 3 * pairs * s < 3.4e12
    assert 22.8e12 < flops * s < 23.0e12
    assert 3 * elementwise * s < 0.01e12    # the taps and gates: bytes


# -- the rehearsal: a copy with the toy cell added as files -----------------

@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    path = bench_toy.make_copy(str(tmp_path_factory.mktemp("lfm2")))
    bench = os.path.join(os.path.dirname(path), "benchmark")
    man = load(path)
    toy = {**CFG, **TOY, "assumed": {}, "departures": ["toy size"],
           "check": {**CFG["check"], "limits": dict(bench_toy.TOY_LIMITS),
                     "limits_set_from": "tests/benchmark/bench_toy.py"}}
    bench_toy.dump(toy, os.path.join(bench, "configs", "lfm2-toy.json"))
    man["configs"].append({
        "name": "lfm2-toy", "source": "toy", "reduced": toy["reduced"],
        "why": "toy", "file": "benchmark/configs/lfm2-toy.json"})
    bench_toy.dump(TOY_TRAFFIC,
                   os.path.join(bench, "traffic", "tokens_b2_s128c.json"))
    man["workloads"].append({"name": "toy-lfm2", "config": "lfm2-toy",
                             "traffic": "tokens_b2_s128c", "chips": 1,
                             "why": "toy"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy-lfm2")
    bench_toy.dump(man, path)
    return path


def test_the_toy_copys_cut_configuration_meets_the_contract(manifest):
    man = load(manifest)
    entry = next(e for e in man["configs"] if e["name"] == "lfm2-toy")
    cfg = load(os.path.join(os.path.dirname(manifest), entry["file"]))
    contract.check_config(entry, cfg)
    assert cfg["driver"] == "lfm2_drivers:ShortConvMoEJob"


def test_rehearsal_of_the_cells_job_is_correct(manifest):
    """One traced run (a run without a trace takes the same steps and
    prints the two end-to-end metrics alone: ``readers.collect``)."""
    rc, last, out = bench_toy.run_cell(manifest, "toy-lfm2", trace=1)
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
    # routed layers' (the dense layer sows none), and the bias's
    assert values["moe.overflow_assignments"] == 0
    assert 0 < values["moe.held_load_share_pct"] < 100
    assert 0 < values["moe.buffer_fill_pct"] < 100
    assert 0 < values["moe.bias_moved_assignments_pct"] < 50
    # the parameters' change is compared, the biases are not among its
    # leaves (tests/test_short_conv_lm.py holds them to the reference's)
    leaves = re.findall(r"compare config=lfm2-toy number=(\S+) value=(\S+)",
                        out)
    assert dict(leaves)["param_change.worst_leaf"] not in ("nan", "inf")
    # the CPU's trace has no device plane: every reader of the device's
    # time finds nothing, returns nothing, and the line leaves it out
    assert got.isdisjoint(n for n in NEW_METRICS if n.startswith("model."))
    assert "scopes_missing" not in out


def test_the_job_seeds_the_biases_and_compares_the_parameters_alone():
    """``program_tree`` holds the parameters and nothing else;
    ``state_tree`` each routed layer's bias; ``make_state`` puts the
    reference's seeded biases, which are not zero, where the program keeps
    them, and the trees the comparison reads have the parameters' leaves."""
    import jax
    import lfm2_drivers
    import run
    cfg = {**CFG, **TOY, "attention": None}
    job = lfm2_drivers.ShortConvMoEJob(cfg, TOY_TRAFFIC, 1, 0)
    ref = run.load_reference(os.path.join(REPO, cfg["reference"]))
    key = jax.random.PRNGKey(3)
    drawn = ref.init(key, cfg)
    tree, stats = job.program_tree(drawn), job.state_tree(drawn)
    assert sorted(stats) == ["block1", "block2", "block3", "block4"]
    assert lfm2_drivers.STATE not in tree and "lm_head" not in tree \
        and "moe" not in tree["block0"] and "conv" in tree["block0"] \
        and "attn" in tree["block1"]
    state = job.make_state(ref.init, key)
    for name, blk in stats.items():
        bias = np.asarray(blk["moe"]["selection_bias"])
        assert bias.shape == (8,) and np.abs(bias).min() > 0
        np.testing.assert_allclose(      # drawn under jit there, not here
            state.batch_stats[name]["moe"]["selection_bias"], bias, rtol=1e-6)
    np.testing.assert_allclose(state.params["embedding"], drawn["embed"],
                               rtol=1e-6)
    structure = jax.tree_util.tree_structure
    assert structure(job.param_change_host(key, state)) == structure(tree) \
        == structure(jax.device_get(state.params))


def test_reader_of_the_counter_by_hand_and_on_a_program_without_it():
    # two layers, two steps each of a batch of two: BIAS_COUNTERS' columns
    class Job:
        class mod:
            step_counters = {
                "block1/moe/moe_bias": {"sum": np.array([30, 1000]),
                                        "steps": 2},
                "block2/moe/moe_bias": {"sum": np.array([50, 1000]),
                                        "steps": 2},
                "block1/moe/moe": {"sum": np.array([1, 2, 3, 0, 4]),
                                   "steps": 2}}
    assert lfm2_readers.bias_moved_assignments_pct({"job": Job()}, {}) == 4.0
    from dt_tpu.parallel import moe
    assert moe.BIAS_COUNTERS == ("moved", "assignments")

    # the parent's Module has no step_counters, and the routed cells' none
    # of this layer's: the reader returns nothing, the line leaves it out
    class Parent:
        class mod:
            pass

    class Routed:
        class mod:
            step_counters = {"block0/moe/moe": {
                "sum": np.array([1, 2, 3, 0, 4]), "steps": 1}}
    on_file = load(readers.metric_file(BENCH,
                                       "moe.bias_moved_assignments_pct"))
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
    import lfm2_drivers
    from dt_tpu.training import metrics as metrics_lib
    from dt_tpu.training.train_state import TrainState
    cfg = {**CFG, **TOY, "attention": None}
    job = lfm2_drivers.ShortConvMoEJob(cfg, TOY_TRAFFIC, 1, 0)
    mod = job.mod
    mod._metric_stats = metrics_lib.device_form(metrics_lib.create("ce"))
    mod._build_steps()
    tokens = jnp.zeros((2, 128), jnp.int32)

    def state():
        made = mod.model.init(jax.random.PRNGKey(0), tokens)
        return TrainState.create(mod.model.apply, made["params"], mod.tx,
                                 made["batch_stats"])
    text = mod._train_step.lower(jax.eval_shape(state), tokens, tokens,
                                 jax.random.PRNGKey(0)).as_text(
                                     debug_info=True)
    return sorted(set(re.findall(r'"(jit\(train_step\)/[^"]*)"', text)))


@pytest.mark.parametrize("name", [n for n in NEW_METRICS
                                  if n.startswith("model.")]
                         + ["model.full_attn_ms_per_step"])
def test_new_metric_resolves_its_reader_and_finds_its_scope(step_scopes,
                                                            name):
    path = readers.metric_file(BENCH, name)
    assert os.path.basename(path) == name + ".json"
    on_file = load(path)
    reader = readers.resolve(on_file["reader"])
    # a trace in which every operation of the step took one millisecond
    trace = {"steps": 1, "devices": 1, "busy_s": 1e-3 * len(step_scopes),
             "scope_seconds": {s: 1e-3 for s in step_scopes}}
    value = reader({"trace": trace}, on_file)
    assert value is not None and value > 0, on_file["args"]
    # and a program without these scopes reads as nothing to count
    bare = {**trace, "scope_seconds": {
        "jit(train_step)/jvp(forward)/block0/mlp_in/dot_general": 1.0},
        "busy_s": 1.0}
    assert not reader({"trace": bare}, on_file)
    assert reader({"trace": None}, on_file) is None


def test_the_layers_parts_lie_inside_it_and_tell_each_other_apart(
        step_scopes):
    """The scopes the metric files hold tell the conv mixers, the attention
    layer, the routed layers, the dense layer and the recomputation apart;
    the taps and the gates lie inside a conv mixer and are the part of it
    that is no matrix product: no operation is in two of the five."""
    import xplane
    args = lambda n: load(readers.metric_file(BENCH, n))["args"]  # noqa: E731
    split = [args(n) for n in ("model.short_conv_ms_per_step",
                               "model.full_attn_ms_per_step",
                               "model.moe_ms_per_step",
                               "model.dense_mlp_ms_per_step",
                               "model.remat_ms_per_step")]
    taps = args("model.short_conv_taps_ms_per_step")
    match = lambda s, a: xplane.scope_matches(  # noqa: E731
        s, a["holds"], a.get("lacks", ()))
    in_taps = lambda s: sum(match(s, {"holds": [part], "lacks": taps[  # noqa: E731
        "lacks"]}) for part in taps["any_of"])
    counts, tapped = [0] * len(split), 0
    for scope in step_scopes:
        hits = [match(scope, a) for a in split]
        assert sum(hits) <= 1, scope
        counts = [c + h for c, h in zip(counts, hits)]
        assert in_taps(scope) <= hits[0], scope     # inside, and in one part
        tapped += in_taps(scope)
        if in_taps(scope):
            assert "dot_general" not in scope, scope
        if not any(hits):    # the ends, the norms between, the optimizer
            assert not re.search(r"/(conv|attn|moe|mlp)/", scope), scope
    assert all(counts) and tapped, (counts, tapped)
    # the mixer's two matrix products are in the whole and not in the taps
    for proj in ("in_proj", "out_proj"):
        mine = [s for s in step_scopes if f"/conv/{proj}/dot_general" in s]
        assert mine and not any(in_taps(s) for s in mine)
    # layer by layer, as the configuration's list says: no layer is named
    # by number in a metric's file, the module's name tells them apart
    for i, kind in enumerate(CFG["layer_types"]):
        conv = [s for s in step_scopes if f"/block{i}/conv/" in s]
        attn = [s for s in step_scopes if f"/block{i}/attn/" in s]
        if kind == "conv":
            assert conv and not attn, i
            for part in ("gate_in", "conv1d", "gate_out"):
                assert any(f"/conv/{part}/" in s for s in conv), (i, part)
        else:
            assert attn and not conv, i
            assert all("/attn/full/" in s for s in attn)
            assert any("/attn/full/q_norm/" in s for s in attn)
            assert any("/attn/full/rope/" in s for s in attn)
    assert any("/block0/mlp/gate/dot_general" in s for s in step_scopes)
    assert not any("/block0/moe/" in s for s in step_scopes)
    for part in ("moe/route", "moe/dispatch", "moe/experts", "moe/combine",
                 "lm_head", "embed"):
        assert any(part in s for s in step_scopes), part
    # the bias's add, its update and its counter are inside route
    assert any("/moe/route/" in s and "_route_biased" in s
               for s in step_scopes)
    for name in NEW_METRICS:    # no file names a layer by its number
        assert "block" not in json.dumps(load(readers.metric_file(
            BENCH, name)).get("args", {}))
