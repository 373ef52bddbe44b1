"""The single-part hybrid decoder's configuration, cell and metric files: the
contract, the parameter and operation counts against values worked by hand
at the published sizes, a toy-size rehearsal of the cell's job on the CPU
(``DT_FORCE_CPU=1``) through the real runner, and every new metric file
against the scope paths of the job's own step.  The numbers a rehearsal
prints are written nowhere."""

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
import nemotron_opcount  # noqa: E402
import readers  # noqa: E402

MANIFEST = load(os.path.join(REPO, "BENCHMARK.json"))
CELL = "nemotron3super-tp8ep64share-seq8192"
CONFIG = "nemotron-3-super-120b-a12b"
TRAFFIC = "tokens_b1_s8192_v16k"
CFG = load(os.path.join(BENCH, "configs", CONFIG + ".json"))
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "mamba_num_heads",
           "n_groups", "num_attention_heads", "num_key_value_heads",
           "n_routed_experts", "vocab_size"]
#: the cell's own per-layer metrics, by name
NEW_METRICS = [
    "model.moe_latent_ms_per_step", "model.moe_dispatch_ms_per_step.lat",
    "kernel.gmm_roofline.lat", "kernel.flash_fwd_roofline.hd128",
    "kernel.flash_bwd_roofline.hd128"]
#: the accepted cells' metrics that this cell reports too, with the cells
#: that stand before it in each one's list
HYBRID = ["granite4hm-b2-seq4096"]
ROUTED = ["sdar30b-ep8share-bd4-seq4096",
          "keye30b-ep8share-dsa2048-seq16384",
          "laguna-xs2-ep8share-swa512-seq8192",
          "lfm2-8b-a1b-ep4share-seq16384"]
SHARED = {
    **{name: HYBRID for name in (
        "model.ssm_mixer_ms_per_step", "model.ssm_scan_ms_per_step",
        "model.ssm_scan_bwd_ms_per_step", "model.ssm_conv_ms_per_step",
        "kernel.ssd_fwd_ms_per_step", "kernel.ssd_bwd_ms_per_step",
        "model.gqa_attn_ms_per_step")},
    **{name: ROUTED for name in (
        "model.moe_ms_per_step", "model.moe_route_ms_per_step",
        "model.moe_experts_ms_per_step", "kernel.gmm_ms_per_step.pl",
        "moe.held_load_share_pct", "moe.fullest_over_mean_load",
        "moe.buffer_fill_pct", "moe.overflow_assignments")},
    "model.moe_shared_ms_per_step": ROUTED[2:3],
    "moe.bias_moved_assignments_pct": ROUTED[3:],
    "model.remat_ms_per_step": HYBRID + ROUTED,
    "kernel.flash_fwd_ms_per_step.gqa": HYBRID + ROUTED[3:],
    "kernel.flash_bwd_ms_per_step": ["gpt2m-seq1024"] + HYBRID + ROUTED[3:]}
#: the cells accepted before this one: in a list that holds this cell's
#: name they stand before it
EARLIER = ["resnet50-synth", "gpt2m-seq1024"] + HYBRID + ROUTED
#: the catalog row's config (guide, architectures.jsonl), key for key
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern":
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}

#: the published configuration at widths in the tens, through the same job:
#: 2 groups of state-space heads of which the second is held, the second
#: key-value head with its two query heads, the second quarter of the
#: experts; a speed at which three steps change the selection
TOY = {"name": "nemotron-toy", "hidden_size": 32, "mamba_head_dim": 8,
       "ssm_state_size": 8, "chunk_size": 16, "head_dim": 8,
       "moe_intermediate_size": 24, "moe_latent_size": 16,
       "moe_shared_expert_intermediate_size": 40, "num_experts_per_tok": 3,
       "mamba_num_heads": 4, "n_groups": 1, "num_attention_heads": 2,
       "num_key_value_heads": 1, "n_routed_experts": 4, "vocab_size": 64,
       "hybrid_override_pattern": "ME*E", "num_hidden_layers": 4,
       "held_experts_first": 4, "held_mamba_heads_first": 4,
       "held_attention_heads_first": 2, "buffer_rows": 256,
       "dtype": "float32", "expert_bias_update_speed": 0.02,
       "source": "toy-size copy of nemotron-3-super-120b-a12b for the CPU "
                 "rehearsal",
       "published": {**CFG["published"], "mamba_num_heads": 8, "n_groups": 2,
                     "num_attention_heads": 4, "num_key_value_heads": 2,
                     "n_routed_experts": 16}}
TOY_TRAFFIC = {"generator": "traffic:uniform_tokens", "what": "toy",
               "batch": 2, "seq_len": 64, "distinct_batches": 3,
               "steps_per_reading": 1, "warm_steps": 0, "trace_last_s": 0.6}


def test_entry_and_file_meet_the_contract_and_no_width_differs():
    entry = next(e for e in MANIFEST["configs"] if e["name"] == CONFIG)
    contract.check_config(entry, CFG)
    assert entry["reduced"] == REDUCED
    assert CFG["published"] == {k: PUBLISHED[k] for k in REDUCED}
    # every key of the source, with its value unless it is a reduced one
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert CFG[key] == value, key
    assert [CFG[k] for k in REDUCED] == [11, "MEMEMEMEM*E", 16, 1, 4, 1, 8,
                                         16384]
    # blocks 27 to 37 of the published string: its longest and most
    # frequent period, 5 : 5 : 1 against 40 : 40 : 8 whole
    whole = PUBLISHED["hybrid_override_pattern"]
    assert len(whole) == 88 and whole[27:38] == CFG["hybrid_override_pattern"]
    assert [whole.count(c) for c in "ME*"] == [40, 40, 8]
    periods = [len(p) + 1 for p in whole.split("*")[1:-1]]
    assert max(periods) == 11 and periods.count(11) > periods.count(9)
    # heads and groups over 8 chips, experts over 64, an eighth of the rows
    for key, chips in (("mamba_num_heads", 8), ("n_groups", 8),
                       ("num_attention_heads", 8), ("n_routed_experts", 64),
                       ("vocab_size", 8)):
        assert chips * CFG[key] == PUBLISHED[key], key
    assert CFG["deployment"].startswith(
        "each block shared by 64 chips: heads and groups over 8")
    for key in ("source", "reduced", "published", "deployment", "assumed",
                "departures"):
        assert CFG[key], key
    for key in ("positions", "router", "norm_topk_eps", "expert_bias_update",
                "aux_loss_coef", "expert_bias_initial_std", "time_step_limit",
                "initial_values", "held_experts_first",
                "held_mamba_heads_first", "held_attention_heads_first",
                "optimizer", "dtype", "remat_blocks", "attention",
                "buffer_rows", "batch"):
        assert key in CFG["assumed"], key
    assert not any(contract.WIDTH.search(k) for k in CFG["reduced"])
    assert CFG["check"]["limits_set_from"]
    assert set(CFG["check"]["limits"]) == set(bench_toy.TOY_LIMITS)
    assert (CFG["aux_loss_coef"], CFG["expert_bias_update_speed"],
            CFG["expert_bias_initial_std"], CFG["norm_topk_eps"],
            CFG["held_experts_first"], CFG["held_mamba_heads_first"],
            CFG["held_attention_heads_first"]) == (
        1e-4, 0.001, 0.01, 1e-20, 0, 0, 0)
    # the same recipe as the routed cells' configurations
    lfm2 = load(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json"))
    for key in ("optimizer", "dtype", "initializer_range", "remat_blocks",
                "expert_bias_update_speed", "expert_bias_initial_std"):
        assert CFG[key] == lfm2[key], key
    # but a stream that starts nearer its tokens: a squared ReLU is positive
    # and its shared expert gives every position one common vector
    assert (CFG["embedding_initializer_range"],
            CFG["residual_out_initializer_range"]) == (0.2, 0.00005)


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
    # the files whose count would be wrong here: the grouped products' shape
    # read from hidden_size, the flash kernels' heads of 64, a dispatch that
    # takes whatever is neither route nor experts (here the latent and the
    # shared expert too); nor the pair that reads XLA's kernel, nor other
    # cells' scopes
    assert not {"kernel.gmm_roofline.pl", "kernel.flash_fwd_roofline.gqa",
                "kernel.flash_bwd_roofline", "model.moe_dispatch_ms_per_step",
                "kernel.gmm_ms_per_step", "kernel.gmm_roofline",
                "model.mlp_ms_per_step", "model.full_attn_ms_per_step",
                "kernel.flash_fwd_ms_per_step",
                "model.short_conv_ms_per_step"} & mine
    names = [w["name"] for w in manifest["workloads"]]
    assert contract.stands_once_after(names, CELL, EARLIER)
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
        assert (m["moves"], m["source"]) == ("tokens_per_s_per_chip",
                                             "device_trace")
        assert (m["layer"], m["unit"], m["better"]) == (
            ("model step: dt_tpu/models, optim", "ms", "lower")
            if name.startswith("model.") else
            ("kernels: ops/pallas", "%", "higher"))
    # the accepted cells' that it shares list those cells, then this one
    for name, before in SHARED.items():
        assert contract.stands_once_after(by_name[name]["workloads"], CELL,
                                          before), name


def test_the_cell_reports_what_the_lm_cells_report_and_its_own():
    manifest_assertions(MANIFEST)
    traffic = load(os.path.join(BENCH, "traffic", TRAFFIC + ".json"))
    assert (traffic["batch"], traffic["seq_len"], traffic["distinct_batches"],
            traffic["steps_per_reading"], traffic["warm_steps"],
            traffic["trace_last_s"]) == (1, 8192, 3, 1, 0, 5.0)
    assert traffic["generator"] == "traffic:uniform_tokens"
    for name in NEW_METRICS:
        path = readers.metric_file(BENCH, name)
        assert os.path.basename(path) == name + ".json"
    assert len(MANIFEST["workloads"]) >= 8
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"])
    # the even held load: T x 22 x 8 / 512, 352 rows an expert; the buffer
    # is a quarter-step of it and whole row tiles of the grouped kernels
    even = traffic["seq_len"] * CFG["num_experts_per_tok"] \
        * CFG["n_routed_experts"] // CFG["published"]["n_routed_experts"]
    assert (even, CFG["buffer_rows"]) == (2816, 4224)
    assert CFG["buffer_rows"] % (even // 4) == 0 \
        and CFG["buffer_rows"] % 128 == 0
    # the new kernel metrics' files read this configuration by these keys:
    # the grouped products at the latent's width, the flash kernels at the
    # held heads of 128
    gmm = load(readers.metric_file(BENCH, "kernel.gmm_roofline.lat"))
    assert gmm["args"]["ops_bytes"] == "sdar_opcount:grouped_ops_bytes"
    assert [CFG[v] for v in gmm["args"]["shape"].values()
            if isinstance(v, str)] == [4224, 1024, 2688, 8]
    for name in ("kernel.flash_fwd_roofline.hd128",
                 "kernel.flash_bwd_roofline.hd128"):
        shape = load(readers.metric_file(BENCH, name))["args"]["shape"]
        assert shape["head_dim"] == CFG["head_dim"] == 128
        assert CFG[shape["heads"]] == 4
        readers.resolve(load(readers.metric_file(BENCH, name))["args"][
            "ops_bytes"])


def test_parameters_and_operations_by_hand():
    d, s = 4096, 8192
    # the issue's table, part by part, at what this chip holds
    in_proj = d * (1024 + 1024 + 128 + 128 + 16)
    assert in_proj == d * 2320
    mamba = in_proj + 1024 * d
    assert nemotron_opcount.part_params(CFG, "M") == mamba
    conv_dim = 1024 + 2 * 128
    mamba_all = mamba + 4 * conv_dim + conv_dim + 3 * 16 + 1024 + d
    assert mamba_all == 13708592                        # 13.71M
    attn = d * 512 + 2 * d * 128 + 512 * d
    assert nemotron_opcount.part_params(CFG, "*") == attn
    assert attn + d == 5246976                          # 5.25M
    held = 8 * 2 * 1024 * 2688
    fixed = d * 512 + 2 * d * 1024 + 2 * d * 5376
    assert (held, 2 * d * 1024, 2 * d * 5376) == (44040192, 8388608,
                                                  44040192)
    assert fixed + held + d == 98570240                 # 98.57M
    ends = 2 * 16384 * d + d
    total = 5 * mamba_all + attn + d + 5 * (fixed + held + d) + ends
    assert (ends, total) == (134221824, 700862960)      # 700.9M
    assert 11.21e9 < 16 * total < 11.22e9               # 11.21 GB
    # with the mixers whole the period does not fit: 19.4 GB
    mamba_whole = d * 18560 + 8192 * d + 5 * (8192 + 2048) + 3 * 128 \
        + 8192 + d
    attn_whole = d * 4096 + 2 * d * 256 + 4096 * d + d
    assert (mamba_whole, attn_whole) == (109640064, 35655680)
    assert 19.3e9 < 16 * (5 * mamba_whole + attn_whole
                          + 5 * (fixed + held + d) + ends) < 19.5e9
    # a token meets 22 x 8 / 512 of a held expert's worth on average
    met = fixed + 2 * 1024 * 2688 * 22 * 8 / 512
    assert nemotron_opcount.part_params(CFG, "E") == met
    with pytest.raises(ValueError):
        nemotron_opcount.part_params(CFG, "-")
    t = np.arange(24)
    assert nemotron_opcount.causal_pairs(24) == (t + 1).sum()
    traffic = {"seq_len": s, "batch": 1}
    weights = 5 * mamba + attn + 5 * met + d * 16384
    pairs = nemotron_opcount.causal_pairs(s) / s * 4 * 4 * 128
    scan = 5 * 2 * 2 * 16 * 64 * 128
    assert nemotron_opcount.scan_forward_ops_per_token(CFG) == scan / 5
    flops = nemotron_opcount.nemotron_train_flops_per_item(CFG, traffic)
    assert flops == pytest.approx(6 * weights + 3 * (pairs + scan))
    # a step of 8,192 tokens: 20.8 TFLOP of matrix products as needed (28
    # with a block's recomputation), of which the shared expert is 10.8
    # (52%) and the routed products at the even load 0.47 (2.2%; 3.3% over
    # the buffer's 4,224 rows); attention's pairs 0.21, the recurrence 0.06
    assert 20.7e12 < 6 * weights * s < 20.9e12
    assert 0.51 < 5 * 2 * d * 5376 / weights < 0.53
    routed = 5 * 2 * 1024 * 2688 * 22 * 8 / 512
    assert 0.021 < routed / weights < 0.023
    assert 0.032 < routed * 4224 / 2816 / weights < 0.034
    assert 21.0e12 < flops * s < 21.2e12
    # one grouped product over the buffer, by the routed cells' count at
    # the shape kernel.gmm_roofline.lat's file names (the latent's width):
    # the same count either way round, a quarter of what hidden_size reads
    args = load(readers.metric_file(BENCH, "kernel.gmm_roofline.lat"))["args"]
    count = readers.resolve(args["ops_bytes"])
    shape = {k: CFG[v] if isinstance(v, str) else v
             for k, v in args["shape"].items()}
    ops, nbytes = count(**shape)
    assert ops == 2 * 4224 * 1024 * 2688 == 23253221376
    assert nbytes == 4224 * 1024 * 2 + 8 * 1024 * 2688 * 2 + 4224 * 2688 * 4
    assert count(4224, 2688, 1024, 8, 2)[0] == ops
    assert count(**{**shape, "d_in": CFG["hidden_size"]})[0] == 4 * ops


# -- the rehearsal: a copy with the toy cell added as files -----------------

@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    path = bench_toy.make_copy(str(tmp_path_factory.mktemp("nemotron")))
    bench = os.path.join(os.path.dirname(path), "benchmark")
    man = load(path)
    toy = {**CFG, **TOY, "assumed": {}, "departures": ["toy size"],
           "check": {**CFG["check"], "limits": dict(bench_toy.TOY_LIMITS),
                     "limits_set_from": "tests/benchmark/bench_toy.py"}}
    bench_toy.dump(toy, os.path.join(bench, "configs", "nemotron-toy.json"))
    man["configs"].append({
        "name": "nemotron-toy", "source": "toy", "reduced": toy["reduced"],
        "why": "toy", "file": "benchmark/configs/nemotron-toy.json"})
    bench_toy.dump(TOY_TRAFFIC,
                   os.path.join(bench, "traffic", "tokens_b2_s64.json"))
    man["workloads"].append({"name": "toy-nemotron", "config": "nemotron-toy",
                             "traffic": "tokens_b2_s64", "chips": 1,
                             "why": "toy"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy-nemotron")
    bench_toy.dump(man, path)
    return path


def test_the_toy_copys_cut_configuration_meets_the_contract(manifest):
    man = load(manifest)
    entry = next(e for e in man["configs"] if e["name"] == "nemotron-toy")
    cfg = load(os.path.join(os.path.dirname(manifest), entry["file"]))
    contract.check_config(entry, cfg)
    assert cfg["driver"] == "nemotron_drivers:SinglePartHybridJob"


def test_rehearsal_of_the_cells_job_is_correct(manifest):
    """One traced run (a run without a trace takes the same steps and
    prints the two end-to-end metrics alone: ``readers.collect``)."""
    rc, last, out = bench_toy.run_cell(manifest, "toy-nemotron", trace=1)
    assert rc == 0 and last is not None, out[-3000:]
    assert last["correct"] is True, out[-3000:]
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0 and last["failed"] == 0
    assert len(last["compared"]) == 8
    got = set(last["metrics"])
    values = {k: last["metrics"][k]["value"] for k in got}
    assert values["loop.metric_device_steps_pct.lm"] == 100.0
    assert values["compile.in_window.lm"] == 0
    # the counters reached the host with the metric's statistics: the two E
    # blocks' (a quarter of the 16 experts held), and the bias's
    assert values["moe.overflow_assignments"] == 0
    assert 0 < values["moe.held_load_share_pct"] < 100
    assert 0 < values["moe.buffer_fill_pct"] < 100
    assert 0 < values["moe.bias_moved_assignments_pct"] < 50
    # the CPU's trace has no device plane: every reader of the device's
    # time finds nothing, returns nothing, and the line leaves it out
    assert got.isdisjoint(NEW_METRICS)
    assert "scopes_missing" not in out


def test_the_job_builds_the_shares_and_seeds_the_biases():
    """The model is given the whole model's head counts, groups and router
    width and each share as (first, count); ``program_tree`` holds the
    parameters, ``state_tree`` each E block's bias, and ``make_state`` puts
    the reference's seeded biases where the program keeps them."""
    import jax
    import nemotron_drivers
    import run
    cfg = {**CFG, **TOY, "attention": None}
    job = nemotron_drivers.SinglePartHybridJob(cfg, TOY_TRAFFIC, 1, 0)
    model = job.mod.model
    assert (model.ssm_heads, model.ssm_groups, model.held_ssm_heads) == (
        8, 2, (4, 4))
    assert (model.num_heads, model.num_kv_heads, model.held_heads) == (
        4, 2, (2, 2))
    assert (model.num_experts, model.held_experts, model.pattern) == (
        16, (4, 4), "ME*E")
    # at the published sizes: 128 heads in 8 groups of which 0..15, 32 query
    # heads over 2 of which 0..3, 512 experts of which 0..7
    assert nemotron_drivers.share(CFG, "held_mamba_heads_first",
                                  "mamba_num_heads") == (0, 16)
    assert nemotron_drivers.share(CFG, "held_attention_heads_first",
                                  "num_attention_heads") == (0, 4)
    assert nemotron_drivers.share(CFG, "held_experts_first",
                                  "n_routed_experts") == (0, 8)
    assert [nemotron_drivers.whole(CFG, k) for k in (
        "mamba_num_heads", "n_groups", "num_attention_heads",
        "num_key_value_heads", "n_routed_experts", "hidden_size")] == [
            128, 8, 32, 2, 512, 4096]
    # a file that holds everything gives no share
    uncut = {k: v for k, v in cfg.items() if k != "published"}
    assert nemotron_drivers.share(uncut, "held_experts_first",
                                  "n_routed_experts") is None
    ref = run.load_reference(os.path.join(REPO, cfg["reference"]))
    key = jax.random.PRNGKey(3)
    drawn = ref.init(key, cfg)
    tree, stats = job.program_tree(drawn), job.state_tree(drawn)
    assert sorted(stats) == ["block1", "block3"]
    assert set(tree["block0"]) == {"norm", "mamba"} \
        and set(tree["block2"]) == {"norm", "attn"} \
        and set(tree["block1"]) == {"norm", "moe"} and "lm_head" in tree
    assert set(tree["block1"]["moe"]) == {
        "router", "up", "down", "latent_in", "latent_out", "shared_up",
        "shared_down"}
    state = job.make_state(ref.init, key)
    for name, blk in stats.items():
        bias = np.asarray(blk["moe"]["selection_bias"])
        assert bias.shape == (16,) and np.abs(bias).min() > 0
        np.testing.assert_allclose(      # drawn under jit there, not here
            state.batch_stats[name]["moe"]["selection_bias"], bias, rtol=1e-6)
    structure = jax.tree_util.tree_structure
    assert structure(job.param_change_host(key, state)) == structure(tree) \
        == structure(jax.device_get(state.params))


def test_the_programs_tree_at_the_published_sizes_is_the_count():
    """The cell's model under ``jax.eval_shape``: 700,862,960 parameters,
    the issue's table block by block, and 5 x 512 biases beside them."""
    import jax
    import jax.numpy as jnp
    import nemotron_drivers
    job = nemotron_drivers.SinglePartHybridJob(
        {**CFG, "attention": None}, load(os.path.join(
            BENCH, "traffic", TRAFFIC + ".json")), 1, 0)
    made = jax.eval_shape(lambda: job.mod.model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32)))
    size = lambda t: sum(int(np.prod(a.shape))  # noqa: E731
                         for a in jax.tree_util.tree_leaves(t))
    params = made["params"]
    assert [size(params[f"block{i}"]) for i in (0, 9, 1)] == [
        13708592, 5246976, 98570240]
    assert size(params) == 700862960
    assert size(made["batch_stats"]) == 5 * 512
    assert params["block0"]["mamba"]["in_proj"]["kernel"].shape == (4096,
                                                                    2320)
    assert params["block1"]["moe"]["up"].shape == (8, 1024, 2688)
    assert params["block1"]["moe"]["router"].shape == (4096, 512)


# -- every new metric file against the job's own scope paths -----------------

@pytest.fixture(scope="module")
def step_scopes():
    """The scope path of every operation of the toy job's train step, as
    jax writes it into the program it hands the compiler (the device trace
    carries the same strings, PERF.md section 3), with blocks
    rematerialised as in the cell."""
    import jax
    import jax.numpy as jnp
    import nemotron_drivers
    from dt_tpu.training import metrics as metrics_lib
    from dt_tpu.training.train_state import TrainState
    cfg = {**CFG, **TOY, "attention": None}
    job = nemotron_drivers.SinglePartHybridJob(cfg, TOY_TRAFFIC, 1, 0)
    mod = job.mod
    mod._metric_stats = metrics_lib.device_form(metrics_lib.create("ce"))
    mod._build_steps()
    tokens = jnp.zeros((2, 64), jnp.int32)

    def state():
        made = mod.model.init(jax.random.PRNGKey(0), tokens)
        return TrainState.create(mod.model.apply, made["params"], mod.tx,
                                 made["batch_stats"])
    text = mod._train_step.lower(jax.eval_shape(state), tokens, tokens,
                                 jax.random.PRNGKey(0)).as_text(
                                     debug_info=True)
    return sorted(set(re.findall(r'"(jit\(train_step\)/[^"]*)"', text)))


SCOPE_METRICS = [n for n in NEW_METRICS if n.startswith("model.")] + [
    n for n in SHARED if n.startswith("model.")
    and n != "model.ssm_scan_bwd_ms_per_step"]


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_scope_metric_resolves_its_reader_and_finds_its_scope(step_scopes,
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


def test_the_blocks_parts_lie_inside_them_and_tell_each_other_apart(
        step_scopes):
    """The scopes the metric files hold tell the three kinds of block and
    the recomputation apart, and inside an E block the route, the latent
    projections, the experts, the shared expert and what is left (dispatch
    and combine): no operation is in two."""
    import xplane
    args = lambda n: load(readers.metric_file(BENCH, n))["args"]  # noqa: E731
    match = lambda s, a: xplane.scope_matches(  # noqa: E731
        s, a["holds"], a.get("lacks", ()))
    kinds = [args(n) for n in ("model.ssm_mixer_ms_per_step",
                               "model.gqa_attn_ms_per_step",
                               "model.moe_ms_per_step",
                               "model.remat_ms_per_step")]
    parts = [args(n) for n in ("model.moe_route_ms_per_step",
                               "model.moe_latent_ms_per_step",
                               "model.moe_experts_ms_per_step",
                               "model.moe_shared_ms_per_step",
                               "model.moe_dispatch_ms_per_step.lat")]
    counts, inside = [0] * len(kinds), [0] * len(parts)
    for scope in step_scopes:
        hits = [match(scope, a) for a in kinds]
        assert sum(hits) <= 1, scope
        counts = [c + h for c, h in zip(counts, hits)]
        mine = [match(scope, a) for a in parts]
        # an E block's operation that is not recomputed is in exactly one
        # of its five parts
        assert sum(mine) == hits[2], scope
        inside = [c + h for c, h in zip(inside, mine)]
        if not any(hits):    # the ends, the norms between, the optimizer
            assert not re.search(r"/(mamba|attn|moe)/", scope), scope
    assert all(counts) and all(inside), (counts, inside)
    # the two projections are in the latent and in no other part
    for proj in ("latent_in", "latent_out"):
        found = [s for s in step_scopes
                 if f"/moe/latent/{proj}/dot_general" in s]
        assert found, proj
    # what is left are the dispatch's gather and the combine's add-back
    # (and the layer's own reshapes of the tokens, which cost nothing)
    left = [s for s in step_scopes if match(s, parts[4])]
    assert all("/moe/dispatch" in s or "/moe/combine" in s
               or re.search(r"/moe/(reshape|add_any)$", s) for s in left)
    assert any("/moe/dispatch" in s for s in left) \
        and any("/moe/combine" in s for s in left)
    # block by block, as the pattern says: no block is named by number in a
    # metric's file, the part's module name tells them apart
    for i, kind in enumerate(TOY["hybrid_override_pattern"]):
        for part, letter in (("mamba", "M"), ("attn", "*"), ("moe", "E")):
            found = [s for s in step_scopes if f"/block{i}/{part}/" in s]
            assert bool(found) == (kind == letter), (i, part)
        assert any(f"/block{i}/norm/" in s for s in step_scopes)
    for part in ("mamba/conv1d", "mamba/ssd_scan", "mamba/gated_norm",
                 "mamba/in_proj", "attn/q_proj", "attn/o_proj", "moe/route",
                 "moe/shared/shared_up", "moe/shared/shared_down", "lm_head",
                 "embed"):
        assert any(part in s for s in step_scopes), part
    # not gated: no gate product in the experts nor in the shared expert
    assert not any("shared_gate" in s for s in step_scopes)
    # the bias's add, its update and its counter are inside route
    assert any("/moe/route/" in s and "_route_biased" in s
               for s in step_scopes)
    for name in NEW_METRICS:    # no file names a block by its number
        assert "block" not in json.dumps(load(readers.metric_file(
            BENCH, name)).get("args", {}))
