"""The flash backward's two metrics (PR 31): its operations and bytes by
hand, the entries against the contract, and which events each kernel
metric reads on made-up rows.  Nothing here touches a device."""

import os
import sys

import pytest

import contract
from bench_toy import BENCH, REPO, load

sys.path.insert(0, BENCH)
import flash_bwd  # noqa: E402
import readers  # noqa: E402
import test_benchmark_files as files  # noqa: E402

MANIFEST = load(os.path.join(REPO, "BENCHMARK.json"))
# one name in both cells, as the events' name is one: an entry of the
# hybrid cell's alone would have to read ``attn.N`` events
# (test_hybrid_cell.py, ``NEW_METRICS``), which the backward's are not
NEW = ["kernel.flash_bwd_ms_per_step", "kernel.flash_bwd_roofline"]
CELLS = ["gpt2m-seq1024", "granite4hm-b2-seq4096"]
ENTRIES = [m for m in MANIFEST["per_layer"] if m["name"] in NEW]
KERNEL = [m for m in MANIFEST["per_layer"]
          if m["name"].startswith("kernel.flash_")]


@pytest.mark.parametrize("shape,ops,nbytes,least_ms", [
    ((8, 16, 1024, 64, 2), 42_949_672_960, 134_742_016, 0.218),
    ((2, 32, 4096, 64, 2), 343_597_383_680, 269_484_032, 1.744),
])
def test_operations_and_bytes_by_hand(shape, ops, nbytes, least_ms):
    batch, heads, seq, d, itemsize = shape
    # five products of seq x seq x d multiply-adds, half of each masked
    assert ops == batch * heads * 5 * seq * seq * d
    # eight arrays of seq x d and a float32 a row
    assert nbytes == batch * heads * (8 * seq * d * itemsize + 4 * seq)
    assert flash_bwd.flash_backward_ops_bytes(*shape) == (ops, nbytes)
    # compute-bound at the v5e's peaks
    assert ops / 197e12 > nbytes / 819e9
    assert 1e3 * ops / 197e12 == pytest.approx(least_ms, abs=5e-4)


def entry_assertions(entry, manifest):
    files.check_metric_entry(entry, manifest)
    files.check_per_layer_moves(entry, manifest)
    # both cells, gpt2-medium's first; a later cell may follow them
    assert entry["workloads"][:2] == CELLS
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "kernels: ops/pallas", "tokens_per_s_per_chip", "device_trace")
    path = readers.metric_file(BENCH, entry["name"])
    assert os.path.basename(path) == entry["name"] + ".json"
    assert load(path)["args"]["op_name_holds"] == "flash_bwd"


def manifest_assertions(manifest):
    """What this file says of ``BENCHMARK.json``, of the one here or of a
    copy that later PRs have appended to: the two names stand in
    ``per_layer`` once each and in this order, wherever."""
    assert contract.in_this_order(
        [m["name"] for m in manifest["per_layer"]], NEW)
    for entry in manifest["per_layer"]:
        if entry["name"] in NEW:
            entry_assertions(entry, manifest)


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda m: m["name"])
def test_entry_meets_the_contract_and_names_its_cells(entry):
    entry_assertions(entry, MANIFEST)
    assert contract.in_this_order(
        [m["name"] for m in MANIFEST["per_layer"]], NEW)


def _ctx(cell, op_seconds, steps=2):
    """``op_seconds``: {operation: (seconds, events)} over ``steps``."""
    _, _, cfg, traffic, _, _ = files.bench_run.load_cell(
        os.path.join(REPO, "BENCHMARK.json"), cell)
    return {"trace": {"steps": steps, "busy_s": 1.0,
                      "op_seconds": {k: v[0] for k, v in op_seconds.items()},
                      "op_events": {k: v[1] for k, v in op_seconds.items()}},
            "rehearsal": False, "bench_dir": BENCH,
            "device_kind": "TPU v5 lite", "chips": 1, "cfg": cfg,
            "traffic": traffic}


def _read(ctx, name):
    m = load(readers.metric_file(BENCH, name))
    return readers.resolve(m["reader"])(ctx, m)


# the names a traced step gives the two kernels in each cell
ROWS = {"gpt2m-seq1024": {"forward": "MultiHeadAttention_0.7",
                          "calls": 24, "least_ms": 0.218},
        "granite4hm-b2-seq4096": {"forward": "attn.3", "calls": 1,
                                  "least_ms": 1.744}}


@pytest.mark.parametrize("cell", ROWS)
def test_backward_events_are_read_by_the_backwards_metrics_only(cell):
    row, suffix = ROWS[cell], ".gqa" if "granite" in cell else ""
    fwd = ["kernel.flash_fwd_ms_per_step" + suffix,
           "kernel.flash_fwd_roofline" + suffix]
    bwd = NEW
    assert {m["name"] for m in KERNEL if cell in m["workloads"]} == \
        set(fwd + bwd)
    # two traced steps: 60 ms of backward events, 20 ms of forward ones;
    # the calls a step are the events the steps hold, under however many
    # names (the file of neither configuration says them)
    calls = row["calls"]
    only_bwd = _ctx(cell, {"flash_bwd.1": (0.04, calls),
                           "flash_bwd.24": (0.02, calls),
                           "fusion.9": (0.5, 7)})
    assert _read(only_bwd, bwd[0]) == pytest.approx(30.0)
    assert _read(only_bwd, bwd[1]) == pytest.approx(
        100 * calls * row["least_ms"] / 30.0, rel=1e-3)
    assert _read(only_bwd, fwd[0]) is None
    assert _read(only_bwd, fwd[1]) is None
    only_fwd = _ctx(cell, {row["forward"]: (0.02, 4), "fusion.9": (0.5, 7)})
    assert _read(only_fwd, fwd[0]) == pytest.approx(10.0)
    assert _read(only_fwd, fwd[1]) > 0
    # the parent of PR 31 has no such event: the line leaves them out
    assert _read(only_fwd, bwd[0]) is None
    assert _read(only_fwd, bwd[1]) is None
    both = _ctx(cell, {row["forward"]: (0.02, 4), "flash_bwd.1": (0.06, 2)})
    assert _read(both, fwd[0]) == pytest.approx(10.0)
    assert _read(both, bwd[0]) == pytest.approx(30.0)
    # nothing traced: nothing to read
    assert _read({**both, "trace": None}, bwd[1]) is None


def test_a_share_cannot_pass_100_percent_by_construction():
    """The kernel executes seven square products where five are counted
    (the scores and dp once, dv, dk, dq; two of them cost double for the
    side of 64 that fills half the MXU): a call at the chip's peak for what
    it executes reads 5 / 7 of the roofline."""
    ops, _ = flash_bwd.flash_backward_ops_bytes(8, 16, 1024, 64, 2)
    executed = ops * 7 / 5
    assert 100 * (ops / 197e12) / (executed / 197e12) < 72
