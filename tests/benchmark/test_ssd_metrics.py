"""The two metrics of the state-space scan's Pallas kernels (PR 40): the
entries against the contract, their files, and which events each reads on
made-up rows.  Nothing here touches a device."""

import os
import sys

import pytest

import contract
from bench_toy import BENCH, REPO, load

sys.path.insert(0, BENCH)
import readers  # noqa: E402
import test_benchmark_files as files  # noqa: E402

MANIFEST = load(os.path.join(REPO, "BENCHMARK.json"))
NEW = ["kernel.ssd_fwd_ms_per_step", "kernel.ssd_bwd_ms_per_step"]
NEEDLES = dict(zip(NEW, ("ssd_fwd", "ssd_bwd")))
CELL = "granite4hm-b2-seq4096"
ENTRIES = [m for m in MANIFEST["per_layer"] if m["name"] in NEW]


def entry_assertions(entry, manifest):
    files.check_metric_entry(entry, manifest)
    files.check_per_layer_moves(entry, manifest)
    # the hybrid cell first; a later cell with such layers may follow it
    assert entry["workloads"][0] == CELL
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "kernels: ops/pallas", "tokens_per_s_per_chip", "device_trace")
    assert (entry["unit"], entry["better"]) == ("ms", "lower")
    path = readers.metric_file(BENCH, entry["name"])
    assert os.path.basename(path) == entry["name"] + ".json"
    mine = load(path)
    # a time by the kernel's own name and nothing else: a share of a
    # roofline for them is a benchmark PR's
    assert mine["reader"] == "readers:kernel_ms_per_step"
    assert mine["args"] == {"op_name_holds": NEEDLES[entry["name"]]}


def manifest_assertions(manifest):
    """What this file says of ``BENCHMARK.json``, of the one here or of a
    copy that later PRs have appended to: the two names stand in
    ``per_layer`` once each and in this order, wherever."""
    assert contract.in_this_order(
        [m["name"] for m in manifest["per_layer"]], NEW)
    for entry in manifest["per_layer"]:
        if entry["name"] in NEW:
            entry_assertions(entry, manifest)


def test_the_two_entries_meet_the_contract_and_name_the_hybrid_cell():
    assert [m["name"] for m in ENTRIES] == NEW
    manifest_assertions(MANIFEST)


def _ctx(op_seconds, steps=2):
    """``op_seconds``: {operation: (seconds, events)} over ``steps``."""
    _, _, cfg, traffic, _, _ = files.bench_run.load_cell(
        os.path.join(REPO, "BENCHMARK.json"), CELL)
    return {"trace": {"steps": steps, "busy_s": 1.0,
                      "op_seconds": {k: v[0] for k, v in op_seconds.items()},
                      "op_events": {k: v[1] for k, v in op_seconds.items()}},
            "rehearsal": False, "bench_dir": BENCH,
            "device_kind": "TPU v5 lite", "chips": 1, "cfg": cfg,
            "traffic": traffic}


def _read(ctx, name):
    m = load(readers.metric_file(BENCH, name))
    return readers.resolve(m["reader"])(ctx, m)


def test_which_events_each_reads():
    """Two traced steps of nine state-space layers: eighteen forward calls a
    step (the forward pass and each block's recomputation) at 0.6 ms, nine
    backward calls at 1.7 ms; the flash kernels' events and a fusion beside
    them are read by neither."""
    ours = _ctx({"ssd_fwd.4": (20 * 0.6e-3, 20), "ssd_fwd.31": (16 * 0.6e-3,
                                                               16),
                 "ssd_bwd.2": (18 * 1.7e-3, 18), "flash_bwd.1": (0.02, 2),
                 "attn.7": (0.01, 4), "fusion.9": (0.5, 7)})
    assert _read(ours, NEW[0]) == pytest.approx(18 * 0.6)
    assert _read(ours, NEW[1]) == pytest.approx(9 * 1.7)
    # the parent of PR 40 has no such event: the line leaves the pair out
    parents = _ctx({"fusion.9": (0.5, 7), "while.12": (0.1, 18)})
    assert _read(parents, NEW[0]) is None and _read(parents, NEW[1]) is None
    # nothing traced: nothing to read
    assert _read({**ours, "trace": None}, NEW[0]) is None
