"""The two metrics of the grouped products' Pallas kernels (PR 38): the
entries against the contract, their files against the old pair's (the same
work, another needle), and which events each reads on made-up rows.
Nothing here touches a device."""

import os
import sys

import pytest

import contract
from bench_toy import BENCH, REPO, load

sys.path.insert(0, BENCH)
import readers  # noqa: E402
import sdar_opcount  # noqa: E402
import test_benchmark_files as files  # noqa: E402

MANIFEST = load(os.path.join(REPO, "BENCHMARK.json"))
NEW = ["kernel.gmm_ms_per_step.pl", "kernel.gmm_roofline.pl"]
# what read XLA's kernel for ragged_dot until PR 38, and reads nothing since
OLD = {"kernel.gmm_ms_per_step.pl": "kernel.gmm_ms_per_step",
       "kernel.gmm_roofline.pl": "kernel.gmm_roofline"}
CELLS = ["sdar30b-ep8share-bd4-seq4096", "keye30b-ep8share-dsa2048-seq16384"]
ENTRIES = [m for m in MANIFEST["per_layer"] if m["name"] in NEW]


def entry_assertions(entry, manifest):
    files.check_metric_entry(entry, manifest)
    files.check_per_layer_moves(entry, manifest)
    # the two routed cells, the first routed cell first; a later one may
    # follow them
    assert entry["workloads"][:2] == CELLS
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "kernels: ops/pallas", "tokens_per_s_per_chip", "device_trace")
    assert (entry["unit"], entry["better"]) == (
        ("%", "higher") if "roofline" in entry["name"] else ("ms", "lower"))
    path = readers.metric_file(BENCH, entry["name"])
    assert os.path.basename(path) == entry["name"] + ".json"
    mine, old = load(path), load(readers.metric_file(BENCH,
                                                     OLD[entry["name"]]))
    # the kernels' common name; the same reader, operations, bytes and shape
    # as the old row: the new share is of the same work
    assert mine["args"]["op_name_holds"] == "grouped_mm"
    assert mine["reader"] == old["reader"]
    assert {**mine["args"], "op_name_holds": None} == \
        {**old["args"], "op_name_holds": None}
    assert readers.resolve(mine["args"]["ops_bytes"]) is \
        sdar_opcount.grouped_ops_bytes


def manifest_assertions(manifest):
    """What this file says of ``BENCHMARK.json``, of the one here or of a
    copy that later PRs have appended to: the two names stand in
    ``per_layer`` once each and in this order, wherever."""
    assert contract.in_this_order(
        [m["name"] for m in manifest["per_layer"]], NEW)
    for entry in manifest["per_layer"]:
        if entry["name"] in NEW:
            entry_assertions(entry, manifest)


def test_the_two_entries_meet_the_contract_and_name_the_routed_cells():
    assert [m["name"] for m in ENTRIES] == NEW
    manifest_assertions(MANIFEST)


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


@pytest.mark.parametrize("cell,rows,ms_per_call", [
    (CELLS[0], 49152, 1.2), (CELLS[1], 24576, 0.6)])
def test_the_share_by_hand_and_which_events_each_pair_reads(cell, rows,
                                                            ms_per_call,
                                                            capsys):
    """Two traced steps of 66 events each (48 under the value's and the
    turned product's name, 18 under the transposed one's) at 1.2 ms a call
    over 49,152 rows (0.6 over 24,576): 2 x rows x 2,048 x 768 operations a
    call are 0.785 (0.392) ms at 197 TFLOP/s, 65.4%."""
    ops, nbytes = sdar_opcount.grouped_ops_bytes(rows, 2048, 768, 16, 2)
    assert ops == 2 * rows * 2048 * 768 and ops / 197e12 > nbytes / 819e9
    call = 1e-3 * ms_per_call
    ours = _ctx(cell, {"grouped_mm.3": (40 * call, 40),
                       "grouped_mm.17": (56 * call, 56),
                       "grouped_mm_t.5": (36 * call, 36),
                       "flash_bwd_bd.1": (0.2, 12), "fusion.9": (0.5, 7)})
    assert _read(ours, NEW[0]) == pytest.approx(66 * ms_per_call)
    capsys.readouterr()
    assert _read(ours, NEW[1]) == pytest.approx(65.4, abs=0.05)
    assert "# kernel grouped_mm events_per_step=66 " in capsys.readouterr().out
    # XLA's kernel is off the path: its pair falls silent
    assert _read(ours, OLD[NEW[0]]) is None
    assert _read(ours, OLD[NEW[1]]) is None
    # the parent of PR 38 has no such event: the line leaves the new pair
    # out, and the old pair reads what it read
    parents = _ctx(cell, {"ragged-dot-none.2": (132 * 2 * call, 132)})
    assert _read(parents, NEW[0]) is None and _read(parents, NEW[1]) is None
    assert _read(parents, OLD[NEW[1]]) == pytest.approx(65.4 / 2, abs=0.05)
    # nothing traced: nothing to read
    assert _read({**ours, "trace": None}, NEW[1]) is None
