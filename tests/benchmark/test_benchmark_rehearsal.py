"""The toy-size rehearsal of a run on the CPU (``DT_FORCE_CPU=1``): the real
runner end to end through ``Module.fit``, the control in the precision one
step below the configuration's, and the timed path broken underneath.  The
numbers a rehearsal prints are written nowhere."""

import json
import os
import sys

import pytest

import bench_toy
from bench_toy import BENCH, load

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return bench_toy.make_copy(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell,trace", [("toy-synth", 0), ("toy-lm", 0),
                                        ("toy-lm", 1)])
def test_rehearsal_prints_exactly_the_contracts_keys(manifest, cell, trace):
    rc, last, out = bench_toy.run_cell(manifest, cell, trace=trace)
    assert rc == 0 and last is not None, out[-3000:]
    assert set(last) == RESULT_KEYS | ({"breakdown"} if trace else set())
    assert last["device"]["platform"] == "cpu"      # named, never a TPU's
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["correct"] is True, out[-3000:]
    listed = load(manifest)["per_layer" if trace else "end_to_end"]
    want = {m["name"] for m in listed
            if cell in m.get("workloads", [cell])}
    got = set(last["metrics"])
    assert got <= want and {"setup_s", "setup.import_s"} & got
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    if trace:
        assert {"busy_s", "window_s"} <= set(last["device"])
        assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
        assert last["metrics"]["compile.in_window.lm"]["value"] == 0
        # the metrics the copy added: their reader, in a module of its
        # own, read the job's counter and the kept trace through ctx
        assert set(bench_toy.TOY_METRICS) <= got
        values = {k: last["metrics"][k]["value"] for k in got}
        assert values["loop.job_device_flushes"] >= last["attempted"]
        assert values["loop.trace_bytes"] > 0
        assert values["loop.metric_device_steps_pct.lm"] == 100.0
        # the CPU's trace has no device plane: the split is left out
        assert got.isdisjoint(
            f"model.{part}.lm" for part in (
                "forward_ms_per_step", "backward_ms_per_step",
                "optimizer_ms_per_step", "unscoped_pct"))
        assert "scopes_missing" not in out
        # the trace is removed once the readers have returned
        kept = [ln for ln in out.splitlines() if ln.startswith("# trace ")]
        assert kept and not os.path.exists(
            kept[0].split(" file=")[1].split(" ")[0])
    # each number compared beside its limit: last in the line, and the
    # last lines on standard error
    assert list(last)[-1] == "compared"
    assert len(last["compared"]) == 8
    assert all(set(v) == {"value", "limit"} and v["value"] <= v["limit"]
               for v in last["compared"].values())
    assert sum(ln.startswith("compared ") for ln in out.splitlines()) == 8
    # the earlier lines say what ran and how the readings spread
    head = [ln for ln in out.splitlines() if ln.startswith("# ")]
    assert any("platform=cpu" in ln and "device_kind=" in ln and
               "run_seconds=" in ln for ln in head)
    assert any("readings=" in ln and "quartiles_s=" in ln and "window_s="
               in ln for ln in head)
    assert sum("memory when=" in ln for ln in head) == 3
    assert sum("compare config=" in ln and "limit=" in ln
               for ln in head) == 7


def test_without_a_tpu_a_run_exits_nonzero_and_prints_no_result(manifest):
    env = {k: v for k, v in os.environ.items() if k != "DT_FORCE_CPU"}
    import subprocess
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         manifest, "--workload", "toy-lm", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env={**env, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
    assert "needs 1 TPU chip" in proc.stderr


@pytest.mark.parametrize("cell", ["toy-lm-frozen", "toy-lm-half"])
def test_a_broken_timed_path_comes_out_not_correct(manifest, cell):
    """A step that returns its state unchanged; a step that leaves out half
    of the batch (tests/benchmark/toy/broken_drivers.py)."""
    rc, last, out = bench_toy.run_cell(manifest, cell)
    assert rc == 0 and last is not None, out[-3000:]
    assert last["correct"] is False
    assert " OVER" in out


def _limit(limits, number):
    return limits["loss_rel" if number.startswith("loss.") else
                  number.replace(".", "_")]


@pytest.mark.parametrize("cell", ["toy-lm", "toy-synth"])
def test_the_control_one_precision_below_fails_a_limit(manifest, cell):
    """``benchmark/control.py`` at toy size, in a process of its own: the
    program's numbers are within the limits, and the reference put in the
    program's place in float8 (the configuration states bfloat16) is over
    one.  (At the cells' own size the same script reads them on the chip.)"""
    import subprocess
    env = bench_toy.rehearsal_env()
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"), "--manifest",
         manifest, "--workload", cell, "--seeds", "11", "--control", "1"],
        capture_output=True, text=True, timeout=600, env=env,
        preexec_fn=bench_toy.two_cores)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    row = json.loads([ln for ln in proc.stdout.splitlines()
                      if ln.startswith("{")][-1])
    config = next(w["config"] for w in load(manifest)["workloads"]
                  if w["name"] == cell)
    limits = load(os.path.join(os.path.dirname(manifest), "benchmark",
                               "configs", config + ".json"))["check"]["limits"]
    assert all(v <= _limit(limits, k) for k, v in row["program"].items()), row
    assert any(v > _limit(limits, k) for k, v in row["control"].items()), row
