"""``benchmark/spans.py``: the readers of the program's own step account
(``dt_tpu/obs/trace.py`` ``StepAccount``), on rows made by hand, and one
traced rehearsal of a toy cell on the CPU that sees every new metric printed.
The numbers a rehearsal prints are written nowhere."""

import os
import sys

import pytest

import bench_toy
from bench_toy import BENCH, REPO, load

sys.path.insert(0, BENCH)
import spans  # noqa: E402

PHASES = ("step.input", "step.place", "step.dispatch", "step.sync",
          "step.fetch", "step.metric", "step.callback", "step.hooks")
NEW = [m for m in load(os.path.join(REPO, "BENCHMARK.json"))["per_layer"]
       if m["source"] == "program_span"]


def row(iteration, flushed, **ms):
    """One account row as a dict, its phases given in milliseconds."""
    out = {"fit": 3, "epoch": 0, "iteration": iteration,
           "dispatched": iteration, "flushed": flushed, "wall_ns": 0}
    out.update({p: int(ms.get(p.split(".")[1], 0) * 1e6) for p in PHASES})
    out["total_ns"] = sum(out[p] for p in PHASES)
    return out


def steady(i, flushed, callback=1.0):
    return row(i, flushed, dispatch=2, input=0.5, place=1.5, fetch=90,
               metric=4, callback=callback, hooks=1)


def test_the_program_names_the_phases_the_readers_sum():
    from dt_tpu.obs import trace
    assert trace.STEP_PHASES == PHASES
    assert set(trace.STEP_ROW_FIELDS) == set(row(0, None))


def test_selection_with_warm_steps_takes_the_windows_rows_only():
    # iteration 0 flushes nothing; the clock's window opens at the second
    # callback (one warm step) and closes at the fifth: three steps
    rows = [row(0, None, dispatch=50, input=3, place=4)] + \
        [steady(i, i) for i in range(1, 6)]
    rows[1]["step.fetch"] = int(900e6)      # a warm step: not the window's
    got = spans.select(rows, 3)
    assert [r["flushed"] for r in got] == [3, 4, 5]
    assert spans.phase_ms(got, "step.fetch") == pytest.approx(90.0)
    assert spans.phase_ms(got, "step.place") == pytest.approx(1.5)
    # fewer rows than steps (the ring dropped them): nothing to read
    assert spans.select(rows, 6) is None and spans.select(rows, 0) is None


def test_without_warm_steps_every_flushed_row_is_the_windows():
    rows = [row(0, None, dispatch=50)] + [steady(i, i) for i in (1, 2)]
    got = spans.select(rows, 2)
    assert [r["iteration"] for r in got] == [1, 2]
    assert spans.phase_ms(got, "step.metric") == pytest.approx(4.0)


def test_unaccounted_leaves_out_the_callback_that_closed_the_window():
    # the last row stops at its callback: the clock read the closing time
    # when the callback started, then stopped the profiler for 3.7 s and
    # raised; the phases after it never ran
    last = row(3, 3, dispatch=2, input=0.5, place=1.5, fetch=90, metric=4,
               callback=3700)
    rows = [steady(1, 1), steady(2, 2), last]
    # 100 ms an iteration; the window = three iterations less the closing
    # callback, plus 6 ms no row covers (what the opening iteration did
    # after its callback)
    window_s = (100 + 100 + 98 + 6) / 1e3
    assert spans.unaccounted(rows, window_s) == pytest.approx(
        100 * 6 / 304)
    assert spans.phase_ms(rows, "step.hooks") == pytest.approx(2 / 3)


def test_a_program_without_the_account_reads_as_nothing(monkeypatch):
    from dt_tpu.obs import trace
    ctx = {"steps": 2, "window_s": 1.0}
    m = {"args": {"phase": "step.fetch"}}
    monkeypatch.delattr(trace, "STEP_ROW_FIELDS")
    assert spans.phase_ms_per_step(ctx, m) is None
    assert spans.unaccounted_pct(ctx, m) is None


def test_readers_take_the_newest_fit_calls_rows(monkeypatch):
    from dt_tpu.obs import trace
    tr = trace.Tracer(capacity=16, enabled=False)
    monkeypatch.setattr(trace, "_DEFAULT", tr)
    for fit_steps in (3, 2):            # two fit calls: the last is read
        acct = tr.step_account()
        for i in range(fit_steps + 1):
            acct.begin(0, i, i)
            acct.phase("step.fetch")
            if i:
                acct.flushed = i
        acct.end()
    ctx = {"steps": 2, "window_s": 1.0}
    assert spans.phase_ms_per_step(
        ctx, {"args": {"phase": "step.fetch"}}) > 0
    assert spans.phase_ms_per_step(
        {**ctx, "steps": 3}, {"args": {"phase": "step.fetch"}}) is None


def test_traced_rehearsal_prints_every_new_metric(tmp_path):
    """``toy-synth`` takes ``resnet50-synth``'s metrics: one traced run on
    the CPU through the real runner reads every one of them from the
    account, and the phases cover the window."""
    manifest = bench_toy.make_copy(str(tmp_path))
    # the toy ResNet's steps take up to a second on two shared cores: a
    # window of four, traced over its last three, so that a step ends
    # inside the traced stretch before the one that closes the window
    mix = os.path.join(str(tmp_path), "benchmark", "traffic", "synth_b8.json")
    bench_toy.dump({**load(mix), "trace_last_s": 3.0}, mix)
    rc, last, out = bench_toy.run_cell(manifest, "toy-synth", seconds=4,
                                       trace=1)
    assert rc == 0 and last is not None, out[-3000:]
    assert last["correct"] is True, out[-3000:]
    want = {m["name"] for m in NEW if "resnet50-synth" in m["workloads"]}
    assert len(want) == 7 and want <= set(last["metrics"]), \
        sorted(want - set(last["metrics"]))
    got = {k: last["metrics"][k]["value"] for k in want}
    assert all(v == v for v in got.values())
    assert got["loop.unaccounted_pct"] < 5, got
    assert got["loop.dispatch_ms_per_step"] > 0
    assert got["loop.fetch_ms_per_step"] > 0
    # the .lm names are the LM cell's and share the files
    assert {m["name"] for m in NEW} - want == {
        n + ".lm" for n in want if n.startswith("loop.")}
