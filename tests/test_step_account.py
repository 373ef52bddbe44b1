"""The step account (``dt_tpu/obs/trace.py`` ``StepAccount``): every
iteration of ``Module.fit``'s step loop leaves a row that says where its
wall time went, live with tracing off; with ``DT_OBS=1`` the same
boundaries are the ``step`` span and its phase spans.  On injected clocks,
in this process (reference analog: the per-process profiler's scoped
regions, ``src/profiler/profiler.h:256``, which timed operators and never
the loop around them)."""

import numpy as np
import pytest

from dt_tpu.obs import export as obs_export
from dt_tpu.obs import trace as obs_trace

FIELDS = obs_trace.STEP_ROW_FIELDS
PHASES = obs_trace.STEP_PHASES
# record tuple indices (dt_tpu/obs/trace.py schema)
PH, RSEQ, NAME, TS, DUR, TID, SID, PARENT, ATTRS = range(9)
WALL0 = 1_700_000_000_000_000_000


class TickingClock:
    """A monotonic clock that advances 1 us every time it is read, and a
    wall clock at a fixed offset from it that reads without advancing:
    every phase gets a length, and every length is exact."""

    def __init__(self):
        self.t = 0

    def mono(self):
        self.t += 1000
        return self.t

    def wall(self):
        return WALL0 + self.t


@pytest.fixture
def tracer(monkeypatch):
    """The process tracer replaced by one on injected clocks (``fit`` asks
    for the process tracer), tracing at its default: off."""
    clock = TickingClock()
    tr = obs_trace.Tracer(name="t", capacity=64, wall_clock=clock.wall,
                          mono_clock=clock.mono)
    monkeypatch.setattr(obs_trace, "_DEFAULT", tr)
    yield tr
    obs_trace.set_enabled(None)


def _module():
    import flax.linen as linen
    from dt_tpu.training import Module

    class Net(linen.Module):
        @linen.compact
        def __call__(self, x, training=True):
            return linen.Dense(2)(x.reshape((x.shape[0], -1)))

    return Module(Net(), optimizer="sgd",
                  optimizer_params={"learning_rate": 0.1}, seed=0)


def _feed(steps, batch=8):
    from dt_tpu import data
    x = np.random.RandomState(0).normal(
        size=(steps * batch, 4, 4, 1)).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 2, steps * batch).astype(np.int32)
    return data.NDArrayIter(x, y, batch_size=batch)


def _rows(tr, fit=None):
    return [dict(zip(FIELDS, r)) for r in tr.step_rows(fit)]


def test_rows_phases_sum_to_the_iterations_wall_time_exactly(tracer):
    seen = []
    _module().fit(_feed(4), num_epoch=2,
                  batch_end_callback=lambda p: seen.append(p.nbatch))
    rows = _rows(tracer)
    # four steps an epoch: five iterations, the first flushes nothing and
    # the last dispatches nothing
    assert [(r["epoch"], r["iteration"]) for r in rows] == \
        [(e, i) for e in (0, 1) for i in range(5)]
    assert [r["dispatched"] for r in rows] == \
        [0, 1, 2, 3, None, 4, 5, 6, 7, None]
    assert [r["flushed"] for r in rows] == \
        [None, 1, 2, 3, 4, None, 1, 2, 3, 4]
    assert seen == [1, 2, 3, 4, 1, 2, 3, 4]
    assert {r["fit"] for r in rows} == {1}
    for r in rows:
        assert sum(r[p] for p in PHASES) == r["total_ns"] > 0
        assert r["step.sync"] == 0            # one process, mesh mode
    # within an epoch one iteration's end is the next one's start: the
    # rows cover the loop's wall time with nothing between them
    for a, b in zip(rows, rows[1:]):
        if a["epoch"] == b["epoch"]:
            assert b["wall_ns"] - a["wall_ns"] == a["total_ns"]
    first, steady, last = rows[0], rows[2], rows[4]
    assert first["step.input"] > 0 and first["step.dispatch"] > 0
    assert first["step.fetch"] == first["step.callback"] == 0
    assert all(steady[p] > 0 for p in PHASES if p != "step.sync")
    assert last["step.dispatch"] == last["step.place"] == 0
    assert last["step.fetch"] > 0 and last["step.callback"] > 0
    # nothing of it needed tracing: the span ring stayed empty
    assert tracer.snapshot()["records"] == []


def test_an_iteration_that_a_callback_leaves_still_writes_its_row(tracer):
    class Done(Exception):
        pass

    def stop_at_two(p):
        if p.nbatch == 2:
            raise Done

    with pytest.raises(Done):
        _module().fit(_feed(6), batch_end_callback=stop_at_two)
    rows = _rows(tracer)
    assert [r["flushed"] for r in rows] == [None, 1, 2]
    cut = rows[-1]
    # the phases it got to, the callback up to the exception among them
    assert cut["dispatched"] == 2 and cut["step.metric"] > 0
    assert cut["step.callback"] > 0
    assert sum(cut[p] for p in PHASES) == cut["total_ns"]
    # the next fit call's rows are told apart by their number
    _module().fit(_feed(1))
    assert [r["fit"] for r in _rows(tracer, fit=-1)] == [2, 2]
    assert len(_rows(tracer, fit=1)) == 3


@pytest.mark.parametrize("metric,path", [
    (["acc", "ce"], "device"),
    (lambda lb, p: float((p.argmax(-1) == lb).mean()), "host"),
], ids=["device_path", "host_path"])
def test_fetch_and_metric_stay_separate_phases_on_either_metric_path(
        tracer, metric, path):
    """On the device path the step hands the host per-row statistics, not
    logits: the wait for them is still ``step.fetch``, ``update_reduced``
    still ``step.metric``, and ``flushed`` counts as before."""
    mod = _module()
    mod.fit(_feed(4), eval_metric=metric,
            batch_end_callback=lambda p: None)
    assert mod.metric_flushes == {"device": 0, "host": 0, path: 4}
    rows = _rows(tracer)
    assert [r["flushed"] for r in rows] == [None, 1, 2, 3, 4]
    assert [r["dispatched"] for r in rows] == [0, 1, 2, 3, None]
    for r in rows[1:]:
        assert r["step.fetch"] > 0 and r["step.metric"] > 0
        assert r["step.callback"] > 0
        assert sum(r[p] for p in PHASES) == r["total_ns"]
    assert rows[0]["step.fetch"] == rows[0]["step.metric"] == 0


def test_account_ring_is_bounded_and_drops_the_oldest():
    tr = obs_trace.Tracer(capacity=8, enabled=False)
    acct = tr.step_account()
    for i in range(20):
        acct.begin(0, i, i)
        acct.phase("step.dispatch")
    assert acct.end() is not None and acct.end() is None
    rows = tr.step_rows()
    assert len(rows) == 8
    assert [r[FIELDS.index("iteration")] for r in rows] == list(range(12, 20))
    assert tr.step_rows(fit=7) == [] and tr.snapshot()["records"] == []


def test_nothing_retained_per_phase_with_tracing_off():
    """Beside ``test_obs.py``'s fast-path test: a phase boundary with
    ``DT_OBS=0`` writes into slots made once per ``fit`` call, and the
    ring at capacity retains no more for a new row than it frees."""
    import os
    import tracemalloc
    tr = obs_trace.Tracer(capacity=16, enabled=False)
    acct = tr.step_account()

    def iteration(i):
        acct.begin(0, i, i)
        for name in PHASES:
            acct.phase(name)

    tracemalloc.start()
    for i in range(1000, 1064):   # fill the ring with rows like the rest
        iteration(i)
    before = tracemalloc.take_snapshot()
    for i in range(2000, 4000):
        iteration(i)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    retained = sum(
        s.size_diff for s in after.compare_to(before, "filename")
        if s.size_diff > 0 and s.traceback and
        s.traceback[0].filename.endswith(os.path.join("obs", "trace.py")))
    assert retained < 512, f"{retained} bytes retained over 2000 rows"
    assert tr.snapshot()["records"] == [] and len(tr.step_rows()) == 16


def test_step_span_covers_the_iteration_and_parents_its_phases(tracer):
    obs_trace.set_enabled(True)
    _module().fit(_feed(3), batch_end_callback=lambda p: None)
    recs = tracer.snapshot()["records"]
    steps = [r for r in recs if r[NAME] == "step"]
    rows = _rows(tracer)
    assert len(steps) == len(rows) == 4
    by_parent = {}
    for r in recs:
        if r[NAME] in PHASES:
            by_parent.setdefault(r[PARENT], []).append(r)
    for span, row in zip(steps, rows):
        # the span is the row: same start, same length, same counts
        assert span[TS] == row["wall_ns"] // 1000
        assert span[DUR] == row["total_ns"] // 1000
        assert span[ATTRS] == {k: row[k] for k in (
            "epoch", "iteration", "dispatched", "flushed")}
        kids = by_parent[span[SID]]
        # the children tile it: each starts where the one before ended
        assert kids[0][TS] == span[TS]
        for a, b in zip(kids, kids[1:]):
            assert b[TS] == a[TS] + a[DUR]
        assert kids[-1][TS] + kids[-1][DUR] == span[TS] + span[DUR]
        for p in PHASES:
            assert sum(k[DUR] for k in kids if k[NAME] == p) == \
                row[p] // 1000
    # a steady iteration's step span holds the fetch and the metric of the
    # step before beside its own dispatch
    names = [k[NAME] for k in by_parent[steps[1][SID]]]
    assert [n for n in names if n != "step.hooks"] == [
        "step.dispatch", "step.input", "step.place", "step.fetch",
        "step.metric", "step.callback"]
    # ... and comes out of export.py as it stands, children under it
    chrome = obs_export.chrome_trace({"tracks": {"w0#1": {"records": recs}}})
    evs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    step_sids = {e["args"]["sid"] for e in evs if e["name"] == "step"}
    assert len(step_sids) == 4
    assert all(e["args"]["parent"] in step_sids for e in evs
               if e["name"] in PHASES)
    summary = obs_export.summarize_chrome(chrome)
    assert summary["tracks"]["w0#1"]["steps"]["count"] == 4


def test_step_ms_is_observed_from_the_accounts_reads(tracer):
    from dt_tpu.obs import metrics as obs_metrics
    obs_metrics.registry().clear()
    obs_metrics.set_enabled(True)
    try:
        _module().fit(_feed(3))
        rows = _rows(tracer)
        hist = {n: h for n, _, h in
                obs_metrics.registry().hists_export()}["step.ms"]
    finally:
        obs_metrics.set_enabled(None)
        obs_metrics.registry().clear()
    # one observation per iteration that dispatched a step, its length
    # the row's own
    dispatched = [r for r in rows if r["dispatched"] is not None]
    assert hist["count"] == len(dispatched) == 3
    assert hist["sum"] == pytest.approx(
        sum(r["total_ns"] for r in dispatched) / 1e6)


def test_epoch_boundary_work_and_eval_are_annotated_spans(tracer):
    obs_trace.set_enabled(True)
    mod = _module()
    mod.fit(_feed(2), eval_data=_feed(1))
    names = [r[NAME] for r in tracer.snapshot()["records"]]
    assert "epoch.snapshot" in names and "eval" in names
    assert names.index("epoch") < names.index("epoch.snapshot") \
        < names.index("eval")
