"""The build account and the ``fit`` rows (``dt_tpu/obs/trace.py``): every
trace, lowering and backend compile that jax reports is a row on the step
account's clock, every ``fit`` call leaves a row at its exit, and with
``DT_OBS=1`` each is a span at the row's own readings.  Live with every gate
off; nothing new runs in a step (reference analog: none; the reference's
profiler saw operators, ``src/profiler/profiler.h:256``, and its engine
never built a program)."""

import time

import numpy as np
import pytest

from dt_tpu.obs import export as obs_export
from dt_tpu.obs import trace as obs_trace

BUILD = obs_trace.BUILD_ROW_FIELDS
FIT = obs_trace.FIT_ROW_FIELDS
STEP = obs_trace.STEP_ROW_FIELDS
STAGES = ("trace", "lower", "backend")
PH, RSEQ, NAME, TS, DUR, TID, SID, PARENT, ATTRS = range(9)
WALL0 = 1_700_000_000_000_000_000
TRACE, LOWER, BACKEND = obs_trace.BUILD_STAGES


class CountingClock:
    """Clocks that advance 1 us a monotonic read and count their reads."""

    def __init__(self):
        self.t = self.mono_reads = self.wall_reads = 0

    def mono(self):
        self.t += 1000
        self.mono_reads += 1
        return self.t

    def wall(self):
        self.wall_reads += 1
        return WALL0 + self.t


@pytest.fixture
def tracer(monkeypatch):
    """The process tracer replaced by a fresh one on the real clocks (the
    build rows' readings are jax's own ``time.time()``)."""
    tr = obs_trace.Tracer(name="t", capacity=256)
    monkeypatch.setattr(obs_trace, "_DEFAULT", tr)
    yield tr
    obs_trace.set_enabled(None)


@pytest.fixture
def clocked(monkeypatch):
    clock = CountingClock()
    tr = obs_trace.Tracer(name="t", capacity=256, wall_clock=clock.wall,
                          mono_clock=clock.mono)
    monkeypatch.setattr(obs_trace, "_DEFAULT", tr)
    return tr, clock


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


def _builds(tr, since=0):
    return [dict(zip(BUILD, r)) for r in tr.build_rows(since)]


def _fits(tr):
    return [dict(zip(FIT, r)) for r in tr.fit_rows()]


def _parts(row):
    return row["enter_ns"] + row["steps_ns"] + row["between_ns"] \
        + row["exit_ns"]


def test_first_call_pays_the_build_and_the_rows_say_which_call(tracer):
    mod = _module()
    mod.fit(_feed(3))
    first = [r for r in _builds(tracer) if r["fun"] == "train_step"]
    # one row a stage, named for the train step in all three, inside the
    # call that paid: its number, and its interval on the same clock
    assert [r["stage"] for r in first] == list(STAGES)
    call = _fits(tracer)[0]
    for r in first:
        assert r["fit"] == call["fit"] == 1 and r["dur_ns"] > 0
        assert call["wall_ns"] <= r["wall_ns"]
        assert r["wall_ns"] + r["dur_ns"] <= \
            call["wall_ns"] + call["total_ns"] + 3_000_000
    assert [r["wall_ns"] for r in first] == sorted(r["wall_ns"] for r in first)
    # only the backend stage has the cache's word; conftest turns it off
    assert [r["cache"] for r in first] == [None, None, "off"]
    assert all(r["retrieval_s"] is None for r in first)
    # they lie inside the first iteration's step.dispatch
    step = dict(zip(STEP, tracer.step_rows(fit=1)[0]))
    assert step["step.dispatch"] >= sum(r["dur_ns"] for r in first)
    # the model's init was built inside the call too, before the loop
    init = [r for r in _builds(tracer) if r["fun"] == "init"]
    assert {r["stage"] for r in init} == set(STAGES)
    assert all(r["fit"] == 1 and r["wall_ns"] < first[0]["wall_ns"]
               for r in init)

    # the same metric and shapes again: nothing is built
    mark = tracer.builds()
    mod.fit(_feed(3))
    assert _builds(tracer, mark) == []
    # another batch shape: the call that meets it pays, and says so
    mod.fit(_feed(3, batch=16))
    again = [r for r in _builds(tracer, mark) if r["fun"] == "train_step"]
    assert [(r["fit"], r["stage"]) for r in again] == \
        [(3, s) for s in STAGES]
    assert [r["fit"] for r in _fits(tracer)] == [1, 2, 3]


def test_builds_outside_any_fit_call_carry_no_number(tracer):
    import jax
    import jax.numpy as jnp
    mod = _module()
    mod.fit(_feed(1))
    mark = tracer.builds()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(5))
    rows = [r for r in _builds(tracer, mark) if r["fun"] == "<lambda>"]
    assert [r["stage"] for r in rows] == list(STAGES)
    assert {r["fit"] for r in _builds(tracer, mark)} == {None}


class _Done(Exception):
    pass


def _raise_at_two(p):
    if p.nbatch == 2:
        raise _Done


@pytest.mark.parametrize("how", ["returns", "callback_raises",
                                 "raises_before_any_iteration"])
def test_every_way_out_writes_the_fit_row_and_its_parts_sum(clocked, how):
    tr, _ = clocked
    mod = _module()
    if how == "returns":
        mod.fit(_feed(4), batch_end_callback=lambda p: None)
    elif how == "callback_raises":
        with pytest.raises(_Done):
            mod.fit(_feed(6), batch_end_callback=_raise_at_two)
    else:
        with pytest.raises(ValueError):
            mod.fit(_feed(2), eval_metric="no-such-metric")
    (row,) = _fits(tr)
    assert row["fit"] == 1 and row["wall_ns"] >= WALL0
    assert _parts(row) == row["total_ns"] > 0
    steps = [dict(zip(STEP, r)) for r in tr.step_rows(fit=1)]
    assert row["iterations"] == len(steps)
    assert row["steps_ns"] == sum(r["total_ns"] for r in steps)
    if how == "raises_before_any_iteration":
        assert row["enter_ns"] == row["total_ns"] and not steps
    else:
        assert row["enter_ns"] > 0 and row["exit_ns"] > 0
        assert row["between_ns"] == 0       # one epoch
        # the entry ends where the first iteration begins
        assert steps[0]["wall_ns"] - row["wall_ns"] == row["enter_ns"]
    # the call is closed: what is built now belongs to no call
    assert tr._open_fits == {}


def test_epoch_end_work_lies_between_the_epochs_iterations(clocked):
    tr, _ = clocked
    _module().fit(_feed(2), num_epoch=3, eval_data=_feed(1))
    (row,) = _fits(tr)
    steps = [dict(zip(STEP, r)) for r in tr.step_rows(fit=1)]
    assert row["iterations"] == len(steps) == 9
    assert row["steps_ns"] == sum(r["total_ns"] for r in steps)
    # snapshot and evaluation after the first and second epochs lie
    # between iterations; after the third they are the exit
    assert row["between_ns"] > 0 and row["exit_ns"] > 0
    assert _parts(row) == row["total_ns"]


def test_nothing_new_runs_in_a_step(clocked):
    """The account reads its clocks nine times an iteration and the wall
    clock once, as before the ``fit`` rows (``tests/test_step_account.py``'s
    boundaries); a call costs two more monotonic reads and one wall read,
    however many steps it takes."""
    tr, clock = clocked
    mod = _module()
    reads = {}
    for steps in (3, 5, 9):
        mod.fit(_feed(steps), batch_end_callback=lambda p: None)
        reads[steps] = (clock.mono_reads, clock.wall_reads)
        clock.mono_reads = clock.wall_reads = 0
    per_step = {((reads[b][0] - reads[a][0]) / (b - a),
                 (reads[b][1] - reads[a][1]) / (b - a))
                for a, b in ((3, 5), (5, 9))}
    assert per_step == {(9.0, 1.0)}
    # three steps: four iterations of nine reads less the phases the first
    # and the last skip (31 before this account had a call's row), plus the
    # call's entry and exit
    assert reads[3] == (31 + 2, 4 + 1)


def test_a_built_step_costs_no_listener_call(tracer):
    """jax reports builds and nothing else: once a step is built, a call of
    it reaches none of the four kinds of listener, the account's among
    them; a build reaches them a bounded few times and leaves a row a
    stage."""
    import jax
    import jax.numpy as jnp
    from jax import monitoring
    calls = []
    probes = [
        (monitoring.register_event_listener,
         monitoring.unregister_event_listener,
         lambda event, **kw: calls.append(event)),
        (monitoring.register_event_duration_secs_listener,
         monitoring.unregister_event_duration_listener,
         lambda event, secs, **kw: calls.append(event)),
        (monitoring.register_event_time_span_listener,
         monitoring.unregister_event_time_span_listener,
         lambda event, start, end, **kw: calls.append(event)),
        (monitoring.register_scalar_listener,
         monitoring.unregister_scalar_listener,
         lambda event, value, **kw: calls.append(event)),
    ]
    for register, _, probe in probes:
        register(probe)
    try:
        step = jax.jit(lambda x: (x * 2 + 1).sum())
        x = jnp.ones((8, 8))
        mark = tracer.builds()
        jax.block_until_ready(step(x))
        built, rows = list(calls), _builds(tracer, mark)
        del calls[:]
        for _ in range(50):
            jax.block_until_ready(step(x))
        assert calls == []
        mod = _module()
        mod.fit(_feed(2))
        del calls[:]
        mod.fit(_feed(6))           # six built steps through fit
        assert calls == []
    finally:
        for _, unregister, probe in probes:
            unregister(probe)
    # the build: a begin, a length and a span for each stage it opened
    # (the function's and the two jnp calls traced inside it)
    assert 9 <= len(built) <= 40, built
    assert [(r["fun"], r["stage"]) for r in rows] == \
        [("<lambda>", s) for s in STAGES]   # the nested traces are no rows


def _stage(event, start, seconds, fun):
    """One stage as jax reports it: its begin, then its end with both
    readings."""
    obs_trace._on_stage_begin(event, start, fun_name=fun)
    return lambda: obs_trace._on_stage(event, start, start + seconds,
                                       fun_name=fun)


def test_nested_stages_are_rows_only_when_long_and_seconds_are_a_union(
        tracer):
    t0 = 1_700_000_000.0
    end_outer = _stage(TRACE, t0, 2.0, "train_step")
    _stage(TRACE, t0 + 0.1, 0.001, "add")()             # nested, short
    _stage(TRACE, t0 + 0.2, 0.5, "_flash_fwd_pallas")()  # nested, long
    end_outer()
    _stage(LOWER, t0 + 2.0, 1.0, "jit(train_step)")()
    obs_trace._on_cache_event(
        "/jax/compilation_cache/compile_requests_use_cache")
    obs_trace._on_cache_event("/jax/compilation_cache/cache_hits")
    obs_trace._on_cache_seconds(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    obs_trace._on_cache_seconds(
        "/jax/compilation_cache/compile_time_saved_sec", 41.0)
    _stage(BACKEND, t0 + 3.0, 0.3, "jit(train_step)")()
    _stage(BACKEND, t0 + 4.0, 0.1, "jit(other)")()       # the words are spent
    rows = _builds(tracer)
    assert [(r["fun"], r["stage"]) for r in rows] == [
        ("_flash_fwd_pallas", "trace"), ("train_step", "trace"),
        ("train_step", "lower"), ("train_step", "backend"),
        ("other", "backend")]
    assert obs_trace.NESTED_ROW_NS == 100_000_000
    served = rows[3]
    assert (served["cache"], served["retrieval_s"], served["saved_s"]) == \
        ("hit", 0.25, 41.0)
    assert (rows[4]["cache"], rows[4]["retrieval_s"]) == ("off", None)
    assert rows[1]["dur_ns"] == pytest.approx(2e9, abs=1000)
    assert rows[1]["wall_ns"] == pytest.approx(t0 * 1e9, abs=1000)
    # the inner trace is inside the outer: 2 s of tracing, not 2.5
    raw = tracer.build_rows()
    assert obs_trace.stage_ns(raw, "trace") == pytest.approx(2e9, abs=2000)
    assert obs_trace.stage_ns(raw, "lower") == pytest.approx(1e9, abs=2000)
    assert obs_trace.stage_ns(raw, "backend") == pytest.approx(4e8, abs=2000)
    # an event of another name is not a stage and opens nothing
    obs_trace._on_stage_begin("/jax/other", t0)
    obs_trace._on_stage("/jax/other", t0, t0 + 1, fun_name="x")
    assert len(tracer.build_rows()) == 5 and obs_trace._BUILDING.open == 0


def test_build_ring_is_bounded_and_read_on_from_a_mark():
    tr = obs_trace.Tracer(capacity=4, enabled=False)
    for i in range(10):
        tr._push_build_row((None, f"f{i}", "trace", i, 1, None, None, None,
                            0))
    assert tr.builds() == 10
    assert [r[1] for r in tr.build_rows()] == ["f6", "f7", "f8", "f9"]
    assert [r[1] for r in tr.build_rows(since=8)] == ["f8", "f9"]
    assert [r[1] for r in tr.build_rows(since=2)] == ["f6", "f7", "f8", "f9"]
    assert tr.build_rows(since=10) == [] and tr.snapshot()["records"] == []


def test_spans_carry_the_rows_own_readings(tracer):
    obs_trace.set_enabled(True)
    mod = _module()
    mod.fit(_feed(3), batch_end_callback=lambda p: None)
    recs = tracer.snapshot()["records"]
    by_name = {}
    for r in recs:
        by_name.setdefault(r[NAME], []).append(r)
    # each build row is a build.<stage> span at the row's readings
    rows = _builds(tracer)
    spans = [r for r in recs if r[NAME].startswith("build.")]
    assert len(spans) == len(rows) >= 6
    for span, row in zip(spans, rows):
        assert span[NAME] == "build." + row["stage"]
        assert span[TS] == row["wall_ns"] // 1000
        assert span[DUR] == row["dur_ns"] // 1000
        assert span[ATTRS] == {"fun": row["fun"], "fit": row["fit"],
                               "cache": row["cache"]}
    # the train step's lie under the first iteration's step.dispatch
    dispatch = by_name["step.dispatch"][0]
    mine = [s for s in spans if s[ATTRS]["fun"] == "train_step"]
    assert len(mine) == 3 and all(s[PARENT] == dispatch[SID] for s in mine)
    # the fit row is a fit span with its entry and its exit under it
    (row,) = _fits(tracer)
    (fit,), (enter,), (leave,) = (by_name[n] for n in
                                  ("fit", "fit.enter", "fit.exit"))
    assert fit[TS] == enter[TS] == row["wall_ns"] // 1000
    assert fit[DUR] == row["total_ns"] // 1000
    assert fit[ATTRS] == {"fit": 1, "iterations": 4}
    assert enter[DUR] == row["enter_ns"] // 1000
    assert leave[DUR] == row["exit_ns"] // 1000
    assert enter[PARENT] == leave[PARENT] == fit[SID]
    assert abs(leave[TS] + leave[DUR] - fit[TS] - fit[DUR]) <= 1
    # the first step starts where the entry ends (two clocks: to 1 ms)
    first_step = by_name["step"][0]
    assert abs(first_step[TS] - enter[TS] - enter[DUR]) < 1000
    # ... and the export lays them out as it stands
    chrome = obs_export.chrome_trace({"tracks": {"w0#1": {"records": recs}}})
    names = {e["name"] for e in chrome["traceEvents"] if e["ph"] == "X"}
    assert {"fit", "fit.enter", "fit.exit", "build.trace", "build.lower",
            "build.backend"} <= names
    assert obs_export.summarize_chrome(chrome)["tracks"]["w0#1"][
        "steps"]["count"] == 4


def test_with_tracing_off_the_rows_are_written_and_no_span(tracer):
    _module().fit(_feed(2))
    assert tracer.snapshot()["records"] == []
    assert len(_fits(tracer)) == 1 and len(_builds(tracer)) >= 6
    # on the step rows' clock: the call's entry is a time.time_ns reading
    assert abs(_fits(tracer)[0]["wall_ns"] - time.time_ns()) < 600e9


def test_names_are_declared_with_their_readers():
    from dt_tpu.obs import names
    for name in ("fit", "fit.enter", "fit.exit", "build.trace",
                 "build.lower", "build.backend"):
        assert names.lookup(name)[1] == "span"
    assert "no compile" in names.lookup("epoch.rebuild")[2]
