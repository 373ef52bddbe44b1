"""The seven ``setup.*`` metrics that read the program's build and ``fit``
rows (``benchmark/builds.py``, PR 39): the entries against the contract, the
readers on rows made by hand, a program without the rows, and one traced
rehearsal of a toy cell on the CPU that reports all seven beside the four
the benchmark takes from outside.  The numbers a rehearsal prints are
written nowhere."""

import os
import sys

import pytest

import bench_toy
import contract
from bench_toy import BENCH, REPO, load

sys.path.insert(0, BENCH)
import builds  # noqa: E402
import readers  # noqa: E402
import test_benchmark_files as files  # noqa: E402

MANIFEST = load(os.path.join(REPO, "BENCHMARK.json"))
SURFACES, LOOP = "compiled surfaces", "loop: training/module.py fit"
#: name -> (unit, better, layer, reader, args)
NEW = {
    "setup.build_trace_s": ("s", "lower", SURFACES, "builds:stage_s",
                            {"stage": "trace"}),
    "setup.build_lower_s": ("s", "lower", SURFACES, "builds:stage_s",
                            {"stage": "lower"}),
    "setup.build_backend_s": ("s", "lower", SURFACES, "builds:stage_s",
                              {"stage": "backend"}),
    "setup.build_cache_hit_pct": ("%", "higher", SURFACES,
                                  "builds:cache_hit_pct", None),
    "setup.fit_s": ("s", "lower", LOOP, "builds:fit_s", None),
    "setup.fit_entry_exit_s": ("s", "lower", LOOP,
                               "builds:fit_entry_exit_s", None),
    "setup.first_steps_wait_s": ("s", "lower", LOOP,
                                 "builds:first_steps_wait_s", None),
}
OUTSIDE = ["setup.import_s", "setup.init_s", "setup.compile_s",
           "setup.warm_s"]
S = 1_000_000_000
T0 = 1_700_000_000 * S


def entry_assertions(entry, manifest):
    files.check_metric_entry(entry, manifest)
    files.check_per_layer_moves(entry, manifest)
    unit, better, layer, reader, args = NEW[entry["name"]]
    # every cell reports setup_s, so every cell reports these: no list
    assert "workloads" not in entry
    assert (entry["unit"], entry["better"], entry["layer"]) == (
        unit, better, layer)
    # rows the program keeps with every gate off: counter-class, as
    # obs/trace.py calls the account (and tests/benchmark/
    # test_program_spans.py reads every `program_span` entry as a step
    # phase with a list of cells)
    assert (entry["moves"], entry["source"]) == ("setup_s", "program_counter")
    path = readers.metric_file(BENCH, entry["name"])
    assert os.path.basename(path) == entry["name"] + ".json"
    on_file = load(path)
    assert on_file["reader"] == reader and on_file.get("args") == args


def manifest_assertions(manifest):
    """What this file says of ``BENCHMARK.json``, of the one here or of a
    copy that later PRs have appended to: the seven names stand in
    ``per_layer`` once each, in this order, wherever, after the four that
    the benchmark takes from outside, which stay."""
    names = [m["name"] for m in manifest["per_layer"]]
    assert contract.in_this_order(names, list(NEW))
    assert contract.in_this_order(names, OUTSIDE)
    assert all(contract.stands_once_after(names, n, OUTSIDE) for n in NEW)
    for entry in manifest["per_layer"]:
        if entry["name"] in NEW:
            entry_assertions(entry, manifest)


def test_the_seven_entries_meet_the_contract_and_name_no_cell():
    manifest_assertions(MANIFEST)
    assert {m["layer"] for m in MANIFEST["per_layer"]} >= {SURFACES, LOOP}


# -- the readers, on rows made by hand ---------------------------------------


def build(fit, fun, stage, at_s, seconds, cache=None):
    return {"fit": fit, "fun": fun, "stage": stage,
            "wall_ns": T0 + int(at_s * S), "dur_ns": int(seconds * S),
            "cache": cache, "retrieval_s": None, "saved_s": None, "tid": 1}


def fit_row(n, at_s, enter, steps, out, iterations=2, between=0.0):
    return {"fit": n, "wall_ns": T0 + int(at_s * S),
            "total_ns": int((enter + steps + between + out) * S),
            "enter_ns": int(enter * S), "steps_ns": int(steps * S),
            "between_ns": int(between * S), "exit_ns": int(out * S),
            "iterations": iterations}


def step_row(n, iteration, **seconds):
    row = {"fit": n, "epoch": 0, "iteration": iteration, "dispatched": None,
           "flushed": None, "wall_ns": 0}
    row.update({p: int(seconds.get(p.split(".")[1], 0) * S)
                for p in ("step.input", "step.place", "step.dispatch",
                          "step.sync", "step.fetch", "step.metric",
                          "step.callback", "step.hooks")})
    row["total_ns"] = sum(v for k, v in row.items() if k.startswith("step."))
    return row


def a_run():
    """A set-up as the benchmark makes one: the state built before any
    call, three calls of one step of which the first pays the build, a
    tree fetched between calls, the window, the reference after it."""
    fits = [fit_row(1, 10, 0.5, 41.0, 0.1), fit_row(2, 60, 0.1, 1.2, 0.1),
            fit_row(3, 62, 0.1, 1.2, 0.1),
            fit_row(4, 70, 0.2, 48.0, 0.3, iterations=40)]
    builds_ = [
        build(None, "build", "trace", 1, 2.0),
        build(None, "build", "backend", 4, 1.0, "hit"),
        build(1, "_flash_fwd", "trace", 12, 3.0),       # inside the next
        build(1, "train_step", "trace", 11, 20.0),
        build(1, "train_step", "lower", 31, 10.0),
        build(1, "train_step", "backend", 41, 8.0, "miss"),
        build(None, "<lambda>", "backend", 52, 0.4, "hit"),
        build(None, "step", "backend", 130, 30.0, "miss"),  # the reference
    ]
    steps = [step_row(1, 0, dispatch=38.6, place=0.2),
             step_row(1, 1, fetch=2.2),
             step_row(2, 0, dispatch=0.1), step_row(2, 1, fetch=1.1),
             step_row(3, 0, dispatch=0.1), step_row(3, 1, fetch=1.1)]
    steps += [step_row(4, i, fetch=1.2) for i in range(40)]
    return fits, builds_, steps


def ctx_of(run):
    """A context whose account is already read: the readers' own input."""
    return {builds.KEY: builds.split(*run)}


def test_set_up_is_what_starts_before_the_windows_fit_call():
    acc = builds.split(*a_run())
    assert acc["window"]["fit"] == 4
    assert [f["fit"] for f in acc["fits"]] == [1, 2, 3]
    assert [b["fun"] for b in acc["later"]] == ["step"]
    assert len(acc["builds"]) == 7 and len(acc["steps"]) == 6
    ctx = ctx_of(a_run())
    stage = lambda s: builds.stage_s(ctx, {"args": {"stage": s}})  # noqa
    # the kernel's trace lies inside the step's: 22 s of tracing, not 25
    assert stage("trace") == pytest.approx(22.0)
    assert stage("lower") == pytest.approx(10.0)
    assert stage("backend") == pytest.approx(9.4)
    assert builds.cache_hit_pct(ctx, {}) == pytest.approx(100 * 2 / 3)
    assert builds.fit_s(ctx, {}) == pytest.approx(41.6 + 1.4 + 1.4)
    assert builds.fit_entry_exit_s(ctx, {}) == pytest.approx(0.6 + 0.2 + 0.2)
    assert builds.first_steps_wait_s(ctx, {}) == pytest.approx(4.4)


def test_closure_names_the_calls_seconds_and_what_is_left():
    named = builds.closure(builds.split(*a_run()))
    assert named["fit_s"] == pytest.approx(44.4)
    assert named["entry_exit_s"] == pytest.approx(1.0)
    assert named["builds_in_steps_s"] == pytest.approx(38.0)
    assert named["builds_in_entry_s"] == 0.0
    assert named["dispatch_s"] == pytest.approx(38.8)
    assert named["fetch_s"] == pytest.approx(4.4)
    # of step.dispatch, what no build row covers
    assert named["unnamed_s"] == pytest.approx(0.8)
    # the parts are the whole: entry and exit, every phase
    assert named["entry_exit_s"] + sum(
        named[p + "_s"] for p in ("input", "place", "dispatch", "sync",
                                  "fetch", "metric", "callback", "hooks")
    ) == pytest.approx(named["fit_s"])


def test_a_build_inside_a_calls_entry_is_not_charged_to_its_steps():
    fits, builds_, steps = a_run()
    builds_.append(build(1, "init", "backend", 10.1, 0.3, "hit"))
    named = builds.closure(builds.split(fits, builds_, steps))
    assert named["builds_in_entry_s"] == pytest.approx(0.3)
    assert named["builds_in_steps_s"] == pytest.approx(38.0)


def test_missing_rows_read_as_nothing_not_as_zero():
    fits, builds_, steps = a_run()
    # the ring dropped a set-up call's step rows: no wait, no closure; the
    # rest stands
    acc = builds.split(fits, builds_, steps[1:])
    assert acc["steps"] is None and builds.closure(acc) is None
    ctx = {builds.KEY: acc}
    assert builds.first_steps_wait_s(ctx, {}) is None
    assert builds.fit_s(ctx, {}) == pytest.approx(44.4)
    # no fit call at all; no backend stage before the window
    assert builds.split([], builds_, steps) is None
    only_traces = [b for b in builds_ if b["stage"] == "trace"]
    assert builds.cache_hit_pct(
        {builds.KEY: builds.split(fits, only_traces, steps)}, {}) is None


def test_the_log_names_slow_rows_calls_and_what_is_unnamed(capsys):
    acc = builds.split(*a_run())
    builds._say(acc, {"setup": {"warm_s": 50.0}})
    out = capsys.readouterr().out.splitlines()
    rows = [ln for ln in out if ln.startswith("# build ")]
    assert len(rows) == 7 and not any("<lambda>" in ln for ln in rows)
    assert "# build fit=1 fun=train_step stage=lower s=10.000 cache=None " \
        "when=setup" in rows
    assert rows[-1].endswith("fun=step stage=backend s=30.000 cache=miss "
                             "when=later")
    assert sum(ln.startswith("# fit fit=") for ln in out) == 3
    assert any(ln.startswith("# setup_unnamed_s=0.800 fit_s=44.400")
               for ln in out)
    assert any(ln.startswith("# setup_benchmark_own_s=5.600") for ln in out)


def test_the_program_names_the_fields_the_readers_use():
    from dt_tpu.obs import trace
    run = a_run()
    assert set(trace.FIT_ROW_FIELDS) == set(run[0][0])
    assert set(trace.BUILD_ROW_FIELDS) == set(run[1][0])
    assert set(trace.STEP_ROW_FIELDS) == set(run[2][0])


@pytest.mark.parametrize("missing", ["BUILD_ROW_FIELDS", "FIT_ROW_FIELDS",
                                     "build_rows", "fit_rows"])
def test_a_program_without_the_rows_reads_as_nothing(monkeypatch, missing):
    from dt_tpu.obs import trace
    monkeypatch.delattr(trace if missing.isupper() else trace.Tracer,
                        missing)
    ctx = {"setup": {"warm_s": 1.0}}
    for name, (_, _, _, reader, args) in NEW.items():
        assert readers.resolve(reader)(ctx, {"args": args}) is None, name
    assert ctx[builds.KEY] is None      # read once, then remembered


def test_a_process_that_called_no_fit_reads_as_nothing(monkeypatch):
    from dt_tpu.obs import trace
    monkeypatch.setattr(trace, "_DEFAULT", trace.Tracer(capacity=8))
    assert builds.fit_s({}, {}) is None
    assert builds.stage_s({}, {"args": {"stage": "lower"}}) is None


def test_traced_rehearsal_reports_all_seven_beside_the_outside_four(tmp_path):
    """``toy-lm`` on the CPU through the real runner: every new metric is
    in the line, the program's backend seconds are the benchmark's own
    listener's (one jax event, two listeners), the ``fit`` calls are inside
    ``setup.warm_s``, and the log names the rows."""
    manifest = bench_toy.make_copy(str(tmp_path))
    rc, last, out = bench_toy.run_cell(manifest, "toy-lm", seconds=1,
                                       trace=1)
    assert rc == 0 and last is not None, out[-3000:]
    assert last["correct"] is True, out[-3000:]
    got = {k: v["value"] for k, v in last["metrics"].items()
           if k.startswith("setup.")}
    assert set(NEW) | set(OUTSIDE) <= set(got), sorted(got)
    assert all(v == v for v in got.values())
    assert got["setup.build_backend_s"] == pytest.approx(
        got["setup.compile_s"], rel=0.02)
    assert 0 < got["setup.fit_s"] < got["setup.warm_s"]
    assert 0 < got["setup.fit_entry_exit_s"] < got["setup.fit_s"]
    assert 0 < got["setup.first_steps_wait_s"] < got["setup.fit_s"]
    assert got["setup.build_trace_s"] > 0 and got["setup.build_lower_s"] > 0
    # the rehearsal leaves the persistent cache off
    assert got["setup.build_cache_hit_pct"] == 0.0
    assert last["metrics"]["setup.fit_s"]["unit"] == "s"
    lines = out.splitlines()
    assert sum(ln.startswith("# fit fit=") for ln in lines) == 3
    unnamed = [ln for ln in lines if ln.startswith("# setup_unnamed_s=")]
    assert len(unnamed) == 1        # said once, whatever reader came first
    # the stages inside the calls, their waits and their entries account
    # for the calls: under a tenth is left without a name
    left = float(unnamed[0].split("=")[1].split()[0])
    assert abs(left) < 0.1 * got["setup.fit_s"], unnamed
    assert any(ln.startswith("# setup_benchmark_own_s=") for ln in lines)
