"""dt_tpu.obs.blackbox — flight-recorder bundles, the open-span
snapshot, the hang watchdog, the scheduler fleet detector +
blackbox_index RPC, and dtop's post-mortem renderer (reference analog:
none — MXNet/ps-lite had no post-mortem capture at all; the ceiling was
scrolling ``PS_VERBOSE`` logs, ``van.cc:563-570``)."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from dt_tpu.obs import blackbox as bb
from dt_tpu.obs import metrics as obs_metrics
from dt_tpu.obs import trace as obs_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DTOP = os.path.join(REPO, "tools", "dtop.py")
GOLDEN = os.path.join(REPO, "tests", "fixtures", "postmortem.golden")


@pytest.fixture(autouse=True)
def _clean_blackbox_plane(tmp_path, monkeypatch):
    """Each test starts (and leaves) the plane reset: fresh ring, no
    providers, no cached install, bundles under a throwaway dir — the
    ring/providers are process-shared, same discipline as the obs and
    metrics fixtures."""
    bb._reset_for_tests()
    monkeypatch.setenv("DT_BLACKBOX_DIR", str(tmp_path / "bbdir"))
    yield
    bb.set_enabled(None)
    bb._reset_for_tests()
    obs_trace.set_enabled(None)
    obs_trace.tracer().reset_counters()
    obs_trace.tracer().drain()


def _fixed_inputs(tmp_path):
    """A fully-injected bundle input set: two writes must produce
    identical bytes (the byte-determinism contract golden files and
    digest names rely on)."""
    clock = {"w": 1_700_000_000_000_000_000, "m": 1_000_000_000}
    tr = obs_trace.Tracer(name="t", capacity=64,
                          wall_clock=lambda: clock["w"],
                          mono_clock=lambda: clock["m"],
                          ident=lambda: 1, enabled=True)
    t0 = tr.begin("allreduce", {"key": "grads"})
    clock["m"] += 4_000_000_000  # the open span is now 4 s old
    clock["w"] += 4_000_000_000
    tr.event("health.nonfinite", {"step": 7, "nonfinite": 1})
    reg = obs_metrics.MetricsRegistry(
        name="t", capacity=8,
        wall_clock=lambda: clock["w"], enabled=True)
    reg.gauge("train.loss", 0.125)
    reg.sample()
    stacks = [{"tid": 1, "name": "MainThread", "daemon": False,
               "frames": [["/x/app.py", 10, "main"],
                          ["/x/dt_tpu/elastic/faults.py", 44,
                           "stall_at"]]},
              {"tid": 2, "name": "dt-heartbeat", "daemon": True,
               "frames": [["/usr/lib/python3/threading.py", 1, "run"]]}]
    bb.register_state("scheduler", lambda: {
        "role": "scheduler", "workers": ["w0", "w1"],
        "slo_history": [{"what": "breach", "rule": "round_wait",
                         "worker": "w1", "value": 700.0,
                         "ts_ms": 1_699_999_999_000}]})
    return dict(trigger="crash.module.epoch_begin", host="w7",
                fatal=True, extra={"site": "module.epoch_begin",
                                   "epoch": 3},
                clock_ms=1_700_000_000_123, pid=4242, stacks=stacks,
                tracer=tr, registry=reg), t0, tr


def test_bundle_schema_roundtrip_and_byte_determinism(tmp_path):
    bb.set_enabled(True)
    kw, _t0, _tr = _fixed_inputs(tmp_path)
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    p1 = bb.write_bundle(dirpath=d1, **kw)
    p2 = bb.write_bundle(dirpath=d2, **kw)
    assert p1 and p2
    # identical content AND identical digest-carrying file name
    assert open(p1, "rb").read() == open(p2, "rb").read()
    assert os.path.basename(p1) == os.path.basename(p2)
    bundle = json.load(open(p1))
    assert bb.validate_bundle(bundle) == []
    assert bundle["trigger"] == "crash.module.epoch_begin"
    assert bundle["host"] == "w7" and bundle["pid"] == 4242
    # the open-span snapshot survived serialization with its age
    [sp] = bundle["open_spans"]
    assert sp["name"] == "allreduce" and sp["age_ms"] == 4000.0
    # ring tails + env view + state provider + manifest row all landed
    assert any(r[2] == "health.nonfinite"
               for r in bundle["span_ring"]["records"])
    assert bundle["metrics_ring"]["series"][0]["gauges"] == \
        {"train.loss": 0.125}
    assert bundle["env"]["DT_HANG_S"] == "120"
    assert bundle["state"]["scheduler"]["workers"] == ["w0", "w1"]
    rows = bb.read_manifest(d1)
    assert len(rows) == 1 and rows[0]["kind"] == "bundle"
    assert rows[0]["file"] == os.path.basename(p1)
    # a corrupted bundle fails validation loudly
    assert bb.validate_bundle({k: v for k, v in bundle.items()
                               if k != "threads"})


def test_secret_env_values_are_redacted(monkeypatch):
    monkeypatch.setenv("DT_ELASTIC_SECRET", "hunter2")
    assert bb.env_view()["DT_ELASTIC_SECRET"] == "<redacted>"


def test_open_span_snapshot_nested_and_cross_thread():
    tr = obs_trace.Tracer(name="t", enabled=True)
    seen = {}
    release = threading.Event()
    entered = threading.Event()

    def other():
        with tr.span("worker.io"):
            entered.set()
            release.wait(5)

    t = threading.Thread(target=other, daemon=True)
    with tr.span("outer", {"epoch": 1}):
        with tr.span("inner"):
            t.start()
            entered.wait(5)
            seen["spans"] = tr.open_spans()
            release.set()
    t.join(5)
    names = [s["name"] for s in seen["spans"]]
    assert names == ["outer", "inner", "worker.io"]  # oldest first
    by = {s["name"]: s for s in seen["spans"]}
    # nesting reconstructs via parent ids; the cross-thread span carries
    # its own tid and no parent (it opened outside the caller's context)
    assert by["inner"]["parent"] == by["outer"]["sid"]
    assert by["worker.io"]["parent"] is None
    assert by["worker.io"]["tid"] != by["outer"]["tid"]
    assert by["outer"]["attrs"] == {"epoch": 1}
    # everything closed: the table drains (begin tokens too)
    t0 = tr.begin("allreduce")
    assert [s["name"] for s in tr.open_spans()] == ["allreduce"]
    tr.complete_span("allreduce", t0)
    assert tr.open_spans() == []
    # abandon() drops a failed attempt's entry without a record
    t1 = tr.begin("wire.request", {"cmd": "x"})
    tr.abandon(t1)
    assert tr.open_spans() == []


def test_open_span_table_armed_without_obs():
    """The bundle's 'died 40 s into allreduce' evidence must not require
    DT_OBS: with only the blackbox plane armed, spans enter/leave the
    open table but record NOTHING in the ring, and no trace context
    rides the wire token path."""
    bb.set_enabled(True)
    obs_trace.set_enabled(False)
    tr = obs_trace.Tracer(name="t")  # follows the (off) trace gate
    with tr.span("outer"):
        t0 = tr.begin("allreduce", {"key": "g"})
        assert t0 is not None  # open-table-only token
        assert [s["name"] for s in tr.open_spans()] == \
            ["outer", "allreduce"]
        tr.complete_span("allreduce", t0)
        assert [s["name"] for s in tr.open_spans()] == ["outer"]
    assert tr.open_spans() == []
    # nothing was recorded: the trace plane stays hard-off
    snap = tr.snapshot()
    assert snap["records"] == [] and snap["dropped"] == 0
    # an UNNAMED begin (wire trace-context path) stays None — no _tc
    # can ride the wire while tracing is off
    assert tr.begin() is None
    # disarm: back to the zero-cost noop singleton
    bb.set_enabled(False)
    assert tr.span("x") is tr.span("y")


def test_watchdog_fire_clear_edge_triggered(tmp_path):
    bb.set_enabled(True)
    clk = {"t": 0.0}
    tr = obs_trace.Tracer(name="t", enabled=True)
    dog = bb.Watchdog(host="w3", hang_seconds=2.0,
                      clock=lambda: clk["t"], tracer=tr,
                      dirpath=str(tmp_path / "wd"), start_thread=False)
    clk["t"] = 1.9
    assert not dog.tick()  # under threshold: quiet
    clk["t"] = 2.5
    assert dog.tick()      # fired once...
    assert not dog.tick()  # ...and stays edge-triggered while stalled
    assert dog.suspected()
    dog.beat(step=17)      # progress: clears
    assert not dog.suspected()
    clk["t"] = 6.0
    assert dog.tick()      # a NEW stall fires again
    evs = [r[2] for r in tr.snapshot()["records"] if r[0] == "i"]
    assert evs.count("hang.suspect") == 2
    assert evs.count("hang.clear") == 1
    # each firing wrote one live (non-fatal) bundle with the stall age
    rows = [r for r in bb.read_manifest(str(tmp_path / "wd"))
            if r.get("trigger") == "hang"]
    assert len(rows) == 2
    bundle = json.load(open(os.path.join(str(tmp_path / "wd"),
                                         rows[0]["file"])))
    assert bb.validate_bundle(bundle) == []
    assert not bundle["fatal"]
    assert bundle["extra"]["stalled_s"] == 2.5
    assert bundle["extra"]["hang_s"] == 2.0


def test_sigterm_handler_writes_bundle_from_real_subprocess(tmp_path):
    d = str(tmp_path / "sig")
    script = (
        "import os, sys, time, types\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "shim = types.ModuleType('dt_tpu')\n"
        f"shim.__path__ = [os.path.join({REPO!r}, 'dt_tpu')]\n"
        "sys.modules['dt_tpu'] = shim\n"
        "from dt_tpu.obs import blackbox\n"
        "blackbox.install(host='sig-child')\n"
        "print('ready', flush=True)\n"
        "time.sleep(60)\n")
    env = {**os.environ, "DT_BLACKBOX": "1", "DT_BLACKBOX_DIR": d}
    p = subprocess.Popen([sys.executable, "-c", script], env=env,
                         stdout=subprocess.PIPE, text=True)
    try:
        assert p.stdout.readline().strip() == "ready"
        p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=30)
    finally:
        if p.poll() is None:
            p.kill()
    # the handler re-raised the default disposition: death BY SIGTERM
    assert rc == -signal.SIGTERM
    rows = bb.read_manifest(d)
    sig_rows = [r for r in rows if r.get("trigger") == "signal.SIGTERM"]
    assert len(sig_rows) == 1 and sig_rows[0]["fatal"]
    bundle = json.load(open(os.path.join(d, sig_rows[0]["file"])))
    assert bb.validate_bundle(bundle) == []
    assert bundle["host"] == "sig-child"
    # the captured stacks include the main thread parked in sleep
    frames = [f for t in bundle["threads"] for f in t["frames"]]
    assert any("sleep" in str(f) or "<module>" in str(f[2])
               for f in frames)


def test_scheduler_fleet_detector_blames_waited_on_worker(tmp_path,
                                                         monkeypatch):
    """The fleet-side half: one worker contributes, the round waits on
    the other — the detector must blame the MISSING contributor (the
    victims that contributed look equally hung), edge-trigger
    hang.suspect, write a scheduler-side bundle, and serve it all over
    blackbox_index; round completion edge-triggers hang.clear."""
    import numpy as np
    bb.set_enabled(True)
    obs_trace.set_enabled(True)  # hang.* events ride the obs plane
    d = str(tmp_path / "sched")
    monkeypatch.setenv("DT_BLACKBOX_DIR", d)
    from dt_tpu.elastic import Scheduler, protocol
    sched = Scheduler(initial_workers=["w0", "w1"])
    try:
        done = {}

        def contribute(host, seq=0):
            done[host] = protocol.request(
                "127.0.0.1", sched.port,
                {"cmd": "allreduce", "host": host, "key": "g",
                 "seq": seq, "value": np.ones(4, np.float32)})

        t0 = threading.Thread(target=contribute, args=("w0",),
                              daemon=True)
        t0.start()
        deadline = time.time() + 10
        while not sched._dp.pending_rounds():
            assert time.time() < deadline, "round never became pending"
            time.sleep(0.01)
        time.sleep(0.05)  # let the round age past the test threshold
        suspect = sched._hang_tick(hang_seconds=0.01)
        assert suspect is not None
        assert suspect["blamed"] == "w1"
        assert suspect["waiting"] == ["w1"]
        assert suspect["round"] == "g"
        # edge-triggered: a second tick refreshes, doesn't re-bundle
        sched._hang_tick(hang_seconds=0.01)
        rows = [r for r in bb.read_manifest(d)
                if r.get("trigger") == "hang"]
        assert len(rows) == 1 and rows[0]["host"] == "scheduler"
        bundle = json.load(open(os.path.join(d, rows[0]["file"])))
        assert bb.validate_bundle(bundle) == []
        assert bundle["extra"]["blamed"] == "w1"
        # the scheduler's state provider stamped the bundle
        assert bundle["state"]["scheduler"]["workers"] == ["w0", "w1"]
        # blackbox_index serves the same story over the wire
        resp = protocol.request("127.0.0.1", sched.port,
                                {"cmd": "blackbox_index"})
        assert resp["enabled"] and resp["suspect"]["blamed"] == "w1"
        assert any(r.get("trigger") == "hang" for r in resp["bundles"])
        # complete the round: the suspect clears, edge-triggered
        contribute("w1")
        t0.join(10)
        assert done["w0"]["value"] is not None
        assert sched._hang_tick(hang_seconds=0.01) is None
        evs = [r[2] for r in sched._obs.snapshot()["records"]
               if r[0] == "i"]
        assert evs.count("hang.suspect") == 1
        assert evs.count("hang.clear") == 1
    finally:
        sched.close()


def test_disabled_path_allocates_nothing_measurable(tmp_path):
    import tracemalloc
    bb.set_enabled(False)
    clk = {"t": 0.0}
    for _ in range(64):  # warm every code path first
        bb.note("step", n=1)
        assert bb.write_bundle("x", dirpath=str(tmp_path)) is None
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(5000):
        bb.note("step", n=1)
        bb.write_bundle("x", dirpath=str(tmp_path))
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    retained = sum(
        s.size_diff for s in after.compare_to(before, "lineno")
        if s.size_diff > 0 and s.count_diff > 64 and s.traceback and
        s.traceback[0].filename.endswith(
            os.path.join("obs", "blackbox.py")))
    assert retained < 512, f"disabled path retained {retained} bytes"
    assert bb.flight_ring() == []
    assert not os.path.exists(bb.manifest_path(str(tmp_path)))
    del clk


def test_blackbox_on_wall_time_overhead_bounded():
    """The armed plane must not materially slow the control/data-plane
    loop (< 1.5x — the acceptance bound; mirrors the obs/metrics
    guards).  Interleaved off/on pairs, best pairwise ratio, so one
    quiet pair survives noisy shared CI."""
    import numpy as np
    bb.set_enabled(True)  # scheduler built WITH the plane (lag stamps on)
    from dt_tpu.elastic import Scheduler, protocol
    sched = Scheduler(initial_workers=["w0"])
    try:
        def trial(n=60):
            t0 = time.perf_counter()
            for i in range(n):
                protocol.request(
                    "127.0.0.1", sched.port,
                    {"cmd": "allreduce", "host": "w0", "key": "g",
                     "seq": trial.seq + i,
                     "value": np.ones(64, np.float32)})
                bb.note("step", i=i)
            trial.seq += n
            return time.perf_counter() - t0
        trial.seq = 0

        trial(20)  # warm the pooled channel + code paths
        ratios = []
        for _ in range(5):
            bb.set_enabled(False)
            off = trial()
            bb.set_enabled(True)
            on = trial()
            ratios.append(on / off)
        assert min(ratios) < 1.5, ratios
    finally:
        sched.close()


def test_bundle_retention_pruned_oldest_first(tmp_path, monkeypatch):
    """DT_BLACKBOX_MAX_BUNDLES bounds total on-disk retention (a long
    job with recurring hang episodes must not fill the disk): oldest
    bundles pruned on write, manifest rows kept."""
    monkeypatch.setenv("DT_BLACKBOX_MAX_BUNDLES", "3")
    bb.set_enabled(True)
    d = str(tmp_path / "ret")
    for i in range(5):
        assert bb.write_bundle(f"t{i}", dirpath=d,
                               clock_ms=1_700_000_000_000 + i, pid=1)
    names = sorted(n for n in os.listdir(d) if n.startswith("bb-"))
    assert len(names) == 3
    # bb-<ts>-<pid>-<trigger>-<digest>.json: field 3 is the trigger
    assert [n.split("-")[3] for n in names] == \
        ["t2", "t3", "t4"]  # oldest two pruned
    assert len(bb.read_manifest(d)) == 5  # the record survives pruning


def test_manifest_accumulates_rows_across_processes(tmp_path):
    """Rows from several processes (distinct pids/outcomes) accumulate
    append-only and survive a torn final line."""
    d = str(tmp_path / "probe")
    for pid, outcome in ((101, "unavailable"), (102, "unavailable"),
                         (103, "success")):
        assert bb.manifest_append({"kind": "probe", "phase": "start",
                                   "ts_ms": pid * 1000, "pid": pid,
                                   "host": "prober"}, dirpath=d)
        assert bb.manifest_append({"kind": "probe", "phase": "end",
                                   "ts_ms": pid * 1000 + 500, "pid": pid,
                                   "host": "prober",
                                   "outcome": outcome,
                                   "duration_s": 1500.0}, dirpath=d)
    with open(bb.manifest_path(d), "a") as f:
        f.write('{"torn": ')  # crash mid-append
    rows = bb.read_manifest(d)
    assert len(rows) == 6
    assert [r["outcome"] for r in rows if r.get("phase") == "end"] == \
        ["unavailable", "unavailable", "success"]


def test_postmortem_render_golden(tmp_path, monkeypatch):
    """dtop --postmortem renders the committed golden byte-for-byte
    from a pinned bundle (deterministic: injected clocks/stacks, UTC
    timestamps) — the report format is a contract, like the Prometheus
    exposition golden."""
    # registry-default env only: the bundle's resolved env view (and so
    # the render's non-default-env line) must not leak CI-local knobs
    for k in list(os.environ):
        if k.startswith("DT_"):
            monkeypatch.delenv(k)
    bb.set_enabled(True)
    kw, _t0, _tr = _fixed_inputs(tmp_path)
    d = str(tmp_path / "golden")
    path = bb.write_bundle(dirpath=d, **kw)
    r = subprocess.run([sys.executable, DTOP, "--postmortem", path],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout == open(GOLDEN).read()
    # dir mode picks the newest bundle and renders the same report
    r2 = subprocess.run([sys.executable, DTOP, "--postmortem", d],
                        capture_output=True, text=True, timeout=120)
    assert r2.returncode == 0 and r2.stdout == r.stdout
