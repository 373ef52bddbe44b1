"""dt_tpu.obs.device — the r18 device plane: compile observatory +
recompile-cause ledger, HBM/memory gauges, OOM census bundles, watchdog
compile labeling, the profile_capture wire command, and dtop's device
board (reference analog: none — MXNet's profiler needed a live process
and saw op timelines only, ``src/profiler/profiler.h:256``; its memory
story was the offline ``example/memcost`` table)."""

import json
import os
import subprocess
import sys
import time

import pytest

from dt_tpu.obs import blackbox as bb
from dt_tpu.obs import device as dev
from dt_tpu.obs import metrics as obs_metrics
from dt_tpu.obs import trace as obs_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DTOP = os.path.join(REPO, "tools", "dtop.py")
GOLDEN = os.path.join(REPO, "tests", "fixtures", "device_board.golden")


@pytest.fixture(autouse=True)
def _clean_device_plane(tmp_path, monkeypatch):
    """Each test starts (and leaves) the plane reset — the ledger and
    capture state are process-shared, same discipline as the blackbox
    fixture."""
    dev._reset_for_tests()
    bb._reset_for_tests()
    monkeypatch.setenv("DT_BLACKBOX_DIR", str(tmp_path / "bbdir"))
    yield
    dev.set_enabled(None)
    dev._reset_for_tests()
    bb.set_enabled(None)
    bb._reset_for_tests()
    obs_trace.set_enabled(None)
    obs_trace.tracer().reset_counters()
    obs_trace.tracer().drain()


# ---------------------------------------------------------------------------
# recompile-cause ledger (pinned number-by-number under injected inputs)
# ---------------------------------------------------------------------------


def test_signature_diff_ledger_pinned():
    dev.set_enabled(True)
    tr = obs_trace.Tracer(name="t", enabled=True)
    s1 = dev._sig_of((_Arr((4, 8), "float32"),),
                     {"mesh": {"data": 2}, "donate": (0,)})
    # identical inputs -> identical digest (the jit cache-key contract)
    assert dev._sig_of((_Arr((4, 8), "float32"),),
                       {"mesh": {"data": 2}, "donate": (0,)}) == s1
    s_shape = dev._sig_of((_Arr((8, 8), "float32"),),
                          {"mesh": {"data": 2}, "donate": (0,)})
    s_dtype = dev._sig_of((_Arr((4, 8), "bfloat16"),),
                          {"mesh": {"data": 2}, "donate": (0,)})
    s_mesh = dev._sig_of((_Arr((4, 8), "float32"),),
                         {"mesh": {"data": 4}, "donate": (0,)})

    assert dev._record_compile("train_step", s1, 100.0, "miss", None,
                               tracer=tr, now_ms=1000) is None
    r1 = dev._record_compile("train_step", s_shape, 50.0, "hit",
                             {"peak_mb": 12.5}, tracer=tr, now_ms=2000)
    assert r1["changed"] == ["shape"]
    assert r1["prev"] == s1["digest"] and r1["new"] == s_shape["digest"]
    r2 = dev._record_compile("train_step", s_dtype, 25.0, "off", None,
                             tracer=tr, now_ms=3000)
    assert sorted(r2["changed"]) == ["dtype", "shape"]
    r3 = dev._record_compile("train_step", s_mesh, 10.0, "miss", None,
                             tracer=tr, now_ms=4000)
    assert r3["changed"] == ["dtype", "mesh"]  # vs the PREVIOUS sig
    # the identical-signature elastic rebuild is named, not hidden
    r4 = dev._record_compile("train_step", s_mesh, 5.0, "hit", None,
                             tracer=tr, now_ms=5000)
    assert r4["changed"] == ["rebuild"]

    s = dev.summary()
    assert s["compiles"] == 5 and s["recompiles"] == 4
    assert s["cache_hits"] == 2 and s["cache_misses"] == 2
    assert s["ms_total"] == 190.0
    assert s["by_what"]["train_step"]["builds"] == 5
    # the last KNOWN estimate is retained across builds that report none
    assert s["by_what"]["train_step"]["mem"] == {"peak_mb": 12.5}
    assert [r["changed"] for r in s["recompile_log"]] == \
        [["shape"], ["shape", "dtype"], ["dtype", "mesh"], ["rebuild"]]
    # counters + events landed on the injected tracer
    assert tr.get_counter("compile.compiles") == 5
    assert tr.get_counter("compile.cache_hits") == 2
    assert tr.get_counter("compile.cache_misses") == 2
    evs = [r for r in tr.snapshot()["records"]
           if r[0] == "i" and r[2] == "compile.recompile"]
    assert len(evs) == 4 and evs[0][8]["changed"] == ["shape"]


class _Arr:
    """Shape/dtype-only stand-in for signature tests (jax-free)."""

    def __init__(self, shape, dtype):
        self.shape = shape
        self.dtype = dtype


# ---------------------------------------------------------------------------
# instrument(): real jit wrap — spans, cache probe, off-path identity
# ---------------------------------------------------------------------------


def test_instrument_real_jit_records_compile_spans(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    dev.set_enabled(True)
    obs_trace.set_enabled(True)
    f = dev.instrument("toy", jax.jit(lambda x: x * 2),
                       {"mesh": {"data": 1}, "donate": ()})
    import numpy as np
    out = f(jnp.ones(4))
    assert np.allclose(np.asarray(out), 2.0)
    assert np.allclose(np.asarray(f(jnp.ones(4))), 2.0)  # cached exec
    assert np.allclose(np.asarray(f(jnp.ones(8))), 2.0)  # new shape
    s = dev.summary()
    assert s["by_what"]["toy"]["builds"] == 2
    assert s["recompiles"] == 1
    assert s["recompile_log"][-1]["changed"] == ["shape"]
    spans = [r for r in obs_trace.tracer().drain()
             if r[0] == "X" and r[2] == "compile.toy"]
    assert len(spans) == 2
    assert spans[0][8]["what"] == "toy"
    # the open-span table drained (no phantom compile for the watchdog)
    assert dev.compiling() is None


def test_instrument_off_path_returns_fn_unchanged():
    dev.set_enabled(False)
    fn = object()
    assert dev.instrument("x", fn) is fn
    assert dev.wire_payload() is None
    assert dev.metrics_hook() is None


@pytest.fixture
def _restore_cache_dir():
    import jax
    orig = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_enable_compilation_cache)
    yield
    jax.config.update("jax_compilation_cache_dir", orig[0])
    jax.config.update("jax_enable_compilation_cache", orig[1])


def test_cache_placed_from_outside_is_left_alone(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and
    ``enable_compilation_cache`` sets no directory in code (a fresh
    interpreter — jax reads the variable at import)."""
    d = str(tmp_path / "outside")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from dt_tpu import config\n"
         "print(config.enable_compilation_cache())\n"
         "print(jax.config.jax_compilation_cache_dir)"],
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": d, "PYTHONPATH": REPO},
        capture_output=True, text=True, timeout=120, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [d, d]


def test_cache_defaults_to_checkout_xla_cache(_restore_cache_dir):
    """Variable unset: the cache goes to the fixed ``<checkout>/.xla_cache``
    (the path is part of the cache key — never a temp name), and a second
    call changes nothing."""
    import jax
    from dt_tpu import config
    jax.config.update("jax_compilation_cache_dir", None)
    want = os.path.join(REPO, ".xla_cache")
    assert config.enable_compilation_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert config.enable_compilation_cache() == want


def test_ledger_cache_outcome_follows_jax_events_and_effective_setting(
        tmp_path, _restore_cache_dir):
    """``hit`` / ``miss`` / ``off`` in the ``compile.<what>`` span and the
    ledger's totals are what jax's own cache events said of that build
    (read from the build account's rows), under whatever cache setting is
    in effect: disabled (conftest), enabled with a directory (a fresh
    program is written, a second build of it is served), enabled with the
    directory taken away.  jax decides once a process whether the cache is
    used: ``reset_cache`` makes it look again."""
    import jax
    import jax.numpy as jnp
    from jax._src import compilation_cache
    dev.set_enabled(True)
    obs_trace.set_enabled(True)
    small = (jax.config.jax_persistent_cache_min_compile_time_secs,
             jax.config.jax_persistent_cache_min_entry_size_bytes)

    def build(k):
        """A new jit object of a program that differs only by ``k`` ->
        (the span's attrs, the ledger's hits, its misses)."""
        f = dev.instrument("toy", jax.jit(lambda x: x * k + 1), {})
        f(jnp.ones(16))
        span = [r for r in obs_trace.tracer().drain()
                if r[0] == "X" and r[2] == "compile.toy"][-1]
        s = dev.summary()
        return span[8], s["cache_hits"], s["cache_misses"]

    try:
        attrs, hits, misses = build(2.0)
        assert (attrs["cache"], hits, misses) == ("off", 0, 0)
        # the stages are jax's own readings of this build
        assert attrs["backend_ms"] > 0 and attrs["lower_ms"] > 0
        assert attrs["trace_ms"] + attrs["lower_ms"] + attrs["backend_ms"] \
            <= attrs["elapsed_ms"] + 0.1
        jax.config.update("jax_compilation_cache_dir",
                          str(tmp_path / "jaxcache"))
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        assert build(3.0)[0]["cache"] == "miss"     # asked, then written
        attrs, hits, misses = build(3.0)            # the same program
        assert (attrs["cache"], hits, misses) == ("hit", 1, 1)
        assert os.listdir(str(tmp_path / "jaxcache"))
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        assert build(5.0)[0]["cache"] == "off"      # placed but disabled
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", None)
        compilation_cache.reset_cache()
        attrs, hits, misses = build(6.0)            # enabled, nowhere
        assert (attrs["cache"], hits, misses) == ("off", 1, 1)
        log = dev.summary()["recompile_log"]
        assert [r["cache"] for r in log[:3]] == ["miss", "hit", "off"]
        assert all("backend_ms" in r and "trace_ms" in r for r in log)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          small[0])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          small[1])
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()


# ---------------------------------------------------------------------------
# memory plane: injected device stats, RSS fallback, staging, census
# ---------------------------------------------------------------------------


class _FakeDevice:
    def __init__(self, i, stats):
        self.id = i
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_memory_gauges_with_injected_stats():
    dev.set_enabled(True)
    reg = obs_metrics.MetricsRegistry(name="t", enabled=True)
    devices = [
        _FakeDevice(0, {"bytes_in_use": 100, "peak_bytes_in_use": 200,
                        "bytes_limit": 1000}),
        _FakeDevice(1, {"bytes_in_use": 50, "peak_bytes_in_use": 60,
                        "bytes_limit": 1000}),
        _FakeDevice(2, None),  # CPU-style: no stats, skipped
    ]
    snap = dev.sample_into(reg, devices=devices)
    assert [d["id"] for d in snap["devices"]] == [0, 1]
    g = {(n, tuple(sorted(lk.items()))): v
         for n, lk, v in reg.gauges_export()}
    assert g[("device.hbm_bytes", (("device", "0"),))] == 100.0
    assert g[("device.hbm_peak_bytes", (("device", "1"),))] == 60.0
    assert g[("device.hbm_limit_bytes", (("device", "0"),))] == 1000.0
    # the host fallback gauge is always there (unlabeled -> it rides
    # the time-series ring too)
    assert g[("device.host_rss_bytes", ())] > 0


def test_staging_occupancy_and_census_provenance():
    dev.set_enabled(True)
    from dt_tpu.training.overlap import StagingPool
    pool = StagingPool(1 << 20)
    dev.register_staging(pool)
    import numpy as np
    buf = pool.acquire(256, np.float32)
    snap = dev.memory_snapshot(devices=[])
    assert snap["staging"]["outstanding"] == 1
    pool.release(buf)
    snap = dev.memory_snapshot(devices=[])
    assert snap["staging"]["outstanding"] == 0
    assert snap["staging"]["bytes"] == 256 * 4
    # census groups by (shape, dtype) and tags via registered shape sets
    dev.register_provenance(
        "params", lambda: {("(4, 8)", "float32")})
    arrays = [_Arr((4, 8), "float32"), _Arr((4, 8), "float32"),
              _Arr((128,), "int32")]
    rows = dev.live_buffer_census(arrays=arrays)
    # ranked by total group bytes: the single (128,) int32 (512 B)
    # outranks the two 128 B float32 buffers (256 B together)
    assert rows[0] == {"shape": "(128,)", "dtype": "int32",
                       "count": 1, "bytes": 512, "tag": ""}
    assert rows[1] == {"shape": "(4, 8)", "dtype": "float32",
                       "count": 2, "bytes": 256, "tag": "params"}


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------


def test_oom_bundle_schema_and_census(tmp_path, monkeypatch):
    dev.set_enabled(True)
    bb.set_enabled(True)
    monkeypatch.setenv("DT_BLACKBOX_DIR", str(tmp_path / "oom"))
    dev.register_provenance("params", lambda: {("(64,)", "float32")})
    err = RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 17179869184 bytes")
    assert dev.is_oom(err)
    assert not dev.is_oom(ValueError("shape mismatch"))
    path = dev.maybe_oom_bundle(err, host="w5")
    assert path is not None
    bundle = json.load(open(path))
    assert bb.validate_bundle(bundle) == []
    assert bundle["trigger"] == "oom" and bundle["fatal"]
    assert bundle["host"] == "w5"
    assert "RESOURCE_EXHAUSTED" in bundle["extra"]["error"]
    assert isinstance(bundle["extra"]["census"], list)
    # the device state provider stamped the bundle too
    assert "device" in bundle["state"]
    assert bundle["state"]["device"]["compile"]["compiles"] == 0
    # a non-OOM error writes nothing
    assert dev.maybe_oom_bundle(ValueError("x")) is None


# ---------------------------------------------------------------------------
# watchdog compile labeling (the --plan hang first-bundle fix)
# ---------------------------------------------------------------------------


def test_watchdog_labels_compile_in_progress(tmp_path):
    dev.set_enabled(True)
    bb.set_enabled(True)
    clk = {"t": 0.0}
    tr = obs_trace.Tracer(name="t", enabled=True)
    dog = bb.Watchdog(host="w1", hang_seconds=2.0,
                      clock=lambda: clk["t"], tracer=tr,
                      dirpath=str(tmp_path / "wd"), start_thread=False)
    # a compile.* span is OPEN on the watchdog's tracer: the stall is
    # (so far) the XLA compiler working, and the bundle says so
    t0 = tr.begin("compile.train_step", {"what": "train_step"})
    clk["t"] = 2.5
    assert dog.tick()
    tr.complete_span("compile.train_step", t0)
    dog.beat(step=0)  # clears
    clk["t"] = 6.0
    assert dog.tick()  # a NEW stall with NO open compile: unlabeled
    rows = sorted((r for r in bb.read_manifest(str(tmp_path / "wd"))
                   if r.get("trigger") == "hang"),
                  key=lambda r: r.get("ts_ms", 0))
    assert len(rows) == 2
    b1 = json.load(open(os.path.join(str(tmp_path / "wd"),
                                     rows[0]["file"])))
    b2 = json.load(open(os.path.join(str(tmp_path / "wd"),
                                     rows[1]["file"])))
    assert b1["extra"]["compile_in_progress"] is True
    assert b1["extra"]["compile"] == "compile.train_step"
    assert "compile_in_progress" not in b2["extra"]
    evs = [r[8] for r in tr.snapshot()["records"]
           if r[0] == "i" and r[2] == "hang.suspect"]
    assert evs[0].get("compile") == "compile.train_step"
    assert "compile" not in evs[1]


def test_fleet_detector_demotes_compiling_worker(monkeypatch, tmp_path):
    """Scheduler half of the hang fix: among the waited-on workers, one
    that reported compiling on its heartbeat is blamed only when no
    non-compiling waiter exists — and the suspect carries the label."""
    import numpy as np
    import threading
    bb.set_enabled(True)
    obs_trace.set_enabled(True)
    monkeypatch.setenv("DT_BLACKBOX_DIR", str(tmp_path / "sched"))
    from dt_tpu.elastic import Scheduler, protocol
    sched = Scheduler(initial_workers=["w0", "w1", "w2"])
    try:
        def contribute(host):
            protocol.request("127.0.0.1", sched.port,
                             {"cmd": "allreduce", "host": host,
                              "key": "g", "seq": 0,
                              "value": np.ones(2, np.float32)})

        t = threading.Thread(target=contribute, args=("w0",),
                             daemon=True)
        t.start()
        deadline = time.time() + 10
        while not sched._dp.pending_rounds():
            assert time.time() < deadline
            time.sleep(0.01)
        # w2 is straggling worse (higher EWMA would blame it), but its
        # heartbeat device view says it is mid-compile -> w1 is blamed
        sched._dev_ingest("w2", {"compiling": "compile.train_step",
                                 "compile": {"compiles": 1}})
        time.sleep(0.05)
        suspect = sched._hang_tick(hang_seconds=0.01)
        assert suspect is not None
        assert set(suspect["waiting"]) == {"w1", "w2"}
        assert suspect["blamed"] == "w1"
        assert suspect["compiling"] == ["w2"]
        assert "compile_in_progress" not in suspect
        # obs_dump/health carry the device section
        dump = sched.obs_dump()
        assert dump["device"]["workers"]["w2"]["compiling"] == \
            "compile.train_step"
        assert dump["device"]["compiling"] == ["w2"]
        # dseq guard: a delayed OLD heartbeat must not roll the view
        # back (resurrecting the cleared compiling flag)
        sched._dev_ingest("w2", {"dseq": 5, "compiling": None,
                                 "compile": {"compiles": 2}})
        sched._dev_ingest("w2", {"dseq": 3,
                                 "compiling": "compile.train_step",
                                 "compile": {"compiles": 1}})
        assert sched.obs_dump()["device"]["workers"]["w2"][
            "compiling"] is None
        # the suspect's conditional labels CLEAR on refresh — a
        # finished compile must not keep labeling a now-genuine wedge
        suspect2 = sched._hang_tick(hang_seconds=0.01)
        assert suspect2 is not None
        assert "compiling" not in suspect2
        assert "compile_in_progress" not in suspect2
        # an eviction scrubs the view
        sched._dev_forget({"w2"})
        assert "device" not in sched.obs_dump()
        for h in ("w1", "w2"):
            threading.Thread(target=contribute, args=(h,),
                             daemon=True).start()
        t.join(10)
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# profile_capture: scheduler queue + heartbeat delivery + worker tick
# ---------------------------------------------------------------------------


def test_profile_capture_command_flow(monkeypatch, tmp_path):
    dev.set_enabled(True)
    bb.set_enabled(True)
    d = str(tmp_path / "cap")
    monkeypatch.setenv("DT_BLACKBOX_DIR", d)
    from dt_tpu.elastic import Scheduler, protocol
    sched = Scheduler(initial_workers=["w0", "w1"])
    try:
        resp = protocol.request(
            "127.0.0.1", sched.port,
            {"cmd": "profile_capture", "host": "op", "target": "w1",
             "steps": 3, "post_seq": 1})
        assert resp["seq"] == 1
        # an at-least-once retry returns the SAME seq, no re-queue
        again = protocol.request(
            "127.0.0.1", sched.port,
            {"cmd": "profile_capture", "host": "op", "target": "w1",
             "steps": 3, "post_seq": 1})
        assert again["seq"] == 1
        # the command rides only the TARGET's heartbeat, keyed past cseq
        hb = protocol.request(
            "127.0.0.1", sched.port,
            {"cmd": "heartbeat", "host": "w0", "pseq": 0,
             "dev": {"cseq": 0}})
        assert "capture_cmds" not in hb
        hb = protocol.request(
            "127.0.0.1", sched.port,
            {"cmd": "heartbeat", "host": "w1", "pseq": 0,
             "dev": {"cseq": 0}})
        assert hb["capture_cmds"] == [{"seq": 1, "target": "w1",
                                       "steps": 3}]
        # worker side: armed once (seq guard), bounded by tick count
        started, stopped = [], []
        monkeypatch.setattr(dev, "_start_trace", started.append)
        monkeypatch.setattr(dev, "_stop_trace",
                            lambda: stopped.append(True))
        assert dev.handle_capture_cmds(hb["capture_cmds"],
                                       host="w1") == 1
        assert dev.handle_capture_cmds(hb["capture_cmds"],
                                       host="w1") == 0  # re-delivery
        assert dev.capture_seq() == 1
        # the NEXT heartbeat's dev payload stops re-delivery at source
        hb2 = protocol.request(
            "127.0.0.1", sched.port,
            {"cmd": "heartbeat", "host": "w1", "pseq": 0,
             "dev": {"cseq": dev.capture_seq()}})
        assert "capture_cmds" not in hb2
        # a second command arriving while one is pending must NOT be
        # consumed-and-dropped: the seq cursor stays put so heartbeat
        # re-delivery can arm it once the slot frees
        assert dev.handle_capture_cmds(
            [{"seq": 2, "target": "w1", "steps": 1}], host="w1") == 0
        assert dev.capture_seq() == 1
        for _ in range(4):
            dev.capture_tick()
        assert len(started) == 1 and stopped == [True]
        rows = [r for r in bb.read_manifest(d)
                if r.get("kind") == "profile_capture"]
        assert len(rows) == 1 and rows[0]["steps"] == 3
        assert rows[0]["host"] == "w1"
        dev.capture_tick()  # disarmed: no-op
        assert len(started) == 1
        # slot free: the re-delivered command arms now
        assert dev.handle_capture_cmds(
            [{"seq": 2, "target": "w1", "steps": 1}], host="w1") == 1
        assert dev.capture_seq() == 2
        # a typo'd/absent target fails loudly, never "queued: true"
        bad = protocol.request(
            "127.0.0.1", sched.port,
            {"cmd": "profile_capture", "host": "op", "target": "w9",
             "steps": 3, "post_seq": 2})
        assert "not a live worker" in bad.get("error", "")
    finally:
        sched.close()


def test_capture_abort_closes_out_truncated_trace(monkeypatch, tmp_path):
    """A capture the step loop cannot finish (fit exits mid-capture)
    must stop the profiler and leave an aborted manifest row — never a
    silently-open trace."""
    dev.set_enabled(True)
    bb.set_enabled(True)
    d = str(tmp_path / "abort")
    monkeypatch.setenv("DT_BLACKBOX_DIR", d)
    started, stopped = [], []
    monkeypatch.setattr(dev, "_start_trace", started.append)
    monkeypatch.setattr(dev, "_stop_trace",
                        lambda: stopped.append(True))
    dev.capture_abort()  # nothing armed: no-op
    assert stopped == []
    assert dev.arm_capture(8, seq=1, host="w1")
    dev.capture_abort()  # armed but never started: just disarms
    assert stopped == []
    assert dev.arm_capture(8, seq=2, host="w1")
    dev.capture_tick()  # starts
    dev.capture_tick()  # 1 of 8 done
    dev.capture_abort()
    assert stopped == [True]
    [row] = [r for r in bb.read_manifest(d)
             if r.get("kind") == "profile_capture"]
    assert row["aborted"] and row["steps"] == 1
    assert row["requested_steps"] == 8
    dev.capture_tick()  # disarmed: no restart
    assert len(started) == 1


# ---------------------------------------------------------------------------
# guards: disabled-path retention + on/off wall time
# ---------------------------------------------------------------------------


def test_disabled_path_allocates_nothing_measurable():
    import tracemalloc
    dev.set_enabled(False)
    fn = object()
    for _ in range(64):  # warm every code path
        assert dev.instrument("x", fn) is fn
        assert dev.wire_payload() is None
        dev.capture_tick()
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(5000):
        dev.instrument("x", fn)
        dev.wire_payload()
        dev.capture_tick()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    retained = sum(
        s.size_diff for s in after.compare_to(before, "lineno")
        if s.size_diff > 0 and s.count_diff > 64 and s.traceback and
        s.traceback[0].filename.endswith(
            os.path.join("obs", "device.py")))
    assert retained < 512, f"disabled path retained {retained} bytes"
    assert dev.summary()["compiles"] == 0


def test_instrumented_step_wall_time_overhead_bounded():
    """The armed wrapper must not materially slow the steady-state step
    (< 1.5x on vs off — the house bound).  The workload is a
    realistically-sized step (~0.5 ms of compute, like the metrics
    plane's loopback-allreduce guard): the wrapper's per-call cost is a
    shape-tuple key + the AOT executable's python dispatch — tens of
    microseconds, which only looks large against a degenerate
    microseconds-long program no real training step resembles.
    Interleaved off/on pairs, best pairwise ratio, so one quiet pair
    survives noisy CI."""
    import jax
    import jax.numpy as jnp

    def step(a):
        for _ in range(4):
            a = jnp.tanh(a @ a)
        return a

    x = jnp.ones((256, 256))
    plain = jax.jit(step)
    plain(x).block_until_ready()  # compile once outside the timing
    dev.set_enabled(True)
    wrapped = dev.instrument("wt", jax.jit(step))
    wrapped(x).block_until_ready()

    def trial(f, n=60):
        t0 = time.perf_counter()
        for _ in range(n):
            f(x)
        jax.block_until_ready(f(x))
        return time.perf_counter() - t0

    trial(plain, 20)
    trial(wrapped, 20)
    ratios = []
    for _ in range(5):
        off = trial(plain)
        on = trial(wrapped)
        ratios.append(on / off)
    assert min(ratios) < 1.5, ratios


# ---------------------------------------------------------------------------
# dtop device-board golden (render contract, like the postmortem golden)
# ---------------------------------------------------------------------------


def _board_summary():
    """A pinned summary with a device section (the .metrics.json shape
    dtop consumes)."""
    return {
        "tracks": {
            "w0#100": {"steps": {"count": 4, "p50_ms": 10.0,
                                 "p90_ms": 12.0, "p99_ms": 14.0},
                       "stall_ms": {}, "pipeline_ms": {}, "faults": {},
                       "retries": 0, "dropped": 0, "counters": {}},
        },
        "membership_changes": [],
        "device": {
            "compiling": ["w1"],
            "workers": {
                "w0": {"compiling": None,
                       "compile": {"compiles": 4, "recompiles": 1,
                                   "cache_hits": 3, "cache_misses": 1,
                                   "ms_total": 1234.0,
                                   "est": {"peak_mb": 96.0}},
                       "mem": {"devices": [
                           {"id": 0, "bytes_in_use": 104857600,
                            "peak_bytes_in_use": 115343360,
                            "bytes_limit": 1073741824}],
                           "staging": {"bytes": 4194304,
                                       "outstanding": 2}}},
                "w1": {"compiling": "compile.train_step",
                       "compile": {"compiles": 2, "recompiles": 0,
                                   "cache_hits": 0, "cache_misses": 2,
                                   "ms_total": 800.0, "est": None},
                       "mem": {"host_rss_bytes": 268435456}},
            },
            "recompiles_by_track": {
                "w0#100": [{"ts": 5, "what": "train_step",
                            "changed": ["mesh"], "cache": "hit"}]},
        },
    }


def test_dtop_device_board_golden(tmp_path):
    from dt_tpu.obs import export as obs_export
    # round-trip through the export so the golden also pins the
    # otherData threading: job device section -> chrome -> summary
    job = {"tracks": {"w0#100": {"records": [], "counters": {},
                                 "dropped": 0}},
           "device": _board_summary()["device"]}
    chrome = obs_export.chrome_trace(job)
    summary = obs_export.summarize_chrome(chrome)
    assert summary["device"]["workers"]["w1"]["compiling"] == \
        "compile.train_step"
    # golden: the rendered board section is a contract
    trace = str(tmp_path / "t.json")
    with open(trace, "w") as f:
        json.dump(chrome, f)
    r = subprocess.run([sys.executable, DTOP, trace],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    start = r.stdout.index("device board")
    board = r.stdout[start:].split("\n\n")[0] + "\n"
    assert board == open(GOLDEN).read(), board


def test_export_threads_device_section_and_recompile_events():
    from dt_tpu.obs import export as obs_export
    tr = obs_trace.Tracer(name="w", capacity=64, enabled=True,
                          wall_clock=lambda: 1_000_000_000,
                          mono_clock=lambda: 0, ident=lambda: 1)
    tr.event("compile.recompile", {"what": "train_step",
                                   "changed": ["rebuild"],
                                   "cache": "hit", "elapsed_ms": 4.0})
    job = {"tracks": {"w0#1": tr.snapshot()},
           "device": {"workers": {"w0": {"compile": {"compiles": 2}}},
                      "compiling": []}}
    summary = obs_export.summarize_chrome(obs_export.chrome_trace(job))
    assert summary["device"]["workers"]["w0"]["compile"]["compiles"] == 2
    [ev] = summary["device"]["recompiles_by_track"]["w0#1"]
    assert ev["what"] == "train_step" and ev["changed"] == ["rebuild"]
    assert ev["cache"] == "hit"
