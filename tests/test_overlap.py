"""Overlapped gradient pipeline (r10): bucketed D2H -> wire -> H2D.

The overlap engine (``training/overlap.py`` +
``elastic/client.py::AllreducePipeline``) restructures the host-sync
step the way the reference's dependency engine overlapped per-layer
push/pull with backward compute (``src/kvstore/kvstore_dist.h:326-449``)
— these tests pin its CONTRACT:

- bit-identical final params vs the serial path, raw and 2-bit, on the
  8-device CPU mesh (the semantics-preserving requirement);
- ``DT_AR_OVERLAP=0`` escape hatch really restores the serial path;
- a ``reset`` mid-bucket retries ONLY that bucket's round through the
  idempotency replay window (exact averages, single re-dispatch);
- a membership change mid-pipeline completes parked bucket rounds with
  the survivors, and a mid-pipeline error drains the comm thread
  without leaking staging buffers.
"""

import threading
import time

import numpy as np
import pytest

from dt_tpu.elastic import Scheduler, WorkerClient, faults
from dt_tpu.elastic.faults import FaultPlan, FaultRule
from dt_tpu.training import overlap as overlap_lib


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("DT_DROP_MSG", raising=False)
    monkeypatch.delenv("DT_FAULT_PLAN", raising=False)
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# bucket grid
# ---------------------------------------------------------------------------

def test_bucket_bounds_grid_and_cache():
    b = overlap_lib.bucket_bounds(10_000, 4, 4096)  # 1024 elems/bucket
    assert b[0] == (0, 1024) and b[-1] == (9216, 10_000)
    assert all(y - x == 1024 for x, y in b[:-1])
    # contiguous, total coverage
    assert all(b[i][1] == b[i + 1][0] for i in range(len(b) - 1))
    # quantum alignment (2-bit packing words): every boundary except the
    # tail is a multiple of 16
    bq = overlap_lib.bucket_bounds(1000, 4, 100, quantum=16)
    assert all(x % 16 == 0 for x, _ in bq)
    assert bq[-1][1] == 1000
    # cached per unravel spec: same args -> the same tuple object
    assert overlap_lib.bucket_bounds(10_000, 4, 4096) is b
    # degenerate: bucket >= vector -> one bucket; empty vector safe
    assert overlap_lib.bucket_bounds(10, 4, 1 << 20) == ((0, 10),)
    assert overlap_lib.bucket_bounds(0, 4, 1 << 20) == ((0, 0),)


# ---------------------------------------------------------------------------
# bit-exactness vs serial on the 8-device CPU mesh
# ---------------------------------------------------------------------------

def _bn_net():
    """Tiny conv+BN net: batch_stats make the ``"stats"`` aux round ride
    the pipeline (an MLP would leave it untested)."""
    import flax.linen as linen
    import jax
    import jax.numpy as jnp
    from dt_tpu.models.common import bn

    class Net(linen.Module):
        @linen.compact
        def __call__(self, x, training=True):
            x = linen.Conv(4, (3, 3), padding="SAME", use_bias=False)(x)
            x = bn(training)(x)
            x = jax.nn.relu(x)
            x = jnp.mean(x, axis=(1, 2))
            return linen.Dense(2)(x)
    return Net()


def _run_host_pair(overlap_on, compress, monkeypatch, bucket_bytes=256):
    """Two in-process workers through Module.fit host-sync; returns the
    concatenated final params+stats vector (asserted identical across
    the pair).  Each worker's jit steps are compiled on the MAIN thread
    before the fit threads start — two threads tracing/compiling XLA
    programs concurrently on this 2-core box can wedge for minutes, and
    that contention is orthogonal to what these tests pin.  Each worker
    also gets a DISJOINT 4-device submesh: two concurrent 8-device
    programs share every device thread, and XLA CPU's collective
    rendezvous can starve one program behind the other indefinitely;
    disjoint submeshes keep each program's rendezvous self-contained
    (the real deployment runs one process per worker anyway)."""
    import jax
    from dt_tpu import data, parallel
    from dt_tpu.parallel import mesh as mesh_lib
    from dt_tpu.training import Module, metrics

    monkeypatch.setenv("DT_AR_OVERLAP", "1" if overlap_on else "0")
    # tiny buckets: the ~300-param model must split into MANY buckets or
    # the pipeline degenerates to one round and tests nothing
    monkeypatch.setenv("DT_AR_BUCKET_BYTES", str(bucket_bytes))
    s = Scheduler(initial_workers=["w0", "w1"])
    rng = np.random.RandomState(5)
    X = rng.uniform(-1, 1, (32, 6, 6, 1)).astype(np.float32)
    Y = rng.randint(0, 2, 32)
    out, errs = {}, {}

    mods = {}
    devs = jax.devices()
    try:
        for wi, host in enumerate(("w0", "w1")):
            cli = WorkerClient("127.0.0.1", s.port, host=host)
            kv = parallel.create("dist_sync")
            kv.set_controller(cli)
            if compress:
                kv.set_gradient_compression({"type": "2bit",
                                             "threshold": 0.05})
            mod = Module(_bn_net(), optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1,
                                           "momentum": 0.9},
                         kvstore=kv, seed=9,
                         mesh=mesh_lib.make_mesh(
                             devices=devs[wi * 4:(wi + 1) * 4]))
            mod.sync_mode = "host"
            # pre-compile grad/apply on the main thread (exact fit-batch
            # shapes/dtypes via the iterator); outputs discarded — state
            # untouched
            it = data.NDArrayIter(X, Y, batch_size=8)
            b = it.next()
            mod.init_params(b.data)
            mod._use_metric(metrics.create("acc"))  # fit's default
            mod._ensure_unravel()
            fg, fs, _, _ = mod._grad_step(
                mod.state, mod._place(b.data), mod._place(b.label),
                jax.random.PRNGKey(0))
            mod._apply_step(mod.state, fg, fs)
            mods[host] = (cli, mod)

        def worker(host):
            try:
                cli, mod = mods[host]
                mod.fit(data.NDArrayIter(X, Y, batch_size=8), num_epoch=2)
                leaves = jax.tree_util.tree_leaves(
                    (mod.state.params, mod.state.batch_stats))
                out[host] = np.concatenate(
                    [np.asarray(p).ravel() for p in leaves])
                cli.close()
            except Exception as e:  # noqa: BLE001 - surfaced by the assert
                errs[host] = e

        ts = [threading.Thread(target=worker, args=(h,))
              for h in ("w0", "w1")]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=180)
        assert not errs, errs
        assert not any(t.is_alive() for t in ts)
    finally:
        s.close()
    np.testing.assert_array_equal(out["w0"], out["w1"])
    return out["w0"]


def test_overlap_bit_exact_vs_serial_raw(monkeypatch):
    a = _run_host_pair(True, False, monkeypatch)
    b = _run_host_pair(False, False, monkeypatch)
    np.testing.assert_array_equal(a, b)


def test_overlap_bit_exact_vs_serial_compressed(monkeypatch):
    """2-bit compress_on_device rides the same pipeline: packed words
    bucket on the packing-word grid, the device residual is untouched by
    bucketing — final params+BN stats bitwise equal to serial."""
    a = _run_host_pair(True, True, monkeypatch)
    b = _run_host_pair(False, True, monkeypatch)
    np.testing.assert_array_equal(a, b)


def test_escape_hatch_really_serial(monkeypatch):
    """DT_AR_OVERLAP=0 must not touch the pipeline API at all (degrade
    cleanly to serial), and the default must use it."""
    calls = []
    orig = WorkerClient.allreduce_pipeline

    def spy(self, key, window=None):
        calls.append(key)
        return orig(self, key, window=window)

    monkeypatch.setattr(WorkerClient, "allreduce_pipeline", spy)
    _run_host_pair(False, False, monkeypatch)
    assert calls == []
    _run_host_pair(True, False, monkeypatch)
    assert calls and all(k == "grads" for k in calls)


# ---------------------------------------------------------------------------
# fault semantics: mid-bucket reset -> single re-dispatch (token replay)
# ---------------------------------------------------------------------------

def test_midbucket_reset_single_redispatch():
    """A connection reset after one bucket's round was DELIVERED retries
    only that round; the (host, seq) + idempotency-token dedup serves the
    replay the cached result, so every bucket's average stays exact (a
    double-apply would shift it)."""
    plan = faults.install(FaultPlan(
        [FaultRule("reset", op="send", cmd="allreduce", host="w0",
                   times=1)], seed=3))
    sched = Scheduler(initial_workers=["w0", "w1"])
    cs = []
    nb = 4
    try:
        cs = [WorkerClient("127.0.0.1", sched.port, host=h,
                           heartbeat_interval_s=30.0)
              for h in ("w0", "w1")]
        outs = {}

        def run(c, base):
            pipe = c.allreduce_pipeline("g")
            try:
                for k in range(nb):
                    pipe.submit(np.full(8, base + k, np.float32))
                pipe.done_submitting()
                got = {}
                while True:
                    r = pipe.next_result()
                    if r is None:
                        break
                    got[r[0]] = float(r[1][0])
                outs[c.host] = got
            finally:
                pipe.close()

        ts = [threading.Thread(target=run, args=(c, (i + 1) * 10.0))
              for i, c in enumerate(cs)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
        want = {k: 15.0 + k for k in range(nb)}  # exact per-bucket mean
        assert outs["w0"] == want and outs["w1"] == want
        assert plan.applied_summary() == [(0, "w0", 1)]  # one reset fired
    finally:
        for c in cs:
            c.close()
        sched.close()
        faults.clear()


# ---------------------------------------------------------------------------
# membership change / failure mid-pipeline: drain, no leaks
# ---------------------------------------------------------------------------

def test_membership_change_completes_parked_buckets():
    """w1 dies mid-pipeline: the auto-evictor shrinks membership and the
    survivors' parked bucket rounds complete (dataplane.complete_with),
    the pipeline drains in order, and close() joins the comm thread."""
    sched = Scheduler(initial_workers=["w0", "w1"], auto_evict_dead_s=1.0)
    c0 = c1 = None
    try:
        c0 = WorkerClient("127.0.0.1", sched.port, host="w0",
                          heartbeat_interval_s=0.2)
        c1 = WorkerClient("127.0.0.1", sched.port, host="w1",
                          heartbeat_interval_s=0.2)
        c1._stop.set()  # w1's heartbeats stop: it is now "dead"
        c1._hb_thread.join(timeout=5)

        pipe = c0.allreduce_pipeline("g")
        got = {}
        try:
            for k in range(3):
                pipe.submit(np.full(4, float(k), np.float32))
            pipe.done_submitting()
            while True:
                r = pipe.next_result(timeout=60)
                if r is None:
                    break
                got[r[0]] = float(r[1][0])
        finally:
            assert pipe.close(timeout=60), "comm thread failed to drain"
        # rounds completed with the survivor set {w0}: its own values
        assert got == {0: 0.0, 1: 1.0, 2: 2.0}
        assert "w1" not in sched._workers
    finally:
        for c in (c0, c1):
            if c is not None:
                c.close()
        sched.close()


def test_engine_error_drains_without_staging_leak(monkeypatch):
    """A bucket round failing mid-pipeline (e.g. the worker was removed)
    propagates from sync(), the comm thread exits, and every staging
    buffer is back in the pool — then the NEXT step reuses the same
    engine cleanly."""
    import jax.numpy as jnp

    monkeypatch.setenv("DT_AR_BUCKET_BYTES", "64")  # 16 f32 per bucket
    sched = Scheduler(initial_workers=["w0"])
    c = None
    try:
        c = WorkerClient("127.0.0.1", sched.port, host="w0",
                         heartbeat_interval_s=30.0)
        engine = overlap_lib.GradSyncEngine()
        flat = jnp.arange(64, dtype=jnp.float32)  # 4 buckets

        orig = WorkerClient._allreduce

        def boom(self, key, value, _route=None):
            if key.endswith("#b2"):
                raise RuntimeError("injected mid-pipeline failure")
            return orig(self, key, value, _route)

        monkeypatch.setattr(WorkerClient, "_allreduce", boom)
        with pytest.raises(RuntimeError, match="injected"):
            engine.sync(c, None, flat)
        assert engine.staging.outstanding == 0, "staging buffers leaked"

        monkeypatch.setattr(WorkerClient, "_allreduce", orig)
        avg, stats = engine.sync(c, None, flat)
        np.testing.assert_array_equal(np.asarray(avg),
                                      np.arange(64, dtype=np.float32))
        assert stats is None
        assert engine.staging.outstanding == 0
        assert engine.staging.allocated <= 8, \
            "staging buffers not reused across steps"
    finally:
        if c is not None:
            c.close()
        sched.close()


# ---------------------------------------------------------------------------
# obs: d2h/wire/h2d stage spans + bucket counters
# ---------------------------------------------------------------------------

def test_pipeline_stage_spans_and_export_split(monkeypatch):
    import jax.numpy as jnp
    from dt_tpu.obs import export as obs_export
    from dt_tpu.obs import trace as obs_trace

    monkeypatch.setenv("DT_AR_BUCKET_BYTES", "64")
    sched = Scheduler(initial_workers=["w0"])
    c = None
    obs_trace.set_enabled(True)
    try:
        c = WorkerClient("127.0.0.1", sched.port, host="w0",
                         heartbeat_interval_s=30.0)
        engine = overlap_lib.GradSyncEngine()
        engine.sync(c, None, jnp.arange(64, dtype=jnp.float32),
                    flat_s=jnp.ones(4, jnp.float32))
        tr = obs_trace.tracer()
        recs = tr.drain()
        names = [r[2] for r in recs]
        for want in ("pipeline.d2h", "pipeline.wire", "pipeline.h2d",
                     "allreduce"):
            assert want in names, (want, names)
        assert tr.get_counter("pipeline.buckets") >= 4
        assert tr.get_counter("pipeline.aux_rounds") >= 1  # the stats ride
        # export splits the stages per track and surfaces the counter
        job = {"tracks": {"w0#1": {"records": recs,
                                   "counters": tr.counters(),
                                   "dropped": 0}}}
        summary = obs_export.summarize_chrome(obs_export.chrome_trace(job))
        t = summary["tracks"]["w0#1"]
        assert set(t["pipeline_ms"]) >= {"d2h", "wire", "h2d"}
        assert t["pipeline_buckets"] >= 4
    finally:
        obs_trace.set_enabled(None)
        if c is not None:
            c.close()
        sched.close()


# ---------------------------------------------------------------------------
# Trainer rides the same engine
# ---------------------------------------------------------------------------

def test_trainer_overlap_matches_serial(monkeypatch):
    import jax.numpy as jnp
    from dt_tpu.training.trainer import Trainer
    from dt_tpu.parallel import kvstore as kvstore_lib

    monkeypatch.setenv("DT_AR_BUCKET_BYTES", "64")

    def run(overlap_on):
        monkeypatch.setenv("DT_AR_OVERLAP", "1" if overlap_on else "0")
        sched = Scheduler(initial_workers=["w0", "w1"])
        outs, errs = {}, {}

        def worker(host, scale):
            try:
                cli = WorkerClient("127.0.0.1", sched.port, host=host,
                                   heartbeat_interval_s=30.0)
                kv = kvstore_lib.create("dist_sync")
                kv.set_controller(cli)
                params = {"w": jnp.arange(40, dtype=jnp.float32),
                          "b": jnp.ones(3, jnp.float32)}
                tr = Trainer(params, "sgd",
                             {"learning_rate": 0.1}, kvstore=kv)
                grads = {"w": jnp.full(40, scale, jnp.float32),
                         "b": jnp.full(3, -scale, jnp.float32)}
                for _ in range(2):
                    tr.step(grads, batch_size=1)
                outs[host] = np.concatenate(
                    [np.asarray(tr.params["w"]),
                     np.asarray(tr.params["b"])])
                cli.close()
            except Exception as e:  # noqa: BLE001
                errs[host] = e

        try:
            ts = [threading.Thread(target=worker, args=(h, v))
                  for h, v in (("w0", 1.0), ("w1", 3.0))]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=120)
            assert not errs, errs
        finally:
            sched.close()
        np.testing.assert_array_equal(outs["w0"], outs["w1"])
        return outs["w0"]

    np.testing.assert_array_equal(run(True), run(False))
