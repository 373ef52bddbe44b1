#!/usr/bin/env python
"""Serving-plane bench: sustained QPS / latency / loss under faults.

Measures the r21 serving plane (``dt_tpu/serve/``, docs/serving.md) end
to end — REAL replica subprocesses (``python -m dt_tpu.serve.replica``,
each a jax Predictor behind a Gateway) against a real Scheduler, driven by an
open-loop load generator that verifies EVERY answer against the
deterministic toy-model oracle.  Four scenarios:

- **steady** — N replicas, fixed arrival rate: sustained QPS with p99
  under the ``DT_SERVE_DEADLINE_MS`` budget, zero lost requests.
- **replica_kill** — SIGKILL one replica mid-run: clients retry with
  the SAME idempotency token onto the survivors, the scheduler prunes
  the dead replica from ``serve_endpoints``; gates zero lost requests
  (answered-or-shed accounts for every submission) and post-recovery
  p99 back under the deadline.
- **sched_kill** — the primary scheduler (a real
  ``dt_tpu.elastic.scheduler_main`` process) is SIGKILLed mid-run with
  a warm standby watching the lease (docs/ha.md): inference traffic
  never crosses the scheduler, so the gate is zero lost requests AND
  the serving view reconverging on the standby (replicas re-register
  when a heartbeat comes back ``registered: false``).
- **load_step** — ``DT_SERVE_POLICY=1``: a low->high->low arrival-rate
  step against a 1-replica fleet with ``max_replicas=2``; the bench's
  launcher spawns/reaps replica processes to match the scheduler's
  ``want``; gates the decision log reads exactly
  ``[scale_up, scale_down]`` and that its sha256 is identical across
  two runs at one seed (the r14 determinism contract, docs/policy.md).

Loss accounting is strict: every submitted request must end ``ok``
(answer verified against the oracle) or ``shed`` (the gateway's
explicit bounded-admission answer).  ``lost`` (retries exhausted) or
``bad`` (wrong bytes) fail the run.

jax-optional in THIS process (the dtop path shim): the
parent imports only the jax-free elastic + serve.client layers; jax
lives in the replica subprocesses (CPU-forced via ``DT_FORCE_CPU``).

Run: ``python tools/serve_bench.py`` (full, ~8 min) ->
``SERVE_BENCH_r21.json``; ``--smoke`` (~1 min) for the CI gate;
``--scenario steady|replica_kill|sched_kill|load_step`` to run one.
"""

import argparse
import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# import dt_tpu.elastic / dt_tpu.serve.client WITHOUT dt_tpu/__init__
# (which pulls the ops surface and therefore jax) — the dtop
# shim; dt_tpu.serve.replica is jax-free too (Predictor imports lazily)
if "dt_tpu" not in sys.modules:
    import types
    _shim = types.ModuleType("dt_tpu")
    _shim.__path__ = [os.path.join(REPO, "dt_tpu")]
    sys.modules["dt_tpu"] = _shim
    _sshim = types.ModuleType("dt_tpu.serve")
    _sshim.__path__ = [os.path.join(REPO, "dt_tpu", "serve")]
    sys.modules["dt_tpu.serve"] = _sshim

import numpy as np  # noqa: E402

from dt_tpu.elastic import protocol  # noqa: E402
from dt_tpu.serve.client import InferClient  # noqa: E402
from dt_tpu.serve.replica import params_for_step  # noqa: E402

FEATURES, CLASSES, MAX_BATCH = 8, 4, 8
DEADLINE_MS = 100.0  # the p99 budget every scenario is gated against
SENDERS = 16  # load-generator thread pool (open-loop arrivals)

OK, SHED, BAD, LOST = "ok", "shed", "bad", "lost"


def _child_env(extra=None):
    env = dict(os.environ)
    env["DT_FORCE_CPU"] = "1"
    env["DT_SERVE_DEADLINE_MS"] = str(DEADLINE_MS)
    env.setdefault("PYTHONPATH", REPO)
    env.update(extra or {})
    return env


def _wait_port_file(path, proc, timeout=120.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"process died before writing {path} "
                f"(rc {proc.returncode})")
        if os.path.exists(path):
            with open(path) as f:
                return int(f.read().strip())
        time.sleep(0.1)
    raise RuntimeError(f"timed out waiting for {path}")


class ReplicaProc:
    """One ``python -m dt_tpu.serve.replica`` subprocess."""

    def __init__(self, host, sched_spec, tmpdir, env=None,
                 weights_step=0):
        self.host = host
        pf = os.path.join(tmpdir, f"{host}.port")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "dt_tpu.serve.replica",
             "--scheduler", sched_spec, "--host", host,
             "--max-batch", str(MAX_BATCH),
             "--features", str(FEATURES), "--classes", str(CLASSES),
             "--weights-step", str(weights_step),
             "--port-file", pf],
            cwd=REPO, env=_child_env(env),
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        self.port = _wait_port_file(pf, self.proc)
        self.addr = ("127.0.0.1", self.port)

    def kill(self):
        self.proc.kill()
        self.proc.wait(timeout=30)

    def shutdown(self):
        if self.proc.poll() is None:
            try:
                protocol.request(self.addr[0], self.addr[1],
                                 {"cmd": "shutdown"}, timeout=5.0)
            except (ConnectionError, OSError):
                pass
            try:
                self.proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=15)


def _wait_discovery(client, n, timeout=180.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            if len(client.refresh_endpoints()) >= n:
                return
        except (ConnectionError, OSError):
            pass
        time.sleep(0.2)
    raise RuntimeError(f"discovery never reached {n} replicas")


# ---------------------------------------------------------------------------
# open-loop load generator
# ---------------------------------------------------------------------------


class LoadGen:
    """Open-loop arrivals on a fixed schedule; every answer verified
    against the toy oracle for the ``weights_step`` it claims."""

    def __init__(self, client, seed):
        self.client = client
        self.seed = seed
        self.records = []  # (t_done_rel, status, lat_ms)
        self._lock = threading.Lock()
        self._oracle = {}  # step -> w

    def _w(self, step):
        if step not in self._oracle:
            self._oracle[step] = params_for_step(FEATURES, CLASSES,
                                                 step)["w"]
        return self._oracle[step]

    def _one(self, idx, t0):
        rng = np.random.RandomState((self.seed * 1_000_003 + idx)
                                    & 0x7fffffff)
        n = int(rng.randint(1, 4))
        x = rng.randn(n, FEATURES).astype(np.float32)
        t_sub = time.monotonic()
        try:
            resp = self.client.infer(x)
        except (ConnectionError, OSError, RuntimeError):
            status, lat = LOST, 0.0
        else:
            lat = (time.monotonic() - t_sub) * 1000.0
            if resp.get("shed"):
                status = SHED
            elif np.allclose(resp["y"],
                             x @ self._w(int(resp["weights_step"])),
                             rtol=1e-5, atol=1e-5):
                status = OK
            else:
                status = BAD
        with self._lock:
            self.records.append((time.monotonic() - t0, status, lat))

    def run(self, phases):
        """``phases`` = [(rate_per_s, duration_s), ...] back to back.
        Returns the wall duration.  Arrivals are open-loop: each request
        fires at its scheduled offset regardless of earlier completions
        (a pool of SENDERS threads; if all are busy the schedule slips,
        which only ever under-reports pressure)."""
        sched = []
        t = 0.0
        for rate, dur in phases:
            end = t + dur
            while t < end:
                sched.append(t)
                t += 1.0 / rate
        t0 = time.monotonic()
        next_i = [0]
        ilock = threading.Lock()

        def sender():
            while True:
                with ilock:
                    i = next_i[0]
                    if i >= len(sched):
                        return
                    next_i[0] += 1
                delay = t0 + sched[i] - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                self._one(i, t0)

        threads = [threading.Thread(target=sender)
                   for _ in range(SENDERS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return time.monotonic() - t0

    def summary(self, wall_s, post_window=None):
        """Counts + latency percentiles; ``post_window=(a_rel, b_rel)``
        adds a windowed p99 (the post-recovery gate)."""
        counts = {s: 0 for s in (OK, SHED, BAD, LOST)}
        for _, status, _ in self.records:
            counts[status] += 1
        lats = sorted(l for _, s, l in self.records if s == OK)

        def pct(v, q):
            return round(v[min(len(v) - 1, int(len(v) * q))], 1) \
                if v else 0.0

        out = {"submitted": len(self.records), **counts,
               "qps_sustained": round(counts[OK] / max(wall_s, 1e-9),
                                      1),
               "p50_ms": pct(lats, 0.50), "p99_ms": pct(lats, 0.99)}
        if post_window is not None:
            a, b = post_window
            post = sorted(l for t, s, l in self.records
                          if s == OK and a <= t <= b)
            out["p99_post_ms"] = pct(post, 0.99)
        return out


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------


def _gate(row, name, ok):
    row.setdefault("gates", {})[name] = bool(ok)
    return ok


def _finish(row, summary):
    row.update(summary)
    no_loss = summary[LOST] == 0 and summary[BAD] == 0
    _gate(row, "zero_lost", no_loss)
    row["pass"] = all(row["gates"].values())
    return row


def run_steady(seed, replicas, rate, duration, tmpdir):
    from dt_tpu.elastic.scheduler import Scheduler
    sched = Scheduler(initial_workers=[])
    spec = f"127.0.0.1:{sched.port}"
    procs = []
    try:
        procs = [ReplicaProc(f"s{i}", spec, tmpdir)
                 for i in range(replicas)]
        client = InferClient(scheduler=spec)
        _wait_discovery(client, replicas)
        gen = LoadGen(client, seed)
        wall = gen.run([(rate, duration)])
        row = {"scenario": "steady", "replicas": replicas,
               "rate": rate, "duration_s": duration}
        summary = gen.summary(wall)
        _gate(row, "p99_under_deadline",
              0 < summary["p99_ms"] <= DEADLINE_MS)
        return _finish(row, summary)
    finally:
        for p in procs:
            p.shutdown()
        sched.close()


def run_replica_kill(seed, rate, duration, tmpdir):
    from dt_tpu.elastic.scheduler import Scheduler
    sched = Scheduler(initial_workers=[])
    spec = f"127.0.0.1:{sched.port}"
    procs = []
    try:
        procs = [ReplicaProc(f"s{i}", spec, tmpdir) for i in range(2)]
        client = InferClient(scheduler=spec)
        _wait_discovery(client, 2)
        gen = LoadGen(client, seed)
        killer = threading.Timer(duration * 0.5, procs[1].kill)
        killer.start()
        wall = gen.run([(rate, duration)])
        killer.join()
        row = {"scenario": "replica_kill", "replicas": 2,
               "rate": rate, "duration_s": duration,
               "kill_at_s": round(duration * 0.5, 1)}
        # post-recovery window: the last 30% of the run, well past the
        # kill + the scheduler's serve-TTL prune
        summary = gen.summary(wall, post_window=(duration * 0.7, wall))
        _gate(row, "p99_post_under_deadline",
              0 < summary["p99_post_ms"] <= DEADLINE_MS)
        # the dead replica left the serving view (TTL prune)
        view = protocol.request("127.0.0.1", sched.port,
                                {"cmd": "serve_endpoints"})
        _gate(row, "dead_replica_pruned",
              "s1" not in (view.get("replicas") or {}))
        return _finish(row, summary)
    finally:
        for p in procs:
            p.shutdown()
        sched.close()


def run_sched_kill(seed, rate, duration, tmpdir):
    from dt_tpu.elastic.scheduler import Scheduler
    jp = os.path.join(tmpdir, "ctrl.journal")
    lp = os.path.join(tmpdir, "ctrl.lease")
    standby = Scheduler(standby=True, journal_path=jp, lease_path=lp,
                        lease_s=2.0)
    pf = os.path.join(tmpdir, "sched.port")
    primary = subprocess.Popen(
        [sys.executable, "-m", "dt_tpu.elastic.scheduler_main",
         "--journal", jp, "--lease", lp, "--lease-s", "2.0",
         "--port-file", pf],
        cwd=REPO, env=_child_env(),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    procs = []
    try:
        pport = _wait_port_file(pf, primary)
        spec = f"127.0.0.1:{pport},127.0.0.1:{standby.port}"
        procs = [ReplicaProc(f"s{i}", spec, tmpdir) for i in range(2)]
        client = InferClient(scheduler=spec)
        _wait_discovery(client, 2)
        gen = LoadGen(client, seed)

        def kill_primary():
            primary.send_signal(signal.SIGKILL)

        killer = threading.Timer(duration * 0.5, kill_primary)
        killer.start()
        wall = gen.run([(rate, duration)])
        killer.join()
        primary.wait(timeout=30)
        row = {"scenario": "sched_kill", "replicas": 2, "rate": rate,
               "duration_s": duration,
               "kill_at_s": round(duration * 0.5, 1)}
        summary = gen.summary(wall, post_window=(duration * 0.7, wall))
        _gate(row, "p99_post_under_deadline",
              0 < summary["p99_post_ms"] <= DEADLINE_MS)
        # the serving view reconverged on the standby: both replicas
        # re-registered after their heartbeats came back unregistered
        deadline = time.monotonic() + 30.0
        reconverged = False
        while time.monotonic() < deadline and not reconverged:
            try:
                v = protocol.request("127.0.0.1", standby.port,
                                     {"cmd": "serve_endpoints"})
                reps = v.get("replicas") or {}
                reconverged = "error" not in v and len(reps) == 2
            except (ConnectionError, OSError):
                pass
            if not reconverged:
                time.sleep(0.25)
        _gate(row, "standby_serving_view", reconverged)
        _gate(row, "standby_is_leader", standby.is_leader())
        return _finish(row, summary)
    finally:
        for p in procs:
            p.shutdown()
        if primary.poll() is None:
            primary.kill()
            primary.wait(timeout=30)
        standby.close()


# scale-threshold knobs for the load-step drill: QHI low enough that
# the high phase's sampled queue depth breaches it reliably, DOWN_AFTER
# long enough that only SUSTAINED idleness drains the spare replica
LOAD_STEP_ENV = {
    "DT_SERVE_POLICY": "1", "DT_SERVE_QHI": "2.0",
    "DT_SERVE_QLO": "0.5", "DT_SERVE_UP_AFTER": "3",
    "DT_SERVE_DOWN_AFTER": "8", "DT_SERVE_MIN_REPLICAS": "1",
    "DT_SERVE_MAX_REPLICAS": "2",
}


def run_load_step(seed, tmpdir, low_rate=5.0, high_rate=250.0,
                  low_s=5.0, high_s=15.0, cool_s=14.0):
    from dt_tpu.elastic.scheduler import Scheduler
    os.environ.update(LOAD_STEP_ENV)  # read at Scheduler construction
    sched = Scheduler(initial_workers=[])
    spec = f"127.0.0.1:{sched.port}"
    procs = {"s0": ReplicaProc("s0", spec, tmpdir)}
    stop = threading.Event()

    def launcher():
        """Match the fleet to the scheduler's ``want``: spawn when it
        grows, drain-then-shutdown the victims it marks."""
        k = [1]
        while not stop.is_set():
            try:
                v = protocol.request("127.0.0.1", sched.port,
                                     {"cmd": "serve_endpoints"},
                                     timeout=5.0)
            except (ConnectionError, OSError):
                time.sleep(0.3)
                continue
            reps = v.get("replicas") or {}
            live = [h for h, e in reps.items() if not e.get("draining")]
            # count our own live processes, not just the registered
            # view: a replica mid-warmup (or transiently stale-pruned
            # under CPU contention) must not trigger a double spawn
            running = [h for h, p in procs.items()
                       if p.proc.poll() is None
                       and not reps.get(h, {}).get("draining")]
            if (v.get("want") or 0) > max(len(live), len(running)):
                host = f"s{k[0]}"
                k[0] += 1
                procs[host] = ReplicaProc(host, spec, tmpdir)
            for host, e in reps.items():
                if e.get("draining") and host in procs:
                    addr = tuple(e["addr"])
                    try:
                        st = protocol.request(addr[0], addr[1],
                                              {"cmd": "serve_stats"},
                                              timeout=5.0)
                    except (ConnectionError, OSError):
                        continue
                    if st.get("queue_depth", 1) == 0:
                        procs.pop(host).shutdown()
            stop.wait(0.3)

    lt = threading.Thread(target=launcher, daemon=True)
    lt.start()
    try:
        client = InferClient(scheduler=spec)
        _wait_discovery(client, 1)
        # periodic rediscovery so the round-robin picks up the spawned
        # replica mid-phase (errors already trigger it; this is faster)
        rstop = threading.Event()

        def rediscover():
            while not rstop.wait(1.0):
                try:
                    client.refresh_endpoints()
                except (ConnectionError, OSError):
                    pass

        rt = threading.Thread(target=rediscover, daemon=True)
        rt.start()
        gen = LoadGen(client, seed)
        wall = gen.run([(low_rate, low_s), (high_rate, high_s),
                        (low_rate, cool_s)])
        rstop.set()
        # the scale-down fires on sustained idle; give the cool phase's
        # tail a bounded grace to finish draining
        deadline = time.monotonic() + 20.0
        v = {}
        while time.monotonic() < deadline:
            v = protocol.request("127.0.0.1", sched.port,
                                 {"cmd": "serve_endpoints"})
            kinds = [d["kind"] for d in v.get("decisions") or []]
            if kinds == ["scale_up", "scale_down"] and \
                    v.get("want") == 1:
                break
            time.sleep(0.5)
        decisions = v.get("decisions") or []
        row = {"scenario": "load_step",
               "rates": [low_rate, high_rate, low_rate],
               "duration_s": round(wall, 1),
               "decisions": decisions,
               "decision_log_sha256": hashlib.sha256(
                   json.dumps(decisions, sort_keys=True)
                   .encode()).hexdigest()}
        _gate(row, "scaled_up_then_down",
              [d["kind"] for d in decisions] ==
              ["scale_up", "scale_down"])
        _gate(row, "want_back_to_min", v.get("want") == 1)
        return _finish(row, gen.summary(wall))
    finally:
        stop.set()
        lt.join(timeout=10)
        for p in list(procs.values()):
            p.shutdown()
        sched.close()
        for key in LOAD_STEP_ENV:
            os.environ.pop(key, None)


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def run_scenarios(names, seed, smoke):
    rows = []
    for name in names:
        tmpdir = tempfile.mkdtemp(prefix=f"serve_bench_{name}_")
        try:
            if name == "steady":
                row = run_steady(seed, replicas=2,
                                 rate=60.0 if smoke else 120.0,
                                 duration=8.0 if smoke else 20.0,
                                 tmpdir=tmpdir)
            elif name == "replica_kill":
                row = run_replica_kill(seed, rate=120.0,
                                       duration=24.0, tmpdir=tmpdir)
            elif name == "sched_kill":
                row = run_sched_kill(seed, rate=120.0, duration=24.0,
                                     tmpdir=tmpdir)
            elif name == "load_step":
                # run TWICE at one seed: the decision log must be
                # byte-identical (docs/policy.md determinism contract)
                a = run_load_step(seed, tmpdir)
                b = run_load_step(seed, tmpdir)
                same = a["decision_log_sha256"] == \
                    b["decision_log_sha256"]
                _gate(a, "decision_log_deterministic", same)
                a["pass"] = a["pass"] and b["pass"] and same
                a["second_run"] = {k: b[k] for k in
                                   ("decision_log_sha256", "pass",
                                    "submitted", OK, SHED)}
                row = a
            else:
                raise ValueError(f"unknown scenario {name!r}")
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scenario", default="",
                    help="run one of steady|replica_kill|sched_kill|"
                         "load_step (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: the steady scenario only, short "
                         "(~1 min); does not write the repo JSON")
    ap.add_argument("--out", default="",
                    help="output JSON path (default "
                         "SERVE_BENCH_r21.json; /tmp for --smoke)")
    args = ap.parse_args()

    if args.scenario:
        names = [args.scenario]
    elif args.smoke:
        names = ["steady"]
    else:
        names = ["steady", "replica_kill", "sched_kill", "load_step"]

    rows = run_scenarios(names, args.seed, args.smoke)
    ok = all(r["pass"] for r in rows)
    summary = {
        "what": "dt_tpu serving plane under load + seeded faults: real "
                "replica subprocesses (jax Predictor + Gateway dynamic "
                "batcher) against a real Scheduler, open-loop load "
                "generator verifying every answer against the toy "
                "oracle; loss gate = every submission answered or "
                "explicitly shed",
        "host_cores": os.cpu_count(),
        "seed": args.seed,
        "deadline_ms": DEADLINE_MS,
        "max_batch": MAX_BATCH,
        "rows": rows,
        "acceptance": {"pass": ok,
                       "gates": {r["scenario"]: r["gates"]
                                 for r in rows}},
    }
    out = args.out or (os.path.join(tempfile.gettempdir(),
                                    "serve_bench_smoke.json")
                       if args.smoke
                       else os.path.join(REPO, "SERVE_BENCH_r21.json"))
    with open(out, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"out": out, "rows": len(rows), "pass": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
