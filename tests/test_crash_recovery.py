"""Crash recovery: a SIGKILLed worker is auto-evicted and the job finishes.

Beyond the reference (SURVEY §5.3: ps-lite heartbeats only *report* dead
nodes — ``kv.get_num_dead_node`` — and a crashed worker hangs a dist_sync
job): here the scheduler evicts silent workers, completes the pending
collectives with the survivors, rewrites host_worker, and audit-logs the
removal.
"""

import json
import os
import time

from dt_tpu.elastic import Scheduler


def _write_hosts(path, hosts):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write("\n".join(hosts) + "\n")
    os.replace(tmp, path)


def _spawn(workers, port, host, out, num_epoch, **env):
    return workers.spawn(
        "elastic_worker.py", "--scheduler-port", port, "--host", host,
        "--num-epoch", num_epoch, "--out", out, "--heartbeat", "0.2",
        ELASTIC_TRAINING_ENABLED=1, **env)


def test_sigkill_worker_is_evicted_and_job_completes(tmp_path, workers):
    hw = str(tmp_path / "host_worker")
    _write_hosts(hw, ["w0", "w1", "w2"])
    outs = {h: str(tmp_path / f"{h}.json") for h in ("w0", "w1", "w2")}
    sched = Scheduler(host_worker_file=hw, auto_evict_dead_s=6.0)
    procs = {}
    try:
        num_epoch = 40  # long enough that the kill lands mid-run
        for h in ("w0", "w1", "w2"):
            procs[h] = _spawn(workers, sched.port, h, outs[h], num_epoch)
        # wait until training is underway, then SIGKILL w2 (no cleanup,
        # no goodbye — the crash case)
        workers.until(lambda: sched._last_completed_epoch >= 2,
                      "training never started")
        procs["w2"].kill()

        for h in ("w0", "w1"):
            workers.finish(procs[h], h)

        r0 = json.load(open(outs["w0"]))
        r1 = json.load(open(outs["w1"]))
        # survivors finished every epoch, in exact sync, as a 2-worker job
        assert r0["final_step"] == r1["final_step"]
        assert r0["param_hash"] == r1["param_hash"]
        assert r0["num_workers_at_end"] == 2
        # the eviction is audit-logged and host_worker was rewritten
        log = open(hw + "_log").read()
        assert "REMOVED w2" in log
        hosts = [ln.strip() for ln in open(hw) if ln.strip()]
        assert hosts == ["w0", "w1"]
        assert not os.path.exists(outs["w2"])  # w2 died before finishing
    finally:
        sched.close()


def test_crashed_worker_reenters_under_old_identity(tmp_path, workers):
    """Identity reissue (ps-lite ``van.cc:187-218`` ``is_recovery``): a
    SIGKILLed worker is evicted, restarts under its OLD host name with
    ``DT_RECOVERY=1``, is re-admitted at the next membership barrier AS
    ITSELF (audit line RECOVERED, not ADDED), bootstraps from the
    snapshot, and the job finishes with ALL THREE workers in exact sync."""
    hw = str(tmp_path / "host_worker")
    _write_hosts(hw, ["w0", "w1", "w2"])
    outs = {h: str(tmp_path / f"{h}.json") for h in ("w0", "w1", "w2")}
    go_file = str(tmp_path / "go_recover")
    sched = Scheduler(host_worker_file=hw, auto_evict_dead_s=6.0)
    procs = {}
    try:
        num_epoch = 100  # wide re-entry window: under heavy load the
        # restarted worker needs many epoch boundaries to catch one
        for h in ("w0", "w1", "w2"):
            procs[h] = _spawn(workers, sched.port, h, outs[h], num_epoch)
        workers.until(lambda: sched._last_completed_epoch >= 2,
                      "training never started")
        procs["w2"].kill()

        # pre-warm the replacement process NOW (it parks on go_file);
        # registration must wait until the eviction landed, or it would
        # take the ordinary quick-restart path instead of recovery
        procs["w2"] = _spawn(workers, sched.port, "w2", outs["w2"],
                             num_epoch, DT_RECOVERY=1, DT_WAIT_FILE=go_file)

        workers.until(lambda: "w2" in sched._removed_hosts,
                      "eviction never happened")
        open(go_file, "w").close()  # release the recovery registration

        for h in ("w0", "w1", "w2"):
            workers.finish(procs[h], h)

        results = {h: json.load(open(outs[h])) for h in ("w0", "w1", "w2")}
        # exact sync across ALL THREE, and the job ended as a 3-worker job
        assert len({r["param_hash"] for r in results.values()}) == 1, results
        assert len({r["final_step"] for r in results.values()}) == 1
        assert all(r["num_workers_at_end"] == 3 for r in results.values())
        # audit trail: REMOVED then RECOVERED (not ADDED) for w2
        log = open(hw + "_log").read()
        assert "REMOVED w2" in log and "RECOVERED w2" in log
        assert "ADDED w2" not in log
        # host_worker repaired: w2 listed again
        hosts = [ln.strip() for ln in open(hw) if ln.strip()]
        assert sorted(hosts) == ["w0", "w1", "w2"]
    finally:
        sched.close()


def test_quick_restart_recovery_before_eviction(tmp_path):
    """A worker that crashes and restarts with DT_RECOVERY=1 BEFORE the
    eviction window expires must still take the recovery path: the dead
    incarnation is dropped from the live set immediately (survivors'
    pending collectives complete), and the restarted one re-enters at
    the next barrier as itself (r5 review finding: the quick restart
    previously re-registered via the normal path and silently trained
    fresh params from epoch 0)."""
    import threading

    import numpy as np

    from dt_tpu.elastic import WorkerClient

    hw = str(tmp_path / "host_worker")
    _write_hosts(hw, ["a", "b"])
    sched = Scheduler(host_worker_file=hw)  # NO auto-eviction
    ca = cb2 = None
    try:
        ca = WorkerClient("127.0.0.1", sched.port, host="a",
                          heartbeat_interval_s=0.2)
        cb = WorkerClient("127.0.0.1", sched.port, host="b",
                          heartbeat_interval_s=0.2)
        cb.close()  # b "crashes" (stops heartbeating; not evicted yet)

        # a parks in a round that expects b
        res = {}

        def ar():
            res["v"] = ca.allreduce("g", np.ones(4, np.float32))

        t = threading.Thread(target=ar)
        t.start()
        time.sleep(0.3)
        assert t.is_alive()  # genuinely waiting on the dead incarnation

        # quick restart under the old identity
        cb2 = WorkerClient("127.0.0.1", sched.port, host="b",
                           is_recovery=True, heartbeat_interval_s=0.2)
        assert cb2.recovery_pending and cb2.rank == -1
        # the dead incarnation was dropped: a's round completes solo
        t.join(120)
        assert not t.is_alive()
        np.testing.assert_allclose(res["v"], np.ones(4))

        # re-admission at the next barrier, in lockstep
        rejoin = {}

        def wait():
            rejoin["epoch"] = cb2.wait_rejoin()

        t2 = threading.Thread(target=wait)
        t2.start()
        # the recovering host must ARRIVE at the barrier before the
        # survivor releases it, or its re-admission defers to a next
        # barrier this test never runs (re-admission only applies to
        # pending hosts present in _barrier_arrived — by design)
        deadline = time.time() + 60
        while "b" not in sched._barrier_arrived:
            assert time.time() < deadline, "recovery barrier never arrived"
            time.sleep(0.05)
        ca.membership_change_barrier({"EPOCH_BEGIN": 0})
        t2.join(120)
        assert not t2.is_alive()
        assert rejoin["epoch"] == 0
        assert sorted(ca.workers) == ["a", "b"]
        assert cb2.rank >= 0 and not cb2.recovery_pending
        log = open(hw + "_log").read()
        assert "REMOVED b" in log and "RECOVERED b" in log
    finally:
        for c in (ca, cb2):
            if c is not None:
                c.close()
        sched.close()
