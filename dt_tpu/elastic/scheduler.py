"""The elastic scheduler service.

Replaces the ps-lite scheduler role + the fork's ``ETDefaultNodeManager``
(``ps-lite/src/elastic_training.cc``, ``van.cc:256-315``).  One instance per
job (the launcher runs it on the root host).  Thread-per-connection TCP
serving many requests per persistent connection (the pooled transport,
``protocol.serve_connection``); all state under one lock — control traffic
is a handful of messages per epoch.

Responsibilities (SURVEY.md §3.3):

- worker registry: ordered live set; rank = position (``van.cc:519-539``)
- heartbeats + dead-node count (``van.cc:686-698``,
  ``postoffice.cc:410-429``)
- the epoch-boundary MEMBERSHIP_CHANGE_BARRIER: release only when every live
  worker arrived; first diff ``host_worker`` against the live set and apply
  ONE change (removals win over adds, ``elastic_training.cc:91-126``)
- ``host_worker_log`` audit lines ``SEQ ADDED|REMOVED IP TIME``
  (``elastic_training.cc:108-126``)
- new-worker launch via callback (``launchCommandOnNewWorker``,
  ``elastic_training.cc:26-62``)
- the parameter snapshot joiners bootstrap from (the "server copy",
  ``module.py:552-571``)
- exact-average ``allreduce``/``broadcast`` for CPU-process clusters — the
  data plane the reference's servers provided (``kvstore_dist_server.h:
  710-739``); on a real pod this path is idle (gradients ride ICI inside the
  jit step) but it gives multi-process tests the reference's exact-value
  dist-sync semantics (``tests/nightly/dist_sync_kvstore.py``).

High availability (r11): the reference's scheduler was a single point of
failure — one process held membership, barrier, recovery-queue, and
snapshot state unreplicated (``elastic_training.cc:1-158``) and its death
killed the job.  Here every control-state transition is a named op on a
:class:`~dt_tpu.elastic.journal.ControlState` behind a fsync'd
write-ahead journal (``journal_path``), leadership is a lease file with a
monotonic fencing **incarnation** (``lease_path``/``DT_CTRL_LEASE_S``),
and a warm standby (``standby=True``, same journal) tails the journal and
takes over when the lease expires — replaying to the exact pre-crash
state, seeding heartbeat grace, and serving under ``incarnation + 1``
while the journal refuses any write from the deposed leader
(:class:`~dt_tpu.elastic.journal.Fenced`).  Data-plane allreduce rounds
are the one thing the journal does not carry (gradient-sized, per-step):
a primary given ``peer=`` replicates each COMPLETED round's served
results to the standby over the pooled wire path before answering, so an
at-least-once retry that lands on the new leader after the switch is
served the very same averaged result — rounds complete exactly once
across a failover.  ``docs/ha.md`` has the full protocol.
"""

from __future__ import annotations

import logging
import os
import random
import socket
import threading
import time
from typing import Callable, Dict, List, Optional, Set

import numpy as np

from dt_tpu import config
from dt_tpu import policy as policy_lib
from dt_tpu.elastic import commands, faults, journal, protocol
from dt_tpu.elastic.dataplane import DataPlane
from dt_tpu.obs import blackbox as obs_blackbox
from dt_tpu.obs import metrics as obs_metrics
from dt_tpu.obs import trace as obs_trace

logger = logging.getLogger("dt_tpu.elastic")
_drop_rng = random.Random(0xD207)  # deterministic fault injection

#: commands whose responses are NOT token-cached: read-only, or already
#: dedup'd by their own (host, seq) machinery — fetch_snapshot blobs would
#: dominate the cache's memory, and high-rate heartbeats would churn the
#: bounded cache out of the very tokens the dedup exists to protect.
#: Derived view over the r17 PROTOCOL_REGISTRY (elastic/commands.py):
#: the idempotency class declared per command IS the exemption decision,
#: and dtlint DT013 cross-checks both against the handler's actual
#: behavior — a mutating no-dedup command can no longer slip in here
_TOKEN_EXEMPT = commands.token_exempt("scheduler")

#: commands a PASSIVE instance (warm standby / fenced ex-leader) still
#: serves: round replication from the live primary, obs ingest/export,
#: health introspection, and shutdown — everything else is refused with
#: ``not_leader`` so clients rotate to the real leader.  Derived view
#: over the PROTOCOL_REGISTRY ``passive`` flag (elastic/commands.py)
_PASSIVE_CMDS = commands.passive_cmds()

#: bound on retained (host, incarnation) obs tracks — LRU-evicted so a
#: job with heavy restart churn can't grow scheduler memory unboundedly
_OBS_MAX_TRACKS = 64


class Scheduler:
    def __init__(self, host_worker_file: Optional[str] = None,
                 initial_workers: Optional[List[str]] = None,
                 port: int = 0,
                 launch_callback: Optional[Callable[[str, int], None]] = None,
                 host_worker_log: Optional[str] = None,
                 expected_workers: Optional[int] = None,
                 pre_change_hook: Optional[Callable[[int], None]] = None,
                 auto_evict_dead_s: Optional[float] = None,
                 startup_grace_s: float = 120.0,
                 journal_path: Optional[str] = None,
                 lease_path: Optional[str] = None,
                 lease_s: Optional[float] = None,
                 standby: bool = False,
                 peer: Optional[tuple] = None,
                 resume: bool = False):
        """``initial_workers`` seeds the base set; else the first line-set of
        ``host_worker_file`` does (``postoffice.cc:247-259`` baseline read).
        ``launch_callback(host, epoch_begin)`` starts a worker process on
        ``host`` (the reference shells out to ``launch.py --launch-worker``).
        ``expected_workers``: registrations to wait for before barriers make
        sense (DMLC_NUM_WORKER analog).

        HA: ``journal_path`` enables the control-state WAL (a restart of
        THIS role replays it; default ``DT_CTRL_JOURNAL``).
        ``standby=True`` builds a warm standby: state comes from the
        journal only, the instance binds its port but answers
        ``not_leader`` until the lease (``lease_path``, default
        ``<journal>.lease``) goes stale for ``lease_s``
        (``DT_CTRL_LEASE_S``) and it takes over under the next fencing
        incarnation.  ``peer=(host, port)`` on the PRIMARY replicates
        completed allreduce rounds to the standby before responses are
        released (exactly-once rounds across a failover)."""
        self.host_worker_file = host_worker_file
        if initial_workers is None and host_worker_file and \
                not standby and os.path.exists(host_worker_file):
            initial_workers = _read_hosts(host_worker_file)

        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        # ALL membership / barrier / recovery / snapshot state lives in
        # the journaled ControlState (mutated only via _apply, under the
        # lock); the bare attributes of rounds 3-10 are now read-only
        # properties over it (tests/tools introspect them)
        self._state = journal.ControlState()  # guarded-by: _lock

        # -- HA plumbing (journal / lease / fencing) -----------------------
        self.journal_path = journal_path or \
            (config.env("DT_CTRL_JOURNAL") or None)
        # snapshot sidecar resolution (blobs live NEXT TO the journal,
        # the WAL carries only markers) — set before any replay applies
        # a snapshot op
        self._state.sidecar_base = self.journal_path
        self.lease_s = float(lease_s if lease_s is not None
                             else config.env("DT_CTRL_LEASE_S"))
        lp = lease_path or config.env("DT_CTRL_LEASE") or \
            (self.journal_path + ".lease" if self.journal_path else None)
        self._lease = journal.Lease(lp) \
            if (lp and self.journal_path) else None
        self._journal: Optional[journal.JournalWriter] = None
        self._journal_reader = journal.JournalReader(self.journal_path) \
            if self.journal_path else None
        self._incarnation = 0  # fencing epoch; bumped only in _takeover
        self.standby = bool(standby)
        self.peer = tuple(peer) if peer else None
        self._active = threading.Event()
        self._takeover_lock = threading.Lock()

        if standby:
            if not self.journal_path:
                raise ValueError("standby scheduler needs a journal_path")
            with self._cv:
                self._refresh_from_journal_locked()
            self._incarnation = self._lease.incarnation() \
                if self._lease else 0
        else:
            if self.journal_path:
                # cold restart of the primary role: replay our own journal
                with self._cv:
                    self._refresh_from_journal_locked()
            if self._lease is not None:
                self._incarnation = self._lease.acquire(
                    owner=f"sched:{os.getpid()}")
            if self.journal_path:
                self._journal = journal.JournalWriter(
                    self.journal_path, fence=self._incarnation,
                    lease=self._lease)
            if resume and self.journal_path:
                # r19 cold-restart resume (docs/checkpoint.md): the replayed
                # journal holds the dead incarnation's fleet; the journaled
                # resume op clears it (so `init` below re-seeds from the
                # host file, possibly at a different size) while keeping the
                # committed fleet-checkpoint manifest workers restore from.
                with self._cv:
                    self._apply("resume", seq=self._state.resume_seq + 1)
            if not self._state.workers and initial_workers:
                with self._cv:
                    self._apply("init", workers=list(initial_workers),
                                expected=(expected_workers
                                          or len(initial_workers)))

        # r19: while a DT_RESUME boot is still rolling the fleet forward to
        # its checkpointed epoch, _register serves the committed manifest so
        # workers restore params + data cursors before their first barrier.
        self._resume_boot = bool(resume)  # guarded-by: _lock

        self.expected_workers = (expected_workers
                                 or self._state.expected_workers
                                 or len(self._state.workers))
        # Seed heartbeats at startup so a worker that never comes up ages
        # out and is counted dead, instead of defaulting to "alive forever".
        now = time.time()
        self._heartbeats = {h: now for h in self._state.workers}  # guarded-by: _lock
        self._log_path = host_worker_log or (
            host_worker_file + "_log" if host_worker_file else None)
        self._launch_callback = launch_callback
        # Called with the epoch right before the host_worker diff — the
        # in-process analog of the EC2 manager thread that rewrites the file
        # (launch.py:88-235); used by operator automation and tests.
        self._pre_change_hook = pre_change_hook
        # r14 policy engine (dt_tpu/policy, ISSUE 11): straggler EWMAs →
        # journaled batch-share rebalances, chronic-straggler evictions
        # (via the host_worker diff, like the EC2 lifecycle daemon), and
        # scale proposals.  DT_POLICY=1 arms it; immutable after init.
        self._policy = policy_lib.PolicyEngine.from_env() \
            if policy_lib.enabled() else None

        # snapshot publish/fetch keep their own lock so a multi-MB blob
        # copy never blocks membership traffic (the blob itself lives in
        # the ControlState and is journaled like every transition)
        self._snapshot_lock = threading.Lock()
        # observability (dt_tpu/obs): this instance's control-plane tracer
        # holds the scheduler's own spans/events AND the always-on
        # transport counters the old ad-hoc _tstats ints became
        # (transport_stats() is now a thin view over these); workers'
        # span rings arrive on the heartbeat channel and accumulate in
        # _obs_tracks, one track per (host, incarnation) — obs_dump()
        # merges everything into one job timeline
        self._obs = obs_trace.Tracer(name="control-plane")
        self._obs_lock = threading.Lock()
        self._obs_tracks: Dict[str, dict] = {}  # guarded-by: _obs_lock
        self._obs_cap = self._obs._cap
        self._barrier_t0 = None  # mc_barrier window span start; guarded-by: _lock
        # r19 fleet-checkpoint timing (obs-only; the journaled truth lives
        # in ControlState.ckpt_pending/_committed): intent/ack monotonic
        # times feed the ckpt.commit dur_ms/spread_ms event attributes.
        self._ckpt_times: Dict[int, dict] = {}  # guarded-by: _lock
        # r19 scheduler drain: once set, heartbeat responses carry
        # ckpt_epoch_end so the fleet checkpoints at the next boundary.
        # Monotonic write-once bool: benign unlocked.
        self._ckpt_epoch_end = False
        if self._resume_boot and self._state.ckpt_committed is not None:
            m = self._state.ckpt_committed
            self._obs.event("ckpt.resume",
                            attrs={"step": int(m["step"]),
                                   "epoch": int(m["epoch"]),
                                   "workers": list(m["workers"])})
        # the single-funnel data plane (allreduce rounds + dist_async
        # store), shared machinery with RangeServer (dataplane.py).  When
        # range servers register, workers route bulk data to THEM and this
        # embedded plane goes idle (kvstore_dist.h:547-589 key sharding).
        self._dp = DataPlane(
            expected_fn=lambda: list(self._state.workers),
            tracer=self._obs,
            replicate_fn=self._make_replicator() if self.peer else None,
            track_lag=self._policy is not None)
        # range-server registry: index -> (host, port); fixed after launch
        # (the reference's server count is DMLC_NUM_SERVER, not elastic).
        # Own lock: _server_list() is called from inside _register, which
        # already holds the (non-reentrant) scheduler lock.
        self._servers: Dict[int, tuple] = {}  # guarded-by: _servers_lock
        self._servers_lock = threading.Lock()
        # remote profiler control (rank 0 drives all workers)
        self._profile_cmds: List[dict] = []  # guarded-by: _lock
        self._profile_seq = 0  # guarded-by: _lock
        self._profile_posted: Dict[tuple, int] = {}  # retry dedup; guarded-by: _lock
        # r18 device plane (dt_tpu/obs/device.py): the latest per-host
        # heartbeat `dev` view (compile totals, compiling-now flag,
        # memory snapshot) — obs_dump/health carry it, the fleet-hang
        # detector demotes a compiling worker's blame — plus the
        # targeted profile_capture command queue (delivered on the
        # target's next heartbeat, (host, post_seq) retry dedup exactly
        # like the broadcast profiler commands above)
        self._dev_lock = threading.Lock()
        self._dev_tracks: Dict[str, dict] = {}  # guarded-by: _dev_lock
        self._capture_cmds: List[dict] = []  # guarded-by: _lock
        self._capture_seq = 0  # guarded-by: _lock
        self._capture_posted: Dict[tuple, int] = {}  # guarded-by: _lock
        # r21 serving plane (dt_tpu/serve): the live replica table —
        # host -> {addr, ts, gauges, weights_step, refreshes, draining}.
        # EPHEMERAL like _dev_tracks, deliberately NOT ControlState:
        # replicas re-register within one heartbeat interval after a
        # failover (serve_heartbeat answers registered=false), so
        # journaling the table would only add replay surface.  The
        # ServePolicy autoscaler evaluates on each heartbeat; only
        # non-hold decisions enter _serve_decisions (log determinism:
        # a function of the load pattern, not of heartbeat timing).
        self._serve_lock = threading.Lock()
        self._serve_replicas: Dict[str, dict] = {}  # guarded-by: _serve_lock
        self._serve_order: List[str] = []  # guarded-by: _serve_lock
        self._serve_policy = policy_lib.ServePolicy.from_env() \
            if policy_lib.serving_enabled() else None
        self._serve_hi = 0  # guarded-by: _serve_lock
        self._serve_lo = 0  # guarded-by: _serve_lock
        self._serve_want: Optional[int] = None  # guarded-by: _serve_lock
        self._serve_decisions: List[dict] = []  # guarded-by: _serve_lock
        self._serve_ttl = 3.0  # stale-heartbeat prune horizon (s)
        self._serve_last_eval = 0.0  # guarded-by: _serve_lock
        # idempotency-token response cache (protocol.request reliable
        # mode); TTL + LRU bound its memory on a long-running scheduler
        self._tokens = protocol.TokenCache(
            ttl_s=float(config.env("DT_CTRL_TOKEN_TTL_S")))

        # r15 metrics/health plane (dt_tpu/obs/metrics.py): the process
        # registry carries the scheduler-derived gauges (heartbeat
        # staleness, worker step rate, ring drops) and the histograms
        # the data plane / journal observe into; worker time-series
        # batches arrive on the heartbeat (msg["hm"]) and accumulate in
        # _hm_tracks with sample-seq dedup — the metrics twin of the
        # span-ring ingest above.  The declarative SLO engine runs on
        # every background sample / health read and fires edge-triggered
        # health.breach/clear events on the control-plane track.
        self._metrics = obs_metrics.registry() \
            if obs_metrics.enabled() else None
        self._slo = obs_metrics.SLOEngine.from_env() \
            if self._metrics is not None else None
        self._hm_lock = threading.Lock()
        self._hm_tracks: Dict[str, dict] = {}  # guarded-by: _hm_lock
        self._hm_sampler: Optional[obs_metrics.Sampler] = None
        self._http: Optional[obs_metrics.HealthServer] = None
        self.metrics_port: Optional[int] = None
        if self._metrics is not None:
            self._hm_sampler = obs_metrics.Sampler(
                self._metrics, hook=self._health_refresh,
                tracer=self._obs)
            port_spec = config.env("DT_METRICS_PORT")
            if port_spec != "":
                try:
                    self._http = obs_metrics.HealthServer(
                        int(port_spec), self.metrics_text,
                        self.health_view)
                    self.metrics_port = self._http.port
                    logger.info("metrics/health endpoint on :%d",
                                self.metrics_port)
                except (OSError, ValueError) as e:
                    # never fatal (every other path in this plane is
                    # best-effort): a same-host HA pair reads the same
                    # DT_METRICS_PORT, so the standby's bind loses to
                    # the primary's — it must still come up and protect
                    # failover, just without its own endpoint; a
                    # non-numeric port (ValueError) degrades the same
                    logger.warning("metrics/health endpoint on :%s "
                                   "unavailable (%s); continuing "
                                   "without it", port_spec, e)

        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((protocol.bind_interface(), port))
        self._sock.listen(128)
        self.port = self._sock.getsockname()[1]
        self._stop = threading.Event()
        # close() runs on the caller AND on handler threads (the
        # "shutdown" command): the idempotence check is a test-and-set
        # under its own lock, not a bare flag (dtflow DT008 r12)
        self._close_lock = threading.Lock()
        self._closed = False  # guarded-by: _close_lock
        # accepted connections, severed on close() so clients parked on
        # a dying scheduler see a reset (and fail over) instead of
        # hanging until their own timeout — an in-process close behaves
        # like the process death it stands in for
        self._conns: Set[socket.socket] = set()  # guarded-by: _conns_lock
        self._conns_lock = threading.Lock()
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()
        # Crash recovery beyond the reference: auto-evict workers whose
        # heartbeats go silent for auto_evict_dead_s (the reference's
        # GetDeadNodes only *reports*; a crashed worker would hang the
        # synchronous job until an operator intervened).  Evicted hosts are
        # removed from membership AND the host_worker file, pending
        # collectives complete with the survivors, and the audit log gets a
        # REMOVED line.  Base workers are evictable here — a crashed base
        # worker would otherwise hang the job forever (the base-worker
        # protection applies to operator-driven removals, not deaths).
        self.auto_evict_dead_s = auto_evict_dead_s
        # workers that never registered get a longer leash: process startup
        # (python + jax import) takes seconds-to-minutes
        self.startup_grace_s = max(startup_grace_s, auto_evict_dead_s or 0)
        self._evict_thread: Optional[threading.Thread] = None
        self._lease_thread: Optional[threading.Thread] = None
        self._monitor_thread: Optional[threading.Thread] = None
        # r16 flight recorder (dt_tpu/obs/blackbox.py): the fleet-hang
        # detector ages pending allreduce rounds and cross-blames the
        # worker everyone is waiting on; blackbox_index serves the
        # bundle manifest.  The state provider stamps every bundle this
        # process writes with the live control state.
        self._bb_lock = threading.Lock()
        self._bb_suspect: Optional[dict] = None  # guarded-by: _bb_lock
        self._bb_thread: Optional[threading.Thread] = None
        # the ACTIVE instance owns the "scheduler" provider slot — a
        # same-process warm standby must not clobber the live primary's
        # state in its bundles; a standby registers at takeover
        if obs_blackbox.enabled() and not standby:
            obs_blackbox.register_state("scheduler", self._bb_state)
        if standby:
            self._monitor_thread = threading.Thread(
                target=self._monitor_loop, daemon=True)
            self._monitor_thread.start()
            logger.info("standby scheduler listening on :%d (journal %s)",
                        self.port, self.journal_path)
        else:
            self._active.set()
            if self._lease is not None:
                self._obs.event("leader.elected",
                                {"incarnation": self._incarnation,
                                 "reason": "primary start"})
                self._start_lease_thread()
            if auto_evict_dead_s:
                self._start_evict_thread()
            self._start_hang_thread()
            logger.info("scheduler listening on :%d (incarnation %d), "
                        "base workers %s", self.port, self._incarnation,
                        self._state.workers)

    # ------------------------------------------------------------------
    # journaled state access (the r11 ControlState refactor)
    # ------------------------------------------------------------------

    def _apply(self, op: str, **kw) -> None:
        """WAL-append (fsync) then apply one control-state op.
        Caller holds the lock. (publish_snapshot holds _snapshot_lock
        instead — the journal writer serializes appends internally, and
        the snapshot blob is the one field read under that lock.)  Raises
        :class:`journal.Fenced` when a newer leader holds the lease; the
        dispatcher surfaces that to the client, which rotates."""
        if self._journal is not None:
            self._journal.append(op, kw)
        self._state.apply(op, **kw)

    def _refresh_from_journal_locked(self) -> None:
        """Apply journal records appended since the last read (standby
        tailing / cold-restart replay).  Caller holds the lock."""
        if self._journal_reader is None:
            return
        for _fence, op, kw in self._journal_reader.read_new():
            self._state.apply(op, **kw)

    # read-only views kept for tests/tools that introspect the round-3
    # attribute names (chaos_run, test_faults, test_crash_recovery);
    # snapshot copies taken under the lock — never called from paths
    # that already hold it (internal code reads self._state directly)
    @property
    def _workers(self) -> List[str]:
        with self._lock:
            return list(self._state.workers)

    @property
    def _registered(self) -> Set[str]:
        with self._lock:
            return set(self._state.registered)

    @property
    def _removed_hosts(self) -> Set[str]:
        with self._lock:
            return set(self._state.removed_hosts)

    @property
    def _pending_recovery(self) -> Set[str]:
        with self._lock:
            return set(self._state.pending_recovery)

    @property
    def _barrier_arrived(self) -> Set[str]:
        with self._lock:
            return set(self._state.barrier_arrived)

    @property
    def _last_completed_epoch(self) -> int:
        with self._lock:
            return self._state.last_completed_epoch

    # ------------------------------------------------------------------
    # leadership: lease renewal, standby monitoring, takeover
    # ------------------------------------------------------------------

    @property
    def incarnation(self) -> int:
        """This instance's fencing epoch (0 = no lease configured)."""
        return self._incarnation

    def is_leader(self) -> bool:
        return self._active.is_set()

    def _start_evict_thread(self) -> None:
        self._evict_thread = threading.Thread(
            target=self._evict_loop, daemon=True)
        self._evict_thread.start()

    def _start_lease_thread(self) -> None:
        self._lease_thread = threading.Thread(
            target=self._lease_loop, daemon=True)
        self._lease_thread.start()

    # ------------------------------------------------------------------
    # r16 fleet-hang detector (dt_tpu/obs/blackbox.py)
    # ------------------------------------------------------------------

    def _start_hang_thread(self) -> None:
        if not obs_blackbox.enabled() or self._bb_thread is not None:
            return
        self._bb_thread = threading.Thread(target=self._hang_loop,
                                           daemon=True,
                                           name="dt-sched-hang")
        self._bb_thread.start()

    def _hang_loop(self) -> None:
        period = max(min(obs_blackbox.hang_s() / 4.0, 5.0), 0.05)
        while not self._stop.wait(period):
            if not self._active.is_set():
                continue
            try:
                self._hang_tick()
            except Exception:  # noqa: BLE001 — the detector must not die
                logger.exception("fleet-hang detector pass failed")

    def _hang_tick(self, hang_seconds: Optional[float] = None
                   ) -> Optional[dict]:
        """One fleet-progress check: when the oldest pending allreduce
        round has aged past ``DT_HANG_S``, cross-blame the worker the
        fleet is waiting on (worst straggler EWMA among the missing
        contributors — the workers that DID contribute all look hung
        too, but they are victims) and edge-trigger ``hang.suspect`` +
        one live scheduler-side bundle.  Round completion (or the next
        stall-free pass) edge-triggers ``hang.clear``.  Returns the
        current suspect view (tests drive this directly)."""
        threshold = float(hang_seconds if hang_seconds is not None
                          else obs_blackbox.hang_s())
        stalled = [p for p in self._dp.pending_rounds()
                   if p["age_s"] is not None and p["age_s"] > threshold
                   and p["waiting"]]
        fired = None
        cleared = False
        with self._bb_lock:
            was = self._bb_suspect
            if stalled:
                oldest = max(stalled, key=lambda p: p["age_s"])
                scores = self._dp.straggler_scores()
                # r18: a waited-on worker whose heartbeat dev view says
                # it is mid-XLA-compile is doing legitimate work, not
                # wedged — demote it below every non-compiling waiter
                # (and label the suspect) so a recompiling-after-resize
                # worker is not blamed for a hang it isn't causing.
                # BOUNDED demotion: only while the dev view is FRESH
                # (a dead worker's frozen track must not deflect blame
                # until eviction) and the compile's own age is under
                # max(10x the hang threshold, 5 min) — a worker WEDGED
                # inside lower().compile() becomes blamable again,
                # still carrying the compile label so the post-mortem
                # names the wedge site.  When every eligible waiter is
                # compiling, the worst straggler still gets named,
                # labeled.
                demote_max = max(10.0 * threshold, 300.0)
                now = time.time()
                with self._dev_lock:
                    compiling = {
                        h for h, v in self._dev_tracks.items()
                        if v.get("compiling")
                        and now - v.get("_ts", 0.0) <= 2.0 * threshold
                        and float(v.get("compiling_age_s", 0.0))
                        <= demote_max}
                    labeled = {h for h, v in self._dev_tracks.items()
                               if v.get("compiling")}
                blamed = max(oldest["waiting"],
                             key=lambda h: (h not in compiling,
                                            scores.get(h, 0.0)))
                cur = {"round": oldest["key"],
                       "age_s": oldest["age_s"],
                       "waiting": oldest["waiting"],
                       "contributed": oldest["contributed"],
                       "blamed": blamed,
                       "straggler_ms": round(scores.get(blamed, 0.0), 3)}
                if blamed in labeled:
                    cur["compile_in_progress"] = True
                if labeled & set(oldest["waiting"]):
                    cur["compiling"] = sorted(
                        labeled & set(oldest["waiting"]))
                if was is None:
                    self._bb_suspect = cur
                    fired = cur
                else:
                    was.update(cur)  # refresh age/blame, no re-fire
                    for k in ("compile_in_progress", "compiling"):
                        # conditional keys must CLEAR on refresh — a
                        # finished compile's label sticking to a now-
                        # genuine wedge would mislead the post-mortem
                        if k not in cur:
                            was.pop(k, None)
            elif was is not None:
                self._bb_suspect = None
                cleared = True
        if fired is not None:
            self._obs.event("hang.suspect", dict(fired))
            obs_blackbox.note("hang.suspect", role="scheduler", **fired)
            obs_blackbox.write_bundle("hang", host="scheduler",
                                      fatal=False, extra=dict(fired),
                                      tracer=self._obs)
        if cleared:
            self._obs.event("hang.clear", {"role": "scheduler"})
            obs_blackbox.note("hang.clear", role="scheduler")
        with self._bb_lock:
            return dict(self._bb_suspect) if self._bb_suspect else None

    def _bb_state(self) -> dict:
        """Blackbox state provider: the control state every bundle this
        process writes should carry (forensics must not need the
        journal to say who was in the job)."""
        out = {"role": "scheduler", "incarnation": self._incarnation,
               "active": self._active.is_set(), "port": self.port}
        # bounded acquire, not `with`: a bundle written from a signal
        # handler must not deadlock on a lock the dying thread holds —
        # the lock IS held inside the branch (DT006 can't see the
        # timeout-acquire form)
        if self._lock.acquire(timeout=0.5):
            try:
                out["workers"] = list(self._state.workers)  # dtlint: ignore[DT006]
                out["last_completed_epoch"] = \
                    self._state.last_completed_epoch  # dtlint: ignore[DT006]
                out["pending_recovery"] = \
                    sorted(self._state.pending_recovery)  # dtlint: ignore[DT006]
            finally:
                self._lock.release()
        if self._slo is not None:
            try:
                slo = self._slo.state()
                out["slo_active"] = slo["active"]
                out["slo_history"] = slo["history"][-8:]
            except Exception:  # noqa: BLE001 — best-effort forensics
                pass
        with self._bb_lock:
            if self._bb_suspect:
                out["hang_suspect"] = dict(self._bb_suspect)
        return out

    def _lease_loop(self):
        """Leader-side lease heartbeat; losing the lease to a newer
        incarnation demotes this instance (it stops serving writes —
        the journal would refuse them anyway)."""
        period = max(self.lease_s / 3.0, 0.05)
        owner = f"sched:{os.getpid()}"
        while not self._stop.wait(period):
            if self._lease is None or not self._active.is_set():
                return
            if not self._lease.renew(self._incarnation, owner):
                logger.error("lease lost to a newer incarnation; fencing "
                             "this scheduler (was %d)", self._incarnation)
                self._obs.event("leader.fenced",
                                {"incarnation": self._incarnation})
                self._active.clear()
                return

    def _primary_gone(self) -> bool:
        """True when a leader HAS existed (lease file present) and its
        lease lapsed.  A standby never takes over before any primary
        ever led — the launcher starts the standby FIRST (its port goes
        into ``DT_CTRL_ENDPOINTS``), and taking over on a missing lease
        file would race the booting primary's first acquire."""
        return (self._lease is not None
                and self._lease.read() is not None
                and self._lease.expired(self.lease_s))

    def _monitor_loop(self):
        """Standby: tail the journal (warmness) and watch the lease;
        expiry triggers takeover."""
        period = max(self.lease_s / 4.0, 0.05)
        while not self._stop.wait(period):
            if self._active.is_set():
                return
            try:
                with self._cv:
                    self._refresh_from_journal_locked()
                if self._primary_gone():
                    self._takeover("lease expired")
                    return
            except Exception:
                # a transient shared-fs error (lease/journal read or a
                # lost acquire race) must not kill the watch thread —
                # that would silently reduce the standby to on-demand
                # takeover only.  Log and keep watching.
                logger.exception("standby monitor pass failed; retrying")

    def _takeover(self, reason: str) -> bool:
        """Promote this standby to leader: final journal catch-up, lease
        acquire under ``incarnation + 1``, heartbeat grace reseed, and
        the ``scheduler.failover`` span chaos_run asserts on."""
        with self._takeover_lock:
            if self._active.is_set():
                return True
            t0 = self._obs.now()
            try:
                inc = self._lease.acquire(owner=f"sched:{os.getpid()}") \
                    if self._lease else self._incarnation + 1
            except journal.Fenced:
                return False  # another standby won; stay passive
            with self._cv:
                self._refresh_from_journal_locked()
                self._incarnation = inc
                self._journal = journal.JournalWriter(
                    self.journal_path, fence=inc, lease=self._lease)
                # heartbeat grace: every replayed worker gets a fresh
                # clock, or the evictor would count the failover window
                # as silence and evict the whole (healthy) fleet
                now = time.time()
                workers = list(self._state.workers)
                for h in workers:
                    self._heartbeats[h] = now
                self._cv.notify_all()
            for h in workers:
                self._dp.host_registered(h)
            self._active.set()
            if self.auto_evict_dead_s:
                self._start_evict_thread()
            if self._lease is not None:
                self._start_lease_thread()
            self._start_hang_thread()
            if obs_blackbox.enabled():
                # the new leader takes the provider slot: its bundles
                # (and any other process state dump) now stamp the LIVE
                # control state, not the deposed primary's
                obs_blackbox.register_state("scheduler", self._bb_state)
            self._obs.complete_span(
                "scheduler.failover", t0,
                {"incarnation": inc, "reason": reason,
                 "workers": len(workers)})
            self._obs.event("leader.elected",
                            {"incarnation": inc, "reason": reason})
            logger.warning("standby took over as leader (incarnation %d):"
                           " %s; workers=%s", inc, reason, workers)
            return True

    def _make_replicator(self):
        """Round-replication sender (primary -> standby): ship a
        completed allreduce round's served results BEFORE the responses
        go out, so a retry landing on the successor after a failover is
        served the identical average (exactly-once rounds).  Carries our
        fencing incarnation — a deposed primary's replica is refused."""
        host, port = self.peer

        def _rep(key: str, gen: int, seqs: Dict[str, int], result) -> None:
            protocol.request(host, int(port),
                             {"cmd": "ha_round",
                              "fence": self._incarnation, "key": key,
                              "gen": gen, "seqs": seqs, "value": result},
                             timeout=5.0)
        return _rep

    # ------------------------------------------------------------------
    # server plumbing
    # ------------------------------------------------------------------

    def _serve(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            with self._conns_lock:
                self._conns.add(conn)
            threading.Thread(target=self._handle_conn, args=(conn,),
                             daemon=True).start()

    def _handle_conn(self, conn: socket.socket):
        self._obs.counter("transport.connections")
        try:
            protocol.serve_connection(conn, self._handle_one)
        finally:
            with self._conns_lock:
                self._conns.discard(conn)

    def _handle_one(self, msg: dict) -> Optional[dict]:
        """One request on a persistent connection: the r13 causal-
        tracing wrapper (``rpc.<cmd>`` handler span linked to the
        client's wire.request span; shared with the range server —
        :func:`protocol.traced_handle`) over :meth:`_handle_inner`."""
        return protocol.traced_handle(self._obs, msg, self._handle_inner)

    def _handle_inner(self, msg: dict) -> Optional[dict]:
        """One request on a persistent connection; ``None`` closes the
        channel without answering (receive-side drop injection — the
        pooled client sees EOF and retries on a fresh channel)."""
        self._obs.counter("transport.requests")
        # Fault injection: DT_DROP_MSG=<percent> drops received
        # requests BEFORE dispatch (the ps-lite PS_DROP_MSG
        # transport fuzz, van.cc:430-431,563-570); clients retry.
        # A FaultPlan (elastic/faults.py) generalizes this with
        # seeded drop/delay/reorder/partition rules.
        drop = os.environ.get("DT_DROP_MSG")
        if drop and _drop_rng.random() * 100 < float(drop):
            logger.debug("DT_DROP_MSG: dropping %s", msg.get("cmd"))
            return None
        plan = faults.active_plan()
        if plan is not None and \
                not plan.on_recv(msg.get("cmd"), msg.get("host")):
            return None
        # leadership gate: a passive instance (standby, or a fenced
        # ex-leader) refuses everything but the passive command set so
        # clients rotate to the live leader.  A standby whose lease
        # watch says the primary is gone takes over ON DEMAND here —
        # the first failed-over client request is what completes the
        # failover, bounding the stall by the lease duration.
        if not self._active.is_set() and \
                msg.get("cmd") not in _PASSIVE_CMDS:
            if not (self.standby and self._primary_gone()
                    and self._takeover("client demand")):
                return {"error": "not_leader",
                        "incarnation": self._incarnation}
        # idempotency-token dedup (protocol.request reliable
        # mode): a replay whose first dispatch completed is
        # served the SAME response instead of re-dispatching
        token = msg.get("token")
        if token is not None:
            cached = self._tokens.get(token)
            if cached is not None:
                self._obs.counter("tokens.dedup_hits")
                return cached
        try:
            resp = self._dispatch(msg)
        except journal.Fenced as e:
            # a newer leader exists: stop accepting writes and tell the
            # client to rotate (its failover layer treats this like a
            # dead endpoint)
            logger.error("request fenced: %s", e)
            self._obs.event("leader.fenced",
                            {"incarnation": self._incarnation})
            self._active.clear()
            return {"error": f"fenced: {e}"}
        except Exception as e:  # surface handler bugs to the worker
            if self._stop.is_set():
                # dying mid-request: close() raced this handler (a
                # parked barrier wait woke into "scheduler closed", or
                # a later step tripped over torn-down state).  Answer
                # with a connection CLOSE, not an error frame — wire-
                # identical to the process death close() stands in for,
                # so the client fails over instead of surfacing a
                # shutdown artifact as a scheduler error.
                return None
            logger.exception("scheduler handler error")
            return {"error": repr(e)}
        if token is not None and "error" not in resp and \
                msg.get("cmd") not in _TOKEN_EXEMPT:
            self._tokens.put(token, resp)
        return resp

    def transport_stats(self) -> dict:
        """{connections, requests}: pooled channels make requests greatly
        exceed accepted connections (chaos_run asserts this).  Thin
        backwards-compat view over the obs counters the old ad-hoc ints
        folded into."""
        return {"connections": self._obs.get_counter(
                    "transport.connections"),
                "requests": self._obs.get_counter("transport.requests")}

    # ------------------------------------------------------------------
    # observability ingest/export (dt_tpu/obs)
    # ------------------------------------------------------------------

    def _obs_ingest(self, host: str, payload: dict) -> None:
        """Fold one worker's flushed span-ring batch into its
        (host, incarnation) track.  At-least-once safe: records carry a
        strictly increasing ``rseq`` (dt_tpu/obs/trace.py schema) and a
        replayed batch's already-ingested prefix is skipped."""
        key = f"{host}#{payload.get('inc', 0)}"
        records = payload.get("records") or ()
        with self._obs_lock:
            tr = self._obs_tracks.setdefault(
                key, {"records": [], "counters": {}, "dropped": 0,
                      "trunc": 0, "rseq": -1, "fseq": -1})
            # LRU by update order, bounded track count: a long-running
            # job with restart churn mints a fresh (host, pid) track per
            # incarnation — without eviction the scheduler (the one
            # process that lives for the whole job) leaks a multi-MB
            # ring per dead incarnation
            self._obs_tracks.pop(key)
            self._obs_tracks[key] = tr
            while len(self._obs_tracks) > _OBS_MAX_TRACKS:
                evicted = next(iter(self._obs_tracks))
                del self._obs_tracks[evicted]
                logger.info("obs: evicted stale track %s (track cap %d)",
                            evicted, _OBS_MAX_TRACKS)
            last = tr["rseq"]
            fresh = [r for r in records if r[1] > last]
            if fresh:
                tr["records"].extend(fresh)
                tr["rseq"] = max(r[1] for r in fresh)
                over = len(tr["records"]) - self._obs_cap
                if over > 0:
                    # count what the per-track ring sheds: the summary's
                    # drop column must admit timeline loss, not report a
                    # truncated track as complete
                    tr["trunc"] += over
                    del tr["records"][:over]
            # counters/dropped are cumulative gauges: apply only NEWER
            # snapshots (a heartbeat stalled in flight must not roll back
            # the close-flush's final values — fseq orders the payloads)
            fseq = int(payload.get("fseq", 0))
            if fseq > tr["fseq"]:
                tr["fseq"] = fseq
                if payload.get("counters"):
                    tr["counters"] = dict(payload["counters"])
                tr["dropped"] = int(payload.get("dropped", tr["dropped"]))

    def obs_dump(self) -> dict:
        """The merged job dump: every worker incarnation's track plus the
        control-plane track (this instance's tracer merged with the
        process tracer, which carries scheduler-side fault-injection
        events and wire spans recorded outside this instance)."""
        with self._obs_lock:
            tracks = {k: {"records": list(v["records"]),
                          "counters": dict(v["counters"]),
                          "dropped": v["dropped"] + v.get("trunc", 0)}
                      for k, v in self._obs_tracks.items()}
        own = self._obs.snapshot()
        proc = obs_trace.tracer().snapshot()
        ctrl = {"records": own["records"] + proc["records"],
                "counters": {**proc["counters"], **own["counters"]},
                "dropped": own["dropped"] + proc["dropped"]}
        tracks["control-plane"] = ctrl
        # per-worker straggler scores (round-contribution-lag EWMA, ms)
        # and the r14 policy view (shares / streaks / decision log) ride
        # the dump so dtop's live boards need no second command; the
        # export threads both through otherData
        with self._lock:
            pol = self._policy_view_locked()
        out = {"tracks": tracks,
               "straggler": self._dp.straggler_scores(),
               "policy": pol}
        dev = self._dev_view()
        if dev["workers"]:
            # the r18 device section rides the dump like policy/health:
            # export threads it through otherData to .metrics.json and
            # dtop's device board
            out["device"] = dev
        srv = self._serve_view()
        if srv["replicas"] or srv["decisions"]:
            # the r21 serving section rides the dump the same way —
            # dtop's serving board (QPS/p99/queue/shed per replica +
            # the autoscale decision log) needs no second command
            out["serving"] = srv
        if self._metrics is not None:
            # the r15 time-series + health sections ride the dump so
            # export.write lands them in .metrics.json and dtop's health
            # board needs no second command
            self._health_refresh()
            out["health"] = self.health_view()
            with self._hm_lock:
                mtracks = {
                    k: {"samples": list(t["samples"]),
                        "gauges": [list(g) for g in t["gauges"]],
                        "dropped": t["dropped"] + t.get("trunc", 0)}
                    for k, t in self._hm_tracks.items()}
            mtracks["control-plane"] = {
                "samples": self._metrics.series(),
                "gauges": self._metrics.gauges_export(),
                "dropped": self._metrics.dropped()}
            out["metrics"] = {"tracks": mtracks}
        return out

    # ------------------------------------------------------------------
    # metrics/health plane (dt_tpu/obs/metrics.py, r15)
    # ------------------------------------------------------------------

    def _hm_ingest(self, host: str, payload: dict) -> None:
        """Fold one worker's shipped metrics batch into its
        (host, incarnation) track.  At-least-once safe: time-series
        samples carry a strictly increasing ``seq`` and a replayed
        batch's already-ingested prefix is skipped; the cumulative
        gauge/hist snapshots apply only when NEWER (``gseq`` orders the
        payloads, like the span ingest's ``fseq``)."""
        if self._metrics is None:
            return
        key = f"{host}#{payload.get('inc', 0)}"
        cap = self._metrics._cap
        with self._hm_lock:
            tr = self._hm_tracks.setdefault(
                key, {"samples": [], "sseq": -1, "gseq": -1,
                      "gauges": [], "hists": [], "dropped": 0,
                      "trunc": 0})
            # LRU by update order, same track bound as the span ingest
            self._hm_tracks.pop(key)
            self._hm_tracks[key] = tr
            while len(self._hm_tracks) > _OBS_MAX_TRACKS:
                del self._hm_tracks[next(iter(self._hm_tracks))]
            fresh = [s for s in (payload.get("samples") or ())
                     if s.get("seq", 0) > tr["sseq"]]
            if fresh:
                tr["samples"].extend(fresh)
                tr["sseq"] = max(s["seq"] for s in fresh)
                over = len(tr["samples"]) - cap
                if over > 0:
                    tr["trunc"] += over
                    del tr["samples"][:over]
            gseq = int(payload.get("gseq", 0))
            if gseq > tr["gseq"]:
                tr["gseq"] = gseq
                tr["gauges"] = [list(g) for g in
                                (payload.get("gauges") or ())]
                tr["hists"] = [list(h) for h in
                               (payload.get("hists") or ())]
                tr["dropped"] = int(payload.get("dropped",
                                                tr["dropped"]))

    def _dev_ingest(self, host: str, payload: dict) -> None:
        """Keep the NEWEST per-host device view (heartbeat ``dev``
        section).  ``dseq`` orders payloads on the at-least-once
        channel — a delayed/duplicated old beat must not roll the view
        back (resurrecting a cleared ``compiling`` flag would feed the
        fleet-blame demotion stale facts); the ingest wall-clock rides
        as ``_ts`` so the demotion can require a FRESH view.  Bounded
        by the worker set plus the same LRU cap as the other
        ingests."""
        with self._dev_lock:
            tr = self._dev_tracks.get(host)
            dseq = int(payload.get("dseq", 0))
            if tr is not None and dseq and int(tr.get("dseq", 0)) >= dseq:
                return  # stale or duplicated beat
            self._dev_tracks.pop(host, None)
            entry = dict(payload)
            entry["_ts"] = time.time()
            self._dev_tracks[host] = entry
            while len(self._dev_tracks) > _OBS_MAX_TRACKS:
                del self._dev_tracks[next(iter(self._dev_tracks))]

    def _dev_forget(self, hosts) -> None:
        """Membership removals scrub the device view too (the
        ``_metrics_forget`` analog): an evicted worker must not keep
        advertising a frozen compile/memory row."""
        hosts = set(hosts)
        with self._dev_lock:
            for h in hosts:
                self._dev_tracks.pop(h, None)

    def _dev_view(self) -> dict:
        """The obs_dump/health device section: per-host compile +
        memory views, plus which hosts report a compile in progress."""
        with self._dev_lock:
            workers = {h: dict(v) for h, v in self._dev_tracks.items()}
        return {"workers": workers,
                "compiling": sorted(h for h, v in workers.items()
                                    if v.get("compiling"))}

    # ------------------------------------------------------------------
    # serving plane (dt_tpu/serve, r21)
    # ------------------------------------------------------------------

    def _serve_register(self, host: str, addr, weights_step: int) -> dict:
        """Admit (or re-admit after a failover) a serving replica.  A
        re-registration preserves the draining flag: a replica the
        autoscaler already chose to drain must not launder itself back
        into rotation by reconnecting."""
        with self._serve_lock:
            prev = self._serve_replicas.get(host)
            self._serve_replicas[host] = {
                "addr": (str(addr[0]), int(addr[1])),
                "ts": time.monotonic(),
                "gauges": dict(prev["gauges"]) if prev else {},
                "weights_step": int(weights_step),
                "refreshes": int(prev["refreshes"]) if prev else 0,
                "draining": bool(prev["draining"]) if prev else False,
            }
            if host not in self._serve_order:
                self._serve_order.append(host)
            live = sum(1 for e in self._serve_replicas.values()
                       if not e["draining"])
            # want tracks the largest fleet ever launched at it: the
            # initial registrations and a scale-up launch both settle
            # live == want; a drained replica re-registering keeps its
            # flag and cannot inflate the target
            self._serve_want = live if self._serve_want is None \
                else max(self._serve_want, live)
            n = len(self._serve_replicas)
        self._obs.event("serve.scale", {"kind": "register", "host": host,
                                        "replicas": n})
        obs_metrics.registry().gauge("serve.replicas", float(n))
        return {"registered": True}

    def _serve_heartbeat(self, host: str, gauges: dict,
                         weights_step: int, refreshes: int) -> dict:
        """Fold one replica's liveness + gauges in, prune stale
        replicas, and run one autoscale evaluation.  An unknown host
        (a standby promoted with an empty table) answers
        ``registered: false`` so the ServeClient re-registers — the
        serving view reconverges without journaling it."""
        now = time.monotonic()
        with self._serve_lock:
            ent = self._serve_replicas.get(host)
            if ent is None:
                return {"registered": False, "drain": False}
            ent["ts"] = now
            ent["gauges"] = dict(gauges)
            ent["weights_step"] = int(weights_step)
            ent["refreshes"] = int(refreshes)
            drain = bool(ent["draining"])
            dead = [h for h, e in self._serve_replicas.items()
                    if now - e["ts"] > self._serve_ttl]
            for h in dead:
                del self._serve_replicas[h]
            n = len(self._serve_replicas)
            decision = self._serve_decide_locked()
        for h in dead:
            logger.warning("serving replica %s lost (stale heartbeat)",
                           h)
            self._obs.event("serve.scale", {"kind": "lost", "host": h,
                                            "replicas": n})
        if dead:
            obs_metrics.registry().gauge("serve.replicas", float(n))
        if decision is not None:
            self._obs.event("serve.scale",
                            {"kind": decision["kind"],
                             "host": decision.get("host"),
                             "replicas": decision["n_after"]})
        return {"registered": True, "drain": drain}

    def _serve_decide_locked(self):
        """One ServePolicy evaluation (serve heartbeat cadence).  Only
        evaluates when the live fleet matches the current want — while
        a scale-up launch or a drain is still in flight, another
        decision would double-fire on the same pressure.  Rate-limited
        to one evaluation per 0.25 s — every replica's heartbeat lands
        here, so un-throttled streaks would scale with fleet size and
        heartbeat cadence instead of with seconds of sustained
        pressure.  Returns the appended decision-log row for event
        emission, or None."""
        if self._serve_policy is None:
            return None
        now = time.monotonic()
        if now - self._serve_last_eval < 0.25:
            return None
        self._serve_last_eval = now
        live = sorted(h for h, e in self._serve_replicas.items()
                      if not e["draining"])
        if self._serve_want is None or len(live) != self._serve_want \
                or not live:
            return None
        base = set(self._serve_order[:self._serve_policy.min_replicas])
        depths = {h: float(self._serve_replicas[h]["gauges"]
                           .get("serve.queue_depth", 0.0))
                  for h in live}
        d = self._serve_policy.decide(live, base, depths,
                                      self._serve_hi, self._serve_lo)
        self._serve_hi, self._serve_lo = d.hi_streak, d.lo_streak
        if d.action == "hold":
            return None
        row = {"seq": len(self._serve_decisions), "kind": d.action,
               "n_before": len(live)}
        if d.action == "scale_up":
            self._serve_want = len(live) + d.want
            row["n_after"] = self._serve_want
        else:
            self._serve_want = len(live) - 1
            self._serve_replicas[d.host]["draining"] = True
            row["n_after"] = self._serve_want
            row["host"] = d.host
        self._serve_decisions.append(row)
        logger.info("serve policy: %s -> want %d (%s)", d.action,
                    self._serve_want, row.get("host", ""))
        return row

    def _serve_view(self) -> dict:
        """The obs_dump/status serving section."""
        with self._serve_lock:
            reps = {h: {"addr": list(e["addr"]),
                        "gauges": dict(e["gauges"]),
                        "weights_step": int(e["weights_step"]),
                        "refreshes": int(e["refreshes"]),
                        "draining": bool(e["draining"])}
                    for h, e in self._serve_replicas.items()}
            return {"enabled": self._serve_policy is not None,
                    "replicas": reps, "want": self._serve_want,
                    "decisions": [dict(d)
                                  for d in self._serve_decisions]}

    def _metrics_forget(self, hosts) -> None:
        """Membership removals scrub the per-worker metrics state (the
        ``_policy_forget`` analog): the retained time-series tracks and
        the worker-labeled gauges would otherwise advertise an evicted
        worker as a live series — frozen step rate and all — for the
        rest of the job."""
        if self._metrics is None:
            return
        hosts = set(hosts)
        with self._hm_lock:
            for key in [k for k in self._hm_tracks
                        if k.split("#")[0] in hosts]:
                del self._hm_tracks[key]
        for h in sorted(hosts):
            self._metrics.forget_label("worker", h)

    def _worker_step_rates(self) -> Dict[str, float]:
        """steps/s per worker host, derived from the last few shipped
        time-series samples carrying ``train.steps`` (the freshest
        incarnation wins — dict update order is LRU)."""
        out: Dict[str, float] = {}
        with self._hm_lock:
            for key, tr in self._hm_tracks.items():
                host = key.split("#")[0]
                pts = [(s["ts_ms"], s["gauges"].get("train.steps"))
                       for s in tr["samples"][-8:]
                       if s.get("gauges", {}).get("train.steps")
                       is not None]
                if len(pts) >= 2 and pts[-1][0] > pts[0][0]:
                    out[host] = round(
                        max(pts[-1][1] - pts[0][1], 0) * 1000.0
                        / (pts[-1][0] - pts[0][0]), 4)
        return out

    def _health_refresh(self) -> None:
        """One health pass: refresh the scheduler-derived gauges and run
        the live SLO rules.  Called from the background sampler, the
        ``health``/``obs_dump`` commands, and ``/metrics`` scrapes —
        cheap (a few dict folds), and takes ``_lock`` / ``_obs_lock`` /
        ``_hm_lock`` one at a time (no nesting).  PASSIVE instances
        skip the pass entirely: a warm standby never receives
        heartbeats (not in ``_PASSIVE_CMDS``), so sampling staleness
        there would fire bogus breaches for every healthy worker —
        the refresh resumes the moment the instance leads."""
        if self._metrics is None or not self._active.is_set():
            return
        reg = self._metrics
        now = time.time()
        with self._lock:
            stale = {h: round(now - self._heartbeats.get(h, now), 3)
                     for h in self._state.workers}
        for h, v in sorted(stale.items()):
            reg.gauge("sched.heartbeat_staleness_s", v,
                      labels={"worker": h})
        rates = self._worker_step_rates()
        for h, r in sorted(rates.items()):
            reg.gauge("worker.step_rate", r, labels={"worker": h})
        with self._obs_lock:
            drops = sum(t["dropped"] + t.get("trunc", 0)
                        for t in self._obs_tracks.values())
        drops += self._obs.dropped() + obs_trace.tracer().dropped()
        reg.gauge("obs.ring_dropped", drops)
        inputs: Dict[str, object] = {
            "worker.step_rate": rates,
            "round.wait_ms": self._dp.straggler_scores(),
            "sched.heartbeat_staleness_s": stale,
            "obs.ring_dropped": float(drops),
        }
        p99 = reg.hist_quantile("journal.append_ms", 0.99)
        if p99 is not None:
            inputs["journal.append_ms.p99"] = p99
        self._slo.evaluate(inputs, tracer=self._obs)

    def health_view(self) -> dict:
        """Machine-readable training-health surface: SLO rule state +
        scheduler gauges/hists + each worker incarnation's latest
        shipped gauges — the ``health`` RPC / ``obs_dump`` payload the
        serving plane and dtop's board read."""
        if self._metrics is None:
            return {"enabled": False}
        with self._hm_lock:
            workers = {
                k: {"samples": len(t["samples"]),
                    "dropped": t["dropped"] + t.get("trunc", 0),
                    "gauges": dict(t["samples"][-1].get("gauges") or {})
                    if t["samples"] else {}}
                for k, t in sorted(self._hm_tracks.items())}
        out = {"enabled": True,
               "interval_s": obs_metrics.interval_s(),
               "slo": self._slo.state(),
               "gauges": self._metrics.gauges_export(),
               "hists": self._metrics.hists_export(),
               "workers": workers}
        dev = self._dev_view()
        if dev["workers"]:
            out["device"] = dev  # r18: the health RPC carries it too
        return out

    def metrics_text(self) -> str:
        """Prometheus text exposition: the scheduler/process registry
        (+ live counters) under ``role="scheduler"``, plus every worker
        incarnation's cumulative gauges/hists and counters under
        ``worker``/``inc`` label sets — the machine-readable surface the
        reference's ``PS_VERBOSE`` logging never was.  Empty exposition
        when the plane is off (graceful like ``health_view``)."""
        if self._metrics is None:
            return ""
        self._health_refresh()
        jobs = [({"role": "scheduler"},
                 {"gauges": self._metrics.gauges_export(),
                  "hists": self._metrics.hists_export()},
                 {**obs_trace.tracer().counters(),
                  **self._obs.counters()})]
        with self._obs_lock:
            ctrs = {k: dict(v["counters"])
                    for k, v in self._obs_tracks.items()}
        with self._hm_lock:
            tracks = [(k, [list(g) for g in t["gauges"]],
                       [list(h) for h in t["hists"]])
                      for k, t in sorted(self._hm_tracks.items())]
        for key, gauges, hists in tracks:
            host, _, inc = key.partition("#")
            jobs.append(({"worker": host, "inc": inc},
                         {"gauges": gauges, "hists": hists},
                         ctrs.get(key, {})))
        return obs_metrics.render_prometheus(jobs)

    def close(self):
        """Shut the service down.  Idempotent, and bounded even when a
        housekeeping pass is mid-flight: the evictor/monitor/lease loops
        are woken (they park on ``_stop``), CV waiters are notified, and
        every owned thread is joined with a timeout — the r11 fix for
        the close-vs-evictor race where an evict pass holding ``_cv``
        could leave ``close()`` returning with live threads still
        mutating a half-closed scheduler."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        # shutdown() BEFORE close(): a plain close of an fd another
        # thread is blocked in accept() on does NOT wake it on Linux —
        # the kernel socket stays alive inside the in-flight syscall,
        # the port keeps accepting, and late requests would hit a
        # half-closed scheduler (closed journal).  shutdown wakes the
        # accept with EINVAL and the serve loop exits.
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass
        # sever accepted connections: a client parked at a barrier on
        # this scheduler must see a reset NOW (it fails over / retries),
        # not its own 300 s timeout — same wire-visible behavior as the
        # process dying
        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        me = threading.current_thread()
        for t in (self._evict_thread, self._monitor_thread,
                  self._lease_thread, self._bb_thread, self._thread):
            if t is not None and t is not me and t.is_alive():
                t.join(timeout=5.0)
        # identity-guarded: closing a deposed/standby instance must not
        # strip the still-running leader's provider (same-process HA pair)
        obs_blackbox.unregister_state("scheduler", fn=self._bb_state)
        if self._hm_sampler is not None:
            self._hm_sampler.stop()
        if self._http is not None:
            self._http.close()
        if self._journal is not None:
            self._journal.close()

    def join(self, timeout: Optional[float] = None) -> bool:
        """Block until :meth:`close` is called (the standalone scheduler
        process entrypoint parks here); True when closed."""
        return self._stop.wait(timeout)

    # ------------------------------------------------------------------
    # dispatch
    # ------------------------------------------------------------------

    def _dispatch(self, msg: dict) -> dict:
        cmd = msg.get("cmd")
        if cmd == "register":
            return self._register(msg["host"], bool(msg.get("is_new")),
                                  bool(msg.get("is_recovery")),
                                  reattach=bool(msg.get("reattach")))
        if cmd == "heartbeat":
            # worker span rings piggyback on the heartbeat, exactly like
            # profiler control already does (kvstore_dist.h:102-110);
            # the r15 metrics time-series batches ride the same message
            ob = msg.get("obs")
            if ob is not None:
                self._obs_ingest(msg["host"], ob)
            hm = msg.get("hm")
            if hm is not None:
                self._hm_ingest(msg["host"], hm)
            dev = msg.get("dev")
            if dev is not None:
                self._dev_ingest(msg["host"], dev)
            with self._lock:
                self._heartbeats[msg["host"]] = time.time()
                pseq = int(msg.get("pseq", 0))
                newer = [c for c in self._profile_cmds if c["seq"] > pseq]
                caps = []
                if dev is not None:
                    cseq = int(dev.get("cseq", 0))
                    caps = [c for c in self._capture_cmds
                            if c["target"] == msg["host"]
                            and c["seq"] > cseq]
            out = {}
            if newer:
                out["profile_cmds"] = newer
            if caps:
                out["capture_cmds"] = caps
            if self._ckpt_epoch_end:
                # r19 scheduler drain: ask the fleet for an epoch-
                # boundary checkpoint (monotonic bool — see
                # request_fleet_checkpoint)
                out["ckpt_epoch_end"] = True
            return out
        if cmd == "obs_push":
            # synchronous flush (worker close / injected-crash path);
            # rseq/sample-seq dedup makes replays idempotent
            if msg.get("obs") is not None:
                self._obs_ingest(msg["host"], msg["obs"])
            if msg.get("hm") is not None:
                self._hm_ingest(msg["host"], msg["hm"])
            return {}
        if cmd == "obs_dump":
            return {"job": self.obs_dump()}
        if cmd == "health":
            # the r15 training-health RPC: SLO state + gauges, fresh
            self._health_refresh()
            return {"health": self.health_view()}
        if cmd == "ha_round":
            return self._ha_round(msg)
        if cmd == "blackbox_index":
            # r16 flight recorder: the collected bundle manifest + the
            # fleet-hang suspect view (dtop and the chaos harness read
            # blame from here; the bundles themselves stay on disk)
            with self._bb_lock:
                suspect = dict(self._bb_suspect) \
                    if self._bb_suspect else None
            return {"enabled": obs_blackbox.enabled(),
                    "dir": obs_blackbox.bundle_dir(),
                    "bundles": obs_blackbox.read_manifest(),
                    "suspect": suspect}
        if cmd == "status":
            with self._lock:
                st = self._state
                out = {"active": self._active.is_set(),
                       "incarnation": self._incarnation,
                       "workers": list(st.workers),
                       "last_completed_epoch":
                           st.last_completed_epoch,
                       "policy": self._policy_view_locked(),
                       "ckpt": {
                           "committed_step":
                               int(st.ckpt_committed["step"])
                               if st.ckpt_committed else None,
                           "pending_step":
                               int(st.ckpt_pending["step"])
                               if st.ckpt_pending else None,
                           "draining": sorted(st.draining)}}
            out["straggler"] = self._dp.straggler_scores()
            srv = self._serve_view()
            if srv["replicas"] or srv["decisions"]:
                out["serving"] = {"replicas": sorted(srv["replicas"]),
                                  "want": srv["want"],
                                  "decisions": len(srv["decisions"])}
            return out
        if cmd == "profile":
            # rank-0-drives-all profiling (kvstore_dist_server.h:275-322):
            # record the command; every worker picks it up on its next
            # heartbeat and applies it locally with a rank prefix.
            # (host, post_seq) dedups at-least-once client retries — a
            # re-sent command returns its original seq instead of being
            # re-enqueued after later commands.
            with self._lock:
                key = (msg.get("host"), msg.get("post_seq"))
                if key[0] is not None and key in self._profile_posted:
                    return {"seq": self._profile_posted[key]}
                self._profile_seq += 1
                self._profile_cmds.append(
                    {"seq": self._profile_seq,
                     "action": msg["action"],
                     "params": msg.get("params") or {}})
                del self._profile_cmds[:-32]  # bounded history
                if key[0] is not None:
                    self._profile_posted[key] = self._profile_seq
                    while len(self._profile_posted) > 128:
                        self._profile_posted.pop(
                            next(iter(self._profile_posted)))
                return {"seq": self._profile_seq}
        if cmd == "profile_capture":
            # r18 device plane: queue a bounded N-step jax.profiler
            # capture on ONE worker; delivered on the target's next
            # heartbeat (dev.cseq dedups), (host, post_seq) dedups
            # at-least-once client retries exactly like "profile"
            with self._lock:
                key = (msg.get("host"), msg.get("post_seq"))
                if key[0] is not None and key in self._capture_posted:
                    return {"seq": self._capture_posted[key]}
                if msg["target"] not in self._state.workers:
                    # a typo'd/absent target would queue a command only
                    # a heartbeat from that exact host could ever
                    # collect — "queued: true" forever; fail the
                    # operator loudly instead.  (A live worker running
                    # without DT_DEVICE_OBS also never collects — its
                    # heartbeats carry no dev view — which the error
                    # message documents.)
                    return {"error":
                            f"profile_capture target {msg['target']!r} "
                            f"is not a live worker (live: "
                            f"{sorted(self._state.workers)}); note the "
                            f"target must run with DT_DEVICE_OBS=1"}
                self._capture_seq += 1
                self._capture_cmds.append(
                    {"seq": self._capture_seq,
                     "target": msg["target"],
                     "steps": int(msg.get("steps", 8))})
                del self._capture_cmds[:-16]  # bounded history
                if key[0] is not None:
                    self._capture_posted[key] = self._capture_seq
                    while len(self._capture_posted) > 128:
                        self._capture_posted.pop(
                            next(iter(self._capture_posted)))
                return {"seq": self._capture_seq}
        if cmd in DataPlane.CMDS:
            if cmd == "allreduce":
                # a named scheduler-crash site INSIDE the data-plane
                # epoch: chaos `--plan scheduler_kill` kills here,
                # mid-round (docs/ha.md failure catalog)
                faults.crash_point("sched.allreduce",
                                   host=msg.get("host"))
            return self._dp.dispatch(msg)
        if cmd == "serve_register":
            return self._serve_register(msg["host"], msg["addr"],
                                        int(msg.get("weights_step", 0)))
        if cmd == "serve_heartbeat":
            return self._serve_heartbeat(msg["host"],
                                         msg.get("gauges") or {},
                                         int(msg.get("weights_step", 0)),
                                         int(msg.get("refreshes", 0)))
        if cmd == "serve_endpoints":
            # read-only serving view (replica addrs + freshest gauges +
            # the autoscale want/decision log) — the InferClient's
            # discovery, the refresher's walk order, and the bench's
            # scale-to-want signal all read from here
            with self._serve_lock:
                reps = {h: {"addr": list(e["addr"]),
                            "gauges": dict(e["gauges"]),
                            "weights_step": int(e["weights_step"]),
                            "refreshes": int(e["refreshes"]),
                            "draining": bool(e["draining"])}
                        for h, e in self._serve_replicas.items()}
                return {"replicas": reps, "want": self._serve_want,
                        "decisions": [dict(d)
                                      for d in self._serve_decisions]}
        if cmd == "register_server":
            with self._servers_lock:
                self._servers[int(msg["index"])] = (msg["host"],
                                                    int(msg["port"]))
            logger.info("range server %d registered at %s:%d",
                        int(msg["index"]), msg["host"], int(msg["port"]))
            return {}
        if cmd == "servers":
            return {"servers": self._server_list()}
        if cmd == "mc_barrier":
            return self._mc_barrier(msg["host"], int(msg["epoch"]),
                                    msg.get("info") or {})
        if cmd == "barrier":
            return self._plain_barrier(msg["host"],
                                       int(msg.get("seq", -1)))
        if cmd == "publish_snapshot":
            with self._snapshot_lock:
                blob = msg["blob"]
                if self._journal is not None:
                    # model-sized blobs do NOT ride the WAL: durably
                    # sidecar the bytes first, journal the tiny marker,
                    # then memo the resolved blob (same bytes the
                    # sidecar holds — skips a full read-back)
                    marker = journal.write_snapshot_sidecar(
                        self.journal_path, blob)
                    self._apply("snapshot", blob=marker)
                    # memo, not a state transition: the journal carries
                    # the marker; these are the very bytes it references
                    self._state.snapshot = blob  # dtlint: ignore[DT006,DT010]
                else:
                    self._apply("snapshot", blob=blob)
            return {}
        if cmd == "fetch_snapshot":
            with self._snapshot_lock:
                # the snapshot blob is the ONE ControlState field read
                # under _snapshot_lock, not _lock (see _apply docstring)
                snap = self._state.snapshot  # dtlint: ignore[DT006]
                if journal.snapshot_marker(snap) and self.journal_path:
                    # replay left an unresolved marker (sidecar written
                    # after this record was tailed): resolve on fetch,
                    # degrade to "no snapshot" if the file is gone
                    snap = journal.load_snapshot_sidecar(
                        self.journal_path, snap[journal._SNAP_REF])
                    if snap is not None:
                        # marker-resolution memo (see publish_snapshot)
                        self._state.snapshot = snap  # dtlint: ignore[DT006,DT010]
                return {"blob": snap}
        if cmd == "num_dead":
            return {"count": self._num_dead(float(msg.get("timeout_s", 60)))}
        if cmd == "membership":
            with self._lock:
                return {"workers": list(self._state.workers)}
        if cmd == "ckpt_intent":
            return self._ckpt_intent(msg["host"], int(msg["step"]),
                                     int(msg["epoch"]))
        if cmd == "ckpt_ack":
            return self._ckpt_ack(msg["host"], int(msg["step"]),
                                  msg["path"], msg["sha256"],
                                  msg.get("cursor") or {})
        if cmd == "ckpt_manifest":
            with self._lock:
                st = self._state
                pend = None
                if st.ckpt_pending is not None:
                    p = st.ckpt_pending
                    pend = {"step": p["step"], "epoch": p["epoch"],
                            "workers": list(p["workers"]),
                            "acks": sorted(p["acks"])}
                com = None
                if st.ckpt_committed is not None:
                    c = st.ckpt_committed
                    com = {"step": c["step"], "epoch": c["epoch"],
                           "workers": list(c["workers"]),
                           "files": {h: dict(a)
                                     for h, a in c["files"].items()}}
                return {"committed": com, "pending": pend,
                        "resume": bool(self._resume_boot)}
        if cmd == "drain":
            return self._drain(msg["host"])
        if cmd == "shutdown":
            self.close()
            return {}
        return {"error": f"unknown cmd {cmd!r}"}

    def _ha_round(self, msg: dict) -> dict:
        """Install a completed round replicated by the live primary.
        Fenced: a replica stamped with an incarnation below ours comes
        from a deposed leader and is refused (stale-incarnation write)."""
        fence = int(msg.get("fence", 0))
        if fence < self._incarnation:
            return {"error": f"fenced: round replica carries stale "
                             f"incarnation {fence} < {self._incarnation}"}
        self._dp.install_round(msg["key"], int(msg["gen"]),
                               dict(msg["seqs"]), msg["value"])
        self._obs.counter("ha.rounds_replicated")
        return {}

    # ------------------------------------------------------------------
    # registration / heartbeat
    # ------------------------------------------------------------------

    def _register(self, host: str, is_new: bool,
                  is_recovery: bool = False,
                  reattach: bool = False) -> dict:
        """``reattach=True`` (client endpoint rotation, docs/ha.md) is an
        identity/fence refresh from a LIVE process, not a restart: it
        must not purge the host's retry-dedup state — a spurious
        rotation back to a healthy leader would otherwise clear
        ``_async_served``, letting an in-flight async_push retry whose
        response was lost re-apply its gradient (double fold)."""
        faults.crash_point("sched.register", host=host)
        with self._cv:
            st = self._state
            if host in st.removed_hosts and not is_recovery:
                # sender-validation drop of removed hosts
                # (van.cc:571-574)
                return {"error": "host was removed from the job"}
            if is_recovery and host in st.workers:
                # QUICK restart: the old incarnation crashed but hasn't
                # been evicted yet.  Its process is gone, so treat this
                # exactly like an eviction (drop from the live set,
                # rewrite host_worker, finish survivor-satisfied
                # collectives) and fall through to the pending-recovery
                # queue — otherwise the restarted worker would park at
                # the barrier while survivors wait forever on the dead
                # incarnation's contributions.  The host joins
                # _pending_recovery BEFORE _complete_pending_locked and
                # host_worker is rewritten like the auto-evict path
                # (r5 advisor): a parked barrier firing during THIS
                # registration must not re-ADD the host via the normal
                # diff — that would hand the restarted worker a normal
                # rank with begin_epoch=0 (epoch desync) and, in elastic
                # mode, spawn a duplicate process under its identity.
                # (The stale arrival discard rides inside the journaled
                # quick_evict op: the DEAD incarnation may have arrived
                # at the parked barrier before crashing, and its arrival
                # must not count as the NEW incarnation's.)
                self._apply("quick_evict", host=host, seq=st.log_seq + 1)
                self._audit_locked("REMOVED", host)
                self._dp.hosts_removed({host})
                self._metrics_forget({host})
                self._dev_forget({host})
                self._rewrite_host_file([host])
                self._complete_pending_locked()
            if host in st.removed_hosts:
                # identity reissue (van.cc:187-218 is_recovery=true): a
                # crashed worker restarts under its OLD id.  Queue it for
                # re-admission at the next membership barrier — NOT
                # mid-epoch: collectives in flight must keep their
                # contributor set — and let it bootstrap from the
                # snapshot meanwhile.  Its dedup caches are purged
                # (fresh sequences after restart).
                self._apply("recovery_pending", host=host)
                self._heartbeats[host] = time.time()
                self._dp.host_registered(host)
                for key in [k for k in self._profile_posted
                            if k[0] == host]:
                    del self._profile_posted[key]
                self._cv.notify_all()
                self._obs.event("recovery.registered", {"host": host})
                logger.info("recovery registration from %s: pending "
                            "re-admission at the next barrier", host)
                return {"rank": -1, "workers": list(st.workers),
                        "recovery_pending": True,
                        "resume_epoch": st.last_completed_epoch + 1,
                        "profile_seq": self._profile_seq,
                        "fence": self._incarnation,
                        "servers": self._server_list()}
            self._apply("worker_add", host=host, base=not is_new)
            self._heartbeats[host] = time.time()
            if not reattach:
                # a (re)registering worker starts a fresh profiler-post
                # AND async-push sequence — purge its stale retry-dedup
                # entries so its first request after a restart isn't
                # swallowed by an old (host, seq) key (a swallowed
                # async_push would silently drop a gradient and hand
                # back pre-crash weights).  A failover reattach is the
                # SAME process continuing its sequences: no purge.
                for key in [k for k in self._profile_posted
                            if k[0] == host]:
                    del self._profile_posted[key]
                self._dp.host_registered(host)
            self._cv.notify_all()
            # profile_seq: joiners sync PAST the buffered command history
            # (don't replay a long-finished profiling session on new hosts)
            out = {"rank": st.workers.index(host),
                   "workers": list(st.workers),
                   "profile_seq": self._profile_seq,
                   "fence": self._incarnation,
                   "servers": self._server_list()}
            # r19 cold-restart resume: until the restarted fleet passes the
            # checkpointed epoch's barrier, hand every registrant the
            # committed manifest so it restores params + data cursor
            # before its first step (data-parallel state is identical
            # across workers, so any digest-verified blob restores any
            # worker — which is what makes N±1 elastic resume work).
            com = st.ckpt_committed
            if self._resume_boot and com is not None and \
                    st.last_completed_epoch < int(com["epoch"]):
                out["resume"] = {
                    "step": int(com["step"]), "epoch": int(com["epoch"]),
                    "workers": list(com["workers"]),
                    "files": {h: dict(a)
                              for h, a in com["files"].items()}}
            return out

    def wait_for_workers(self, n: Optional[int] = None, timeout: float = 120):
        """Block until n workers registered (rendezvous;
        ``van.cc:95-185`` waits for all ADD_NODEs)."""
        n = n if n is not None else self.expected_workers
        deadline = time.time() + timeout
        with self._cv:
            while len(self._state.registered) < n:
                remaining = deadline - time.time()
                if remaining <= 0:
                    raise TimeoutError(
                        f"only {len(self._state.registered)}/{n} workers "
                        "registered")
                self._cv.wait(remaining)

    def _num_dead(self, timeout_s: float) -> int:
        now = time.time()
        with self._lock:
            return sum(1 for h in self._state.workers
                       if now - self._heartbeats.get(h, 0.0) > timeout_s)

    # ------------------------------------------------------------------
    # dead-worker auto-eviction (crash recovery)
    # ------------------------------------------------------------------

    def _evict_loop(self):
        period = max(self.auto_evict_dead_s / 4.0, 0.1)
        while not self._stop.wait(period):
            if not self._active.is_set():
                continue  # fenced ex-leader: membership is not ours
            now = time.time()
            with self._cv:
                st = self._state
                dead = [
                    h for h in st.workers
                    if now - self._heartbeats.get(h, 0.0) >
                    (self.auto_evict_dead_s if h in st.registered
                     else self.startup_grace_s)]
                if not dead:
                    continue
                try:
                    for h in dead:
                        logger.warning(
                            "evicting dead worker %s (silent %.1fs)",
                            h, now - self._heartbeats.get(h, 0.0))
                        self._apply("evict", host=h, seq=st.log_seq + 1)
                        self._audit_locked("REMOVED", h)
                    self._dp.hosts_removed(set(dead))
                    self._metrics_forget(dead)
                    self._dev_forget(dead)
                    self._rewrite_host_file(dead)
                    # _complete_pending_locked journal-appends too
                    # (barrier_complete / mc_* ops) — a Fenced escaping
                    # from it used to kill this thread with _active
                    # still set: a deposed ex-leader kept serving as
                    # leader (split-brain window) with auto-eviction
                    # silently dead
                    self._complete_pending_locked()
                except journal.Fenced:
                    self._active.clear()
                    continue
                self._cv.notify_all()

    def _rewrite_host_file(self, evicted):
        """Drop THIS pass's evicted hosts from host_worker so the next
        barrier diff doesn't re-add them (atomic rewrite like the EC2
        manager, ``launch.py:218-224``).  Only the just-evicted hosts are
        filtered — an operator's pending re-add of a historically removed
        host must survive.  Caller holds the lock."""
        if not self.host_worker_file or \
                not os.path.exists(self.host_worker_file):
            return
        listed = _read_hosts(self.host_worker_file)
        kept = [h for h in listed if h not in set(evicted)]
        if kept != listed:
            tmp = self.host_worker_file + ".tmp"
            with open(tmp, "w") as f:
                f.write("\n".join(kept) + ("\n" if kept else ""))
            os.replace(tmp, self.host_worker_file)

    def _add_to_host_file(self, host: str) -> None:
        """Re-list a RECOVERED host in host_worker — eviction removed it,
        and without repair the very next barrier diff would re-remove the
        recovered worker.  Caller holds the lock."""
        if not self.host_worker_file or \
                not os.path.exists(self.host_worker_file):
            return
        listed = _read_hosts(self.host_worker_file)
        if host not in listed:
            with open(self.host_worker_file, "a") as f:
                f.write(host + "\n")

    def _complete_pending_locked(self):
        """After membership shrank, finish any collective now satisfied by
        the survivors.  Caller holds the lock."""
        st = self._state
        live = set(st.workers)
        # pending mc_barrier
        if st.barrier_epoch is not None and live and \
                st.barrier_arrived >= live:
            epoch = st.barrier_epoch
            result = self._apply_membership_change(epoch)
            self._apply("barrier_complete", epoch=epoch, result=result)
            self._obs.complete_span("mc_barrier.window", self._barrier_t0,
                                    {"epoch": epoch,
                                     "released_by": "survivors"})
            self._barrier_t0 = None
        # pending plain barrier
        if st.plain_arrived and live and st.plain_arrived >= live:
            self._apply("plain_release", gen=st.plain_gen + 1)
        # r19: a pending fleet checkpoint pinned to a worker set that just
        # lost a member can never gather its acks — abort it (the previous
        # committed checkpoint stays authoritative; the next cadence step
        # re-pins against the survivors)
        if st.ckpt_pending is not None and \
                not set(st.ckpt_pending["workers"]) <= live:
            step = st.ckpt_pending["step"]
            self._apply("ckpt_abort", step=step)
            self._ckpt_times.pop(step, None)
            self._obs.event("ckpt.abort",
                            {"step": step, "reason": "member_lost"})
        # pending allreduce rounds finish with the survivors
        self._dp.complete_with(live, ordered=st.workers)

    # ------------------------------------------------------------------
    # r19 coordinated fleet checkpointing + graceful drain
    # (docs/checkpoint.md; reference gap: callback.py:55-100 saves one
    # host's params locally and kvstore.py:551 cannot save dist-kvstore
    # optimizer state at all — no coordinated, resumable fleet snapshot)

    def _ckpt_intent(self, host: str, step: int, epoch: int) -> dict:
        """First worker to reach a checkpoint step opens the two-phase
        window; replicas of the same (step) intent are absorbed.  The
        journaled pending record pins the worker set whose acks commit."""
        faults.crash_point("sched.ckpt_intent", host=host)
        with self._cv:
            st = self._state
            com = st.ckpt_committed
            if com is not None and step <= int(com["step"]):
                return {"ok": False, "reason": "already_committed"}
            p = st.ckpt_pending
            if p is not None and int(p["step"]) == step:
                return {"ok": True, "seq": p["seq"]}
            if p is not None and step < int(p["step"]):
                return {"ok": False, "reason": "superseded"}
            if p is not None:
                # a newer intent supersedes a stuck window (a pinned
                # worker died before acking and was since re-admitted)
                old = int(p["step"])
                self._apply("ckpt_abort", step=old)
                self._ckpt_times.pop(old, None)
                self._obs.event("ckpt.abort",
                                {"step": old, "reason": "superseded"})
            self._apply("ckpt_intent", step=step, epoch=epoch,
                        seq=st.ckpt_seq + 1, workers=sorted(st.workers))
            self._ckpt_times[step] = {"t0": time.monotonic(), "acks": {}}
            self._obs.event("ckpt.intent",
                            {"step": step, "epoch": epoch,
                             "workers": sorted(st.workers)})
            return {"ok": True, "seq": st.ckpt_seq}

    def _ckpt_ack(self, host: str, step: int, path: str, sha256: str,
                  cursor: dict) -> dict:
        """Record one worker's durable save; the last pinned ack commits
        the manifest in the SAME journaled transition stream, so a torn
        window (crash before commit) leaves the previous committed
        checkpoint authoritative."""
        faults.crash_point("sched.ckpt_ack", host=host)
        with self._cv:
            st = self._state
            p = st.ckpt_pending
            if p is None or int(p["step"]) != step:
                com = st.ckpt_committed
                if com is not None and int(com["step"]) >= step:
                    return {"committed": True}  # retry after commit won
                return {"committed": False, "stale": True}
            if host not in p["acks"]:
                self._apply("ckpt_ack", step=step, host=host, path=path,
                            sha256=sha256, cursor=cursor)
                times = self._ckpt_times.get(step)
                if times is not None:
                    times["acks"][host] = time.monotonic()
                self._obs.event("ckpt.ack", {"host": host, "step": step})
            committed = False
            if set(p["workers"]) <= set(p["acks"]):
                # the torn-window crash site chaos kills at: every ack is
                # journaled but the commit is not — resume must fall back
                # to the previous committed manifest
                faults.crash_point("sched.ckpt_commit", host=host)
                manifest = {"step": int(p["step"]),
                            "epoch": int(p["epoch"]),
                            "seq": int(p["seq"]),
                            "workers": list(p["workers"]),
                            "files": {h: dict(a) for h, a in
                                      sorted(p["acks"].items())}}
                self._apply("ckpt_commit", step=step, manifest=manifest)
                committed = True
                times = self._ckpt_times.pop(step, None)
                attrs = {"step": step, "epoch": manifest["epoch"],
                         "workers": manifest["workers"]}
                if times is not None:
                    now = time.monotonic()
                    ats = sorted(times["acks"].values())
                    attrs["dur_ms"] = round((now - times["t0"]) * 1e3, 3)
                    attrs["spread_ms"] = round(
                        (ats[-1] - ats[0]) * 1e3, 3) if len(ats) > 1 \
                        else 0.0
                self._obs.event("ckpt.commit", attrs)
                if self._metrics is not None:
                    self._metrics.gauge("ckpt.committed_step",
                                        float(step))
                self._cv.notify_all()
            return {"committed": committed}

    def request_fleet_checkpoint(self) -> None:
        """Scheduler-drain entry (SIGTERM on ``scheduler_main``): flag
        every heartbeat response with ``ckpt_epoch_end`` so the fleet
        cuts a coordinated checkpoint at its next epoch boundary — the
        one point where every worker's ``state.step`` already agrees.
        The operator (or ``scheduler_main``) watches ``status.ckpt``
        for the commit before taking the process down."""
        self._ckpt_epoch_end = True
        self._obs.event("drain.requested", {"host": "scheduler"})

    def _drain(self, host: str) -> dict:
        """Graceful departure (SIGTERM → finish current step → drain):
        journal the drain marker, then remove the host through the same
        machinery eviction uses — survivors' in-flight collectives
        complete with the remaining contributions, and no recovery window
        opens for the departed worker."""
        with self._cv:
            st = self._state
            if host in st.draining or host not in st.workers:
                return {"ok": True, "already": True}
            self._apply("drain", host=host, seq=st.log_seq + 1)
            self._obs.event("drain.begin", {"host": host})
            self._apply("evict", host=host, seq=st.log_seq + 1)
            self._audit_locked("DRAINED", host)
            self._dp.hosts_removed({host})
            self._metrics_forget([host])
            self._dev_forget([host])
            self._rewrite_host_file([host])
            self._complete_pending_locked()
            self._cv.notify_all()
            self._obs.event("drain.complete", {"host": host})
            return {"ok": True}
    # ------------------------------------------------------------------

    def _mc_barrier(self, host: str, epoch: int, info: dict) -> dict:
        with self._cv:
            st = self._state
            if host in st.pending_recovery:
                # a recovering host parks at the NEXT barrier whatever
                # epoch it thinks it resumes at (its resume_epoch goes
                # stale while it bootstraps; van.cc:187-218 skips the
                # init barriers the same way)
                epoch = max(epoch, st.last_completed_epoch + 1)
            admitted = st.recovered_at.get(host)
            if admitted is not None:
                if epoch <= admitted:
                    # at-least-once retry of the admitting barrier (its
                    # response was lost): serve the SAME result
                    return self._result_for(host,
                                            st.barrier_result[admitted])
                # the host moved past its re-admission normally
                self._apply("recovered_clear", host=host)
            if epoch <= st.last_completed_epoch:
                # late arrival (a worker added during this epoch's barrier):
                # the change was already applied — return the result
                res = st.barrier_result.get(epoch)
                if res is None:
                    res = {"workers": list(st.workers), "removed": [],
                           "added": [], "epoch": epoch}
                return self._result_for(host, res)

            if st.barrier_epoch is None:
                # the barrier WINDOW span: first arrival -> release (the
                # job-level "how long does a membership change stall
                # training" number the reference never measured)
                self._barrier_t0 = self._obs.now()
            self._apply("barrier_arrive", host=host, epoch=epoch)
            faults.crash_point("sched.barrier_arrived", host=host,
                               epoch=epoch)

            if st.barrier_arrived >= set(st.workers):
                # everyone is here: apply at most one membership change
                arrived = len(st.barrier_arrived)
                result = self._apply_membership_change(epoch)
                self._apply("barrier_complete", epoch=epoch, result=result)
                self._obs.complete_span("mc_barrier.window",
                                        self._barrier_t0,
                                        {"epoch": epoch,
                                         "arrived": arrived})
                self._barrier_t0 = None
                self._cv.notify_all()
                return self._result_for(host, result)

            while epoch > st.last_completed_epoch:
                if self._stop.is_set():
                    raise RuntimeError("scheduler closed")
                if not self._cv.wait(timeout=300):
                    raise TimeoutError(f"mc_barrier epoch {epoch} stuck")
            return self._result_for(host, st.barrier_result[epoch])

    def _result_for(self, host: str, result: dict) -> dict:
        out = dict(result)
        out["you_are_removed"] = host in result["removed"]
        out["rank"] = result["workers"].index(host) \
            if host in result["workers"] else -1
        return out

    def _apply_membership_change(self, epoch: int) -> dict:
        """Diff host_worker vs live set; removals beat adds
        (``elastic_training.cc:91-157``).  Caller holds the lock.

        INVARIANT other layers rely on: one barrier applies removals OR
        additions, never both — so any change involving a removal always
        changes the worker count.  ``Module.fit``'s mesh-rebuild trigger
        (count comparison) and ``MeshManager.depart``'s collective
        matching both depend on this; if this ever applies mixed changes
        in one barrier, fit must switch to comparing the member LIST.

        HA: ``mc_begin`` is journaled before the diff and every applied
        remove/recover/add is its own journal record, so a leader killed
        in here leaves a replayable prefix; the successor resumes the
        SAME barrier in the SAME change direction (``mc_partial`` pins
        removals even if the remaining removable set is empty)."""
        t0 = self._obs.now()
        st = self._state
        if self._pre_change_hook is not None:
            try:
                self._pre_change_hook(epoch)
            except Exception:
                logger.exception("pre_change_hook failed")
        decision = None
        if self._policy is not None:
            # r14 policy decision, phase 1 (pre-diff): breach streaks
            # from the straggler board; chronic stragglers are dropped
            # from host_worker HERE so the normal diff below applies the
            # removal — exactly how the reference's EC2 lifecycle daemon
            # evicted instances (launch.py:218-224 rewrite, then diff).
            # The decision is journaled post-diff as ONE policy_decide
            # op; a leader killed between this rewrite and that op
            # leaves the rewritten file on the shared fs, so the
            # successor resumes the same removal direction.
            decision = self._policy.decide(
                epoch, list(st.workers), set(st.base),
                dict(st.policy_streaks), self._dp.straggler_scores())
            # evictions AND accepted scale-down proposals act through
            # the file + diff; scale-UP proposals stay advisory (the
            # engine cannot invent hosts — the launcher/operator adds
            # them to host_worker, reference launch.py:88-235)
            drop = list(decision.evict) + [
                p["host"] for p in decision.proposals
                if p.get("kind") == "scale_down" and "host" in p]
            if drop and not (self.host_worker_file and
                             os.path.exists(self.host_worker_file)):
                # no host file = no removal path through the diff:
                # demote the eviction to an advisory proposal (the
                # proposal-dedup in _policy_apply_locked keeps the
                # journal from re-recording it every epoch)
                import dataclasses as _dc
                decision = _dc.replace(
                    decision, evict=[],
                    proposals=list(decision.proposals) + [
                        {"kind": "evict", "host": h} for h in drop])
                drop = []
            if drop:
                self._rewrite_host_file(drop)
        desired = set(st.workers)
        if self.host_worker_file and os.path.exists(self.host_worker_file):
            desired = set(_read_hosts(self.host_worker_file))

        # the unqualified mid-change kill site (chaos scheduler_kill_mc):
        # all arrivals are journaled, the completion is not — the
        # successor must resume THIS barrier; the per-host calls below
        # land between individual membership ops
        faults.crash_point("sched.membership_change", epoch=epoch)
        self._apply("mc_begin", epoch=epoch)
        partial = st.mc_partial  # a predecessor's mid-change prefix
        current = set(st.workers)
        removable = (current - desired) - st.base  # base protected
        blocked = (current - desired) & st.base
        if blocked:
            logger.warning("refusing to remove base workers %s "
                           "(README.md:54-61)", sorted(blocked))
        if removable or partial["removed"]:
            # removals win; a pending recovery stays queued for the next
            # barrier (one change direction per barrier — the invariant,
            # which a crash-resumed removal barrier keeps too)
            for h in sorted(removable):
                faults.crash_point("sched.membership_change", host=h,
                                   epoch=epoch)
                self._apply("mc_remove", host=h, seq=st.log_seq + 1)
                self._audit_locked("REMOVED", h)
            self._dp.hosts_removed(removable)
            self._metrics_forget(removable)
            self._dev_forget(removable)
        else:
            # identity reissue first (van.cc:187-218): evicted-but-
            # restarted hosts come back AS THEMSELVES — base protection
            # restored, host file repaired, audit line RECOVERED (not
            # ADDED: operators must see crash re-entries distinctly).
            # Only hosts that ARRIVED at this barrier re-enter: they then
            # start the epoch in lockstep with the survivors (exact
            # sync); a still-bootstrapping host stays pending.
            for h in sorted(st.pending_recovery & st.barrier_arrived):
                faults.crash_point("sched.membership_change", host=h,
                                   epoch=epoch)
                self._apply("mc_recover", host=h, epoch=epoch,
                            seq=st.log_seq + 1)
                self._audit_locked("RECOVERED", h)
                self._add_to_host_file(h)
            # a pending-recovery host must re-enter ONLY through the
            # recovery loop above (as itself, at a barrier it arrived
            # at) — never through the plain ADD diff, which would grant
            # it a fresh-worker rank mid-bootstrap (r5 advisor race)
            to_add = sorted(desired - set(st.workers)
                            - st.pending_recovery)
            for h in to_add:
                faults.crash_point("sched.membership_change", host=h,
                                   epoch=epoch)
                self._apply("mc_add", host=h, seq=st.log_seq + 1)
                self._heartbeats[h] = time.time()  # grace until it registers
                self._audit_locked("ADDED", h)
                if self._launch_callback is not None:
                    # launch with EPOCH_BEGIN = this epoch (the barrier runs
                    # BEFORE epoch's batches; elastic_training.cc:26-62)
                    threading.Thread(target=self._launch_callback,
                                     args=(h, epoch), daemon=True).start()
        removed = list(partial["removed"])
        added = list(partial["added"])
        recovered = list(partial["recovered"])
        if removed or added or recovered:
            self._obs.complete_span(
                "membership_change", t0,
                {"epoch": epoch, "removed": removed, "added": added,
                 "recovered": recovered})
            logger.info("Epoch[%d] membership change: removed=%s added=%s "
                        "recovered=%s -> %s", epoch, removed, added,
                        recovered, st.workers)
        result = {"workers": list(st.workers), "removed": removed,
                  "added": added, "recovered": recovered, "epoch": epoch}
        if self._policy is not None and decision is not None:
            # phase 2 (post-diff): shares over the FINAL worker set ride
            # the barrier result (journaled inside barrier_complete, so
            # every arrival — and a failed-over successor — serves the
            # identical shares)
            result["policy"] = self._policy_apply_locked(epoch, decision)
        return result

    def _policy_apply_locked(self, epoch: int, decision) -> dict:
        """Apply one policy decision: share units over the post-diff
        rank-ordered workers, journaled as a single idempotent
        ``policy_decide`` op when anything changed (the WAL path DT010
        pins).  Returns the barrier-response payload.  Caller holds the
        lock."""
        st = self._state
        live = set(st.workers)
        streaks = {h: s for h, s in decision.streaks.items() if h in live}
        shares = self._policy.shares(list(st.workers), streaks)
        last_props = st.policy_log[-1].get("proposals", []) \
            if st.policy_log else []
        if (shares != st.policy_shares or streaks != st.policy_streaks
                or decision.evict
                or list(decision.proposals) != list(last_props)):
            self._apply("policy_decide", epoch=epoch,
                        seq=st.policy_seq + 1,
                        breached=list(decision.breached),
                        streaks=streaks, shares=shares,
                        lr_scale=decision.lr_scale,
                        evicted=list(decision.evict),
                        proposals=list(decision.proposals))
            self._obs.counter("policy.decisions")
            self._obs.event("policy.rebalance",
                            {"epoch": epoch, "seq": st.policy_seq,
                             "breached": list(decision.breached),
                             "shares": dict(shares)})
            for h in decision.evict:
                self._obs.event("policy.evict",
                                {"epoch": epoch, "host": h})
            # only NEW proposals become events (an unchanged pending
            # proposal re-journaled alongside a streak change must not
            # re-fire per epoch); demoted evictions are evictions, not
            # scale proposals — they go out under policy.evict
            for p in decision.proposals:
                if p in last_props:
                    continue
                if p.get("kind") == "evict":
                    self._obs.event("policy.evict",
                                    {"epoch": epoch, "host": p.get("host"),
                                     "advisory": True})
                else:
                    self._obs.event("policy.scale", {"epoch": epoch, **p})
            logger.info(
                "Epoch[%d] policy decision %d: breached=%s shares=%s "
                "evicted=%s proposals=%s", epoch, st.policy_seq,
                decision.breached, shares, decision.evict,
                decision.proposals)
        return {"shares": dict(st.policy_shares),
                "lr_scale": st.policy_lr_scale, "seq": st.policy_seq}

    def _policy_view_locked(self) -> dict:
        """Operator view of the policy state (``status`` / ``obs_dump``
        → dtop's policy section).  Caller holds the lock."""
        st = self._state
        return {"enabled": self._policy is not None,
                "shares": dict(st.policy_shares),
                "streaks": dict(st.policy_streaks),
                "lr_scale": st.policy_lr_scale,
                "seq": st.policy_seq,
                "log": list(st.policy_log[-32:])}

    def _audit_locked(self, action: str, host: str):
        """``SEQ ADDED|REMOVED IP TIME`` (``elastic_training.cc:108-126``).
        Caller holds the lock; the seq was already advanced by the
        journaled membership op (unique and ordered by construction)."""
        seq = self._state.log_seq
        # every audit line is also a timeline event: ADDED / REMOVED /
        # RECOVERED (covers operator removals, auto-evictions, and the
        # quick-restart eviction, which all funnel through here)
        self._obs.event(f"membership.{action}",
                        {"host": host, "seq": seq})
        if self._log_path:
            with open(self._log_path, "a") as f:
                f.write(f"{seq} {action} {host} "
                        f"{time.strftime('%Y-%m-%d_%H:%M:%S')}\n")

    # ------------------------------------------------------------------
    # plain barrier + exact-average allreduce (CPU-cluster data plane)
    # ------------------------------------------------------------------

    def _plain_barrier(self, host: str, seq: int = -1) -> dict:
        """Plain barrier; ``seq`` dedups at-least-once retries (a re-sent
        request whose generation already released returns immediately
        instead of polluting the next generation)."""
        with self._cv:
            st = self._state
            if seq >= 0 and host not in st.plain_arrived and \
                    st.plain_served.get(host) == seq:
                # retry of a RELEASED barrier (arrival was consumed by a
                # plain_release).  The host-still-arrived case must fall
                # through and park again: after a failover the successor
                # replays the arrival from the journal, and answering the
                # replay here would let this worker through a barrier the
                # rest of the fleet has not reached (docs/ha.md)
                return {}
            gen = st.plain_gen
            self._apply("plain_arrive", host=host, seq=seq)
            if st.plain_arrived >= set(st.workers):
                self._apply("plain_release", gen=gen + 1)
                self._cv.notify_all()
                return {}
            while st.plain_gen == gen:
                if self._stop.is_set():
                    raise RuntimeError("scheduler closed")
                if not self._cv.wait(timeout=300):
                    raise TimeoutError("barrier stuck")
            return {}

    # ------------------------------------------------------------------
    # range-server registry + data-plane introspection
    # ------------------------------------------------------------------

    def _server_list(self) -> list:
        """[[host, port], ...] ordered by server index — the worker's
        key-range → server assignment table (kvstore_dist.h:547-589)."""
        with self._servers_lock:
            return [list(self._servers[i])
                    for i in sorted(self._servers)]

    @property
    def _reduce(self):
        """Embedded plane's allreduce slots (tests introspect these)."""
        return self._dp._reduce

    @property
    def _async_store(self):
        """Embedded plane's dist_async master weights (test hook)."""
        return self._dp._async_store


def _read_hosts(path: str) -> List[str]:
    with open(path) as f:
        return [ln.strip() for ln in f if ln.strip() and
                not ln.strip().startswith("#")]
