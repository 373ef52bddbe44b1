"""Range server — one shard of the key-range-partitioned data plane.

The reference splits every big key across ALL R servers so aggregate
push/pull bandwidth scales with the server fleet
(``src/kvstore/kvstore_dist.h:547-589`` ``EncodeDefaultKey``: contiguous
key ranges, one per server; ``kvstore_dist_server.h`` holds each range's
master weights + updater).  A ``RangeServer`` is the dt_tpu equivalent:
a standalone process (or thread, in tests) serving the shared
:class:`~dt_tpu.elastic.dataplane.DataPlane` machinery for ITS slice of
every gradient/weight tensor.  Slicing happens client-side
(``WorkerClient``): dense tensors are split into R row ranges, sparse
pushes are partitioned by row id, and each slice travels to its server
concurrently — so R servers move R slices in parallel where the embedded
scheduler plane funneled everything through one socket.

Like the scheduler, a range server serves many requests per persistent
connection (``protocol.serve_connection``) — the workers' chunk windows
ride pooled channels, so the per-round cost is frames, not handshakes.

Control remains with the scheduler: a range server registers itself
(``register_server``) and mirrors the live worker membership from the
scheduler with a short-TTL cache — refreshed synchronously when an
unknown host contributes (a just-joined worker), and by a background
poll that completes pending rounds when membership shrinks (a dead
worker must not hang the survivors' allreduce).

Server count is fixed at launch (the reference's ``DMLC_NUM_SERVER``);
elasticity applies to workers, not servers.
"""

from __future__ import annotations

import logging
import os
import random
import socket
import threading
import time
from typing import List, Optional, Set

from dt_tpu import config
from dt_tpu.elastic import commands, faults, protocol
from dt_tpu.elastic.dataplane import DataPlane
from dt_tpu.obs import trace as obs_trace

logger = logging.getLogger("dt_tpu.elastic")
_drop_rng = random.Random(0x5EED)  # deterministic fault injection

#: responses never token-cached (read-only / own (host, seq) dedup);
#: derived view over the r17 PROTOCOL_REGISTRY (elastic/commands.py),
#: like the scheduler's — dtlint DT013 pins it to handler reality
_TOKEN_EXEMPT = commands.token_exempt("range_server")


class RangeServer:
    def __init__(self, scheduler_host: str, scheduler_port: int,
                 index: int, port: int = 0,
                 advertise_host: Optional[str] = None,
                 membership_ttl_s: float = 1.0,
                 poll_interval_s: float = 1.0):
        self.index = int(index)
        self.sched_addr = (scheduler_host, scheduler_port)
        self._members: List[str] = []  # guarded-by: _members_lock
        self._members_ts = 0.0  # guarded-by: _members_lock
        self._members_lock = threading.Lock()
        self._ttl = membership_ttl_s
        # observability (dt_tpu/obs): per-instance tracer; the old ad-hoc
        # _bytes_in/_rounds ints (load-balance evidence: with R servers
        # each should carry ~1/R of the bytes) are obs counters now
        self._obs = obs_trace.Tracer(name=f"range-server-{self.index}")
        # confirm_fn forces a synchronous scheduler read right before a
        # round completes, closing the stale-cache join race (one extra
        # RTT per completing round; contributions are already seconds
        # apart on this plane)
        self._dp = DataPlane(expected_fn=self._expected,
                             confirm_fn=self._refresh_members,
                             tracer=self._obs)
        self._tokens = protocol.TokenCache(
            ttl_s=float(config.env("DT_CTRL_TOKEN_TTL_S")))

        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((protocol.bind_interface(), port))
        self._sock.listen(128)
        self.port = self._sock.getsockname()[1]
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()
        # register with the scheduler so workers discover this shard
        host = advertise_host or protocol.advertise_host()
        protocol.request(scheduler_host, scheduler_port,
                         {"cmd": "register_server", "index": self.index,
                          "host": host, "port": self.port})
        # membership poll: completes pending rounds when workers die
        self._poll_thread = threading.Thread(
            target=self._poll_loop, args=(poll_interval_s,), daemon=True)
        self._poll_thread.start()
        logger.info("range server %d listening on :%d", self.index,
                    self.port)

    # ------------------------------------------------------------------
    # membership mirror
    # ------------------------------------------------------------------

    def _refresh_members(self) -> List[str]:
        try:
            resp = protocol.request(self.sched_addr[0], self.sched_addr[1],
                                    {"cmd": "membership"}, timeout=10)
            with self._members_lock:
                self._members = list(resp["workers"])
                self._members_ts = time.time()
        except (OSError, KeyError):
            pass  # scheduler briefly unreachable: serve the cached view
        with self._members_lock:
            return list(self._members)

    def _expected(self) -> List[str]:
        with self._members_lock:
            fresh = time.time() - self._members_ts < self._ttl
            if fresh:
                return list(self._members)
        return self._refresh_members()

    def _poll_loop(self, interval: float):
        known: Set[str] = set()
        while not self._stop.wait(interval):
            live = set(self._refresh_members())
            if not live:
                continue
            removed = known - live
            if removed:
                self._dp.hosts_removed(removed)
            known = set(live)
            # complete pending rounds the survivors satisfy EVERY tick:
            # a removal may have been absorbed into the cache by an
            # inline _dispatch/_expected refresh between polls, so a
            # shrink comparison against the cache would miss it and the
            # parked handlers would sit until the 300s round timeout
            self._dp.complete_with(live, ordered=sorted(live))

    # ------------------------------------------------------------------
    # server plumbing (same shape as the scheduler's)
    # ------------------------------------------------------------------

    def _serve(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            threading.Thread(target=self._handle_conn, args=(conn,),
                             daemon=True).start()

    def _handle_conn(self, conn: socket.socket):
        protocol.serve_connection(conn, self._handle_one)

    def _handle_one(self, msg: dict) -> Optional[dict]:
        """The r13 causal-tracing wrapper (shared with the scheduler —
        :func:`protocol.traced_handle`): a request carrying trace
        context gets an ``rpc.<cmd>`` handler span on THIS shard's
        tracer, linked to the client's wire.request span.  Range-server
        tracers are per-instance and not merged into the scheduler's
        job dump (separate processes) — the spans serve in-process
        tests."""
        return protocol.traced_handle(self._obs, msg, self._handle_inner)

    def _handle_inner(self, msg: dict) -> Optional[dict]:
        """One request on a persistent connection (``None`` = drop)."""
        # the same DT_DROP_MSG transport fuzz as the scheduler —
        # the sharded plane must survive at-least-once retries too
        drop = os.environ.get("DT_DROP_MSG")
        if drop and _drop_rng.random() * 100 < float(drop):
            logger.debug("DT_DROP_MSG: dropping %s", msg.get("cmd"))
            return None
        plan = faults.active_plan()
        if plan is not None and \
                not plan.on_recv(msg.get("cmd"), msg.get("host")):
            return None
        token = msg.get("token")
        if token is not None:
            cached = self._tokens.get(token)
            if cached is not None:
                self._obs.counter("tokens.dedup_hits")
                return cached
        try:
            resp = self._dispatch(msg)
        except Exception as e:
            logger.exception("range server %d handler error", self.index)
            return {"error": repr(e)}
        if token is not None and "error" not in resp and \
                msg.get("cmd") not in _TOKEN_EXEMPT:
            self._tokens.put(token, resp)
        return resp

    def _dispatch(self, msg: dict) -> dict:
        cmd = msg.get("cmd")
        host = msg.get("host")
        if host is not None:
            with self._members_lock:
                known = host in self._members
            if not known:
                # a contributor we don't know yet: a just-joined worker —
                # force-refresh so its round's expected set includes it.
                # (No dedup-cache purge here: an evicted-but-alive host's
                # retry must still be served its cached result, or the
                # double-apply window the (host,seq) dedup closes
                # re-opens.  Sequence resets are explicit: host_reset.)
                self._refresh_members()
        if cmd == "host_reset":
            # a (re)registering worker starts fresh sequences; the client
            # broadcasts this on register/refresh (the scheduler purges
            # its own plane in _register)
            self._dp.host_registered(msg["host"])
            return {}
        if cmd in DataPlane.CMDS:
            val = msg.get("value")
            size = 0
            if hasattr(val, "nbytes"):
                size = int(val.nbytes)
            elif isinstance(val, dict):
                size = sum(int(v.nbytes) for v in val.values()
                           if hasattr(v, "nbytes"))
            self._obs.counter("data.bytes_in", size)
            self._obs.counter("data.requests")
            out = self._dp.dispatch(msg)
            if out is not None:
                return out
        if cmd == "ping":
            return {"index": self.index}
        if cmd == "shutdown":
            self.close()
            return {}
        return {"error": f"unknown cmd {cmd!r} (range server)"}

    def close(self):
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass


def main():  # pragma: no cover - exercised via launcher integration test
    """CLI entry: ``python -m dt_tpu.elastic.range_server`` with the
    launcher env contract (``DMLC_PS_ROOT_URI/PORT``, ``DT_SERVER_ID``)."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--scheduler-host",
                    default=os.environ.get("DMLC_PS_ROOT_URI"))
    ap.add_argument("--scheduler-port", type=int,
                    default=int(os.environ.get("DMLC_PS_ROOT_PORT", "0")))
    ap.add_argument("--index", type=int,
                    default=int(os.environ.get("DT_SERVER_ID", "0")))
    ap.add_argument("--port", type=int, default=0)
    args = ap.parse_args()
    srv = RangeServer(args.scheduler_host, args.scheduler_port,
                      args.index, port=args.port)
    try:
        while not srv._stop.wait(1.0):
            pass
    except KeyboardInterrupt:
        srv.close()


if __name__ == "__main__":  # pragma: no cover
    main()
