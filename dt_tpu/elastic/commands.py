"""Wire-command registry — the single declaration point for every
elastic control-plane command.

The reference's control vocabulary was an unchecked C++ enum
(``ps-lite/include/ps/internal/message.h:123`` ``Control::Command`` —
the fork grew ``ADD_NODE``-family values in ``elastic_training.cc`` with
nothing auditing senders against handlers); dt_tpu's commands are
stringly-typed dicts dispatched in ``scheduler.py``/``range_server.py``,
which is one typo away from a silently-dead handler arm.  This registry
is the machine-checked contract, mirroring ``dt_tpu.config.ENV_REGISTRY``
(env vars) and ``dt_tpu.obs.names.NAME_REGISTRY`` (obs names):

- dtlint rule **DT012** cross-checks every row against the extracted
  wire reality (send sites vs handler arms, both directions) and against
  the generated catalog in ``docs/protocol_commands.md``;
- rule **DT013** holds the *idempotency class* declared here to the
  statically-inferred handler behavior and to the token-cache exemption
  sets — the class of bug behind the PR-6 "re-applied async_push
  gradient" fix, caught before it ships this time;
- the servers' ``_TOKEN_EXEMPT`` / ``_PASSIVE_CMDS`` sets are **derived
  views** over this table (:func:`token_exempt`, :func:`passive_cmds`),
  so the registry cannot drift from the running dispatch gates.

Idempotency classes (the DT013 vocabulary):

- ``read_only``  — the handler must not mutate control/data state; the
  response is never token-cached (caching reads would churn the bounded
  cache out of the tokens the dedup exists to protect).
- ``idempotent`` — the handler mutates, but an at-least-once replay is
  safe through the command's OWN machinery (record ``rseq``/sample-seq
  dedup, round ``gen``, per-``(host, seq)`` served caches, idempotent
  close).  May be token-exempt.
- ``once``       — the handler mutates with no self-dedup: the response
  MUST be token-cached (``protocol.request`` reliable mode) so a replay
  whose first dispatch completed is served the same answer instead of
  re-dispatching.  Never token-exempt.

Flags: ``exempt`` (not token-cached), ``passive`` (served by a warm
standby / fenced ex-leader), ``external`` (the sender lives outside the
linted tree — operator tooling / tests — so DT012's dead-arm check
admits it; the doc must name the consumer).

Stdlib-only and AST-parseable (a plain dict literal): dtlint loads it
without importing, like the other two registries.  Regenerate the
human-readable catalog with::

    python -m dt_tpu.elastic.commands > docs/protocol_commands.md
"""

from __future__ import annotations

from typing import FrozenSet, Mapping, Tuple

#: cmd -> (roles, idempotency, flags, doc).  ``roles`` / ``flags`` are
#: ``|``-separated; roles name the dispatching server(s).
PROTOCOL_REGISTRY: Mapping[str, Tuple[str, str, str, str]] = {
    # -- membership / control (scheduler) ----------------------------------
    "register": (
        "scheduler", "once", "",
        "worker (re)registration: rank + live set + fence (van.cc:519-539); "
        "mutates membership via journaled ops, no self-dedup"),
    "heartbeat": (
        "scheduler", "idempotent", "exempt",
        "liveness + piggybacked obs/metrics batches (rseq/sample-seq "
        "dedup'd) + profiler-command sync; superseded by the next beat"),
    "mc_barrier": (
        "scheduler", "once", "",
        "membership-change barrier: released when every live worker "
        "arrived and one change was applied (elastic_training.cc:91-126)"),
    "barrier": (
        "scheduler", "once", "",
        "plain epoch barrier; per-host seq dedups released generations"),
    "publish_snapshot": (
        "scheduler", "once", "",
        "publish the parameter snapshot joiners bootstrap from "
        "(module.py:552-571)"),
    "fetch_snapshot": (
        "scheduler", "idempotent", "exempt",
        "fetch the snapshot blob; the only mutation is the sidecar "
        "marker-resolution memo (same bytes the journal references)"),
    "num_dead": (
        "scheduler", "read_only", "exempt",
        "count workers silent past timeout_s (postoffice.cc:410-429)"),
    "membership": (
        "scheduler", "read_only", "exempt",
        "live worker list (range servers mirror it on a short TTL)"),
    "servers": (
        "scheduler", "read_only", "exempt",
        "range-server address table, index order (kvstore_dist.h:547-589)"),
    "register_server": (
        "scheduler", "idempotent", "",
        "range-server shard registration; re-registering index i "
        "overwrites with the identical (host, port)"),
    "profile": (
        "scheduler", "idempotent", "",
        "rank-0-drives-all profiler command post; (host, post_seq) "
        "dedups replays (kvstore_dist_server.h:275-322)"),
    "profile_capture": (
        "scheduler", "idempotent", "",
        "queue a bounded N-step jax.profiler capture on ONE worker "
        "(r18 device plane): delivered on the target's next heartbeat, "
        "trace lands in DT_BLACKBOX_DIR + manifest.jsonl; "
        "(host, post_seq) dedups replays like 'profile'"),
    # -- job survivability plane (r19 — fleet checkpoint / drain / resume,
    # docs/checkpoint.md) ---------------------------------------------------
    "ckpt_intent": (
        "scheduler", "idempotent", "",
        "phase 1 of the coordinated fleet checkpoint: pin (step, worker "
        "set) via a journaled ckpt_intent op; per-step dedup makes every "
        "replay/duplicate a no-op (first caller wins, the rest adopt)"),
    "ckpt_ack": (
        "scheduler", "idempotent", "",
        "one worker's async save landed (path + sha256 + data-iterator "
        "cursor); per-(host, step) journaled dedup, the last ack in the "
        "pinned set triggers the journaled ckpt_commit manifest"),
    "ckpt_manifest": (
        "scheduler", "read_only", "exempt|passive",
        "the newest COMMITTED checkpoint manifest + the pending-intent "
        "view (resume bootstrap, dtop timeline, chaos gates)"),
    "drain": (
        "scheduler", "idempotent", "",
        "graceful-drain notice (SIGTERM preemption): journaled drain op "
        "drops base protection and the eviction machinery removes the "
        "host; draining an already-draining/absent host is a no-op"),
    "shutdown": (
        "scheduler|range_server|replica", "idempotent", "passive|external",
        "remote shutdown of the serving process (idempotent close); "
        "sent by operator tooling and the test harness, not by workers"),
    # -- observability / health (scheduler) --------------------------------
    "obs_push": (
        "scheduler", "idempotent", "exempt|passive",
        "synchronous span/metrics flush (worker close or crash hook); "
        "record rseq + sample-seq dedup make replays no-ops"),
    "obs_dump": (
        "scheduler", "read_only", "exempt|passive",
        "the merged job timeline + metrics/health sections (dtop, "
        "chaos --trace)"),
    "health": (
        "scheduler", "read_only", "exempt|passive",
        "the r15 training-health view: SLO state + gauges (dtop "
        "--health, the serving plane)"),
    "status": (
        "scheduler", "read_only", "exempt|passive",
        "scheduler identity/progress snapshot: leadership, incarnation, "
        "workers, policy view (dtop --status)"),
    "blackbox_index": (
        "scheduler", "read_only", "exempt|passive",
        "r16 flight-recorder manifest + fleet-hang suspect view (dtop "
        "--postmortem discovery, chaos gates)"),
    "ha_round": (
        "scheduler", "idempotent", "exempt|passive",
        "primary->standby completed-round replication; slot gen ordering "
        "makes duplicate/stale replicas no-ops (docs/ha.md)"),
    # -- data plane (scheduler embedded plane + range servers) -------------
    "allreduce": (
        "scheduler|range_server", "idempotent", "exempt",
        "exact-average round contribution; per-(host, seq) served cache "
        "dedups replays (resender.h ACK-dedup role)"),
    "set_optimizer": (
        "scheduler|range_server", "idempotent", "",
        "install the server-side updater from a spec; identical specs "
        "are no-ops (kvstore.py:451-498)"),
    "async_init": (
        "scheduler|range_server", "idempotent", "exempt",
        "init-or-get master weights: first writer seeds, later inits "
        "return the live copy (kvstore_local.h:95-110)"),
    "async_push": (
        "scheduler|range_server", "idempotent", "exempt",
        "dist_async gradient push; (host, key, seq) dedup keeps a "
        "momentum update from applying twice (the PR-6 bug class)"),
    "async_pull_rows": (
        "scheduler|range_server", "read_only", "exempt",
        "row-sparse pull of the requested rows (kvstore_dist.h:317-376)"),
    "async_stats": (
        "scheduler|range_server", "read_only", "exempt",
        "dist_async staleness metrics (VERDICT r4 weak 7)"),
    # -- serving plane (r21 — dt_tpu/serve: inference gateway replicas +
    # scheduler-side serve control; docs/serving.md) ------------------------
    "infer": (
        "replica", "once", "",
        "one inference request (rows ride the pooled zero-copy wire into "
        "the gateway's dynamic batcher); mutates queue/latency state with "
        "no self-dedup, so the response is token-cached — a retry that "
        "crosses a scheduler failover is served the SAME answer"),
    "infer_result": (
        "replica", "read_only", "exempt",
        "poll a queued async infer (wait=false) by rid: done/not-yet view "
        "over the gateway's bounded result window"),
    "serve_stats": (
        "replica", "read_only", "exempt",
        "gateway introspection: queue depth, shed/served counters, "
        "latency percentiles, weights step (serve_bench + dtop + chaos "
        "read gates from here)"),
    "weight_refresh": (
        "replica", "idempotent", "exempt",
        "rolling-refresh drain-then-swap: adopt the committed fleet-"
        "checkpoint manifest step (r19 ckpt_manifest); keyed by step — "
        "re-applying the step already being served is a no-op"),
    "serve_register": (
        "scheduler", "idempotent", "exempt",
        "serving-replica registration: host + gateway addr into the "
        "scheduler's in-memory serve table (re-registering overwrites "
        "with identical state; replicas re-register after a failover "
        "exactly like worker reattach)"),
    "serve_heartbeat": (
        "scheduler", "idempotent", "exempt",
        "replica liveness + live serve gauges (queue_depth/p99/qps/shed) "
        "feeding the r14 policy engine's serving mode; superseded by the "
        "next beat, response carries the drain flag on scale-down"),
    "serve_endpoints": (
        "scheduler", "read_only", "exempt",
        "the live serving view: replica addrs + gauges + the serving "
        "policy decision log (loadgen discovery, rolling refresher, "
        "serve_bench gates)"),
    # -- range-server local ------------------------------------------------
    "host_reset": (
        "range_server", "idempotent", "",
        "a (re)registered worker starts fresh sequences: purge its "
        "retry-dedup entries (idempotent purge; the scheduler does the "
        "same in _register)"),
    "ping": (
        "range_server", "read_only", "exempt|external",
        "shard liveness probe; sent by tests and operator tooling"),
}

_ROLES = frozenset({"scheduler", "range_server", "replica"})
_CLASSES = frozenset({"read_only", "idempotent", "once"})
_FLAGS = frozenset({"exempt", "passive", "external"})


def _split(s: str) -> FrozenSet[str]:
    return frozenset(t for t in s.split("|") if t)


def _validate() -> None:
    """Registry self-consistency, enforced at import (the AST consumers
    re-derive the same invariants statically in rule DT013)."""
    for cmd, (roles, idem, flags, doc) in PROTOCOL_REGISTRY.items():
        r, f = _split(roles), _split(flags)
        if not r or not r <= _ROLES:
            raise ValueError(f"{cmd}: bad roles {roles!r}")
        if idem not in _CLASSES:
            raise ValueError(f"{cmd}: bad idempotency class {idem!r}")
        if not f <= _FLAGS:
            raise ValueError(f"{cmd}: bad flags {flags!r}")
        if idem == "once" and "exempt" in f:
            raise ValueError(
                f"{cmd}: a 'once' command must be token-cached — "
                f"exempting it re-opens the at-least-once replay window")
        if idem == "read_only" and "exempt" not in f:
            raise ValueError(
                f"{cmd}: a read-only command must be token-exempt "
                f"(caching reads churns the bounded token cache)")
        if "passive" in f and "scheduler" not in r:
            raise ValueError(f"{cmd}: passive commands are a scheduler "
                             f"leadership-gate concept")
        if not doc:
            raise ValueError(f"{cmd}: doc required")


_validate()


def token_exempt(role: str) -> FrozenSet[str]:
    """Commands ``role`` serves whose responses are NOT token-cached —
    the derived view behind ``scheduler._TOKEN_EXEMPT`` /
    ``range_server._TOKEN_EXEMPT`` (read-only, or replay-safe through
    their own dedup machinery; caching snapshot blobs or high-rate
    heartbeats would churn the bounded cache out of the very tokens the
    dedup exists to protect)."""
    if role not in _ROLES:
        raise ValueError(f"unknown role {role!r}")
    return frozenset(
        cmd for cmd, (roles, _idem, flags, _doc)
        in PROTOCOL_REGISTRY.items()
        if role in _split(roles) and "exempt" in _split(flags))


def passive_cmds() -> FrozenSet[str]:
    """Commands a PASSIVE scheduler instance (warm standby / fenced
    ex-leader) still serves — everything else is refused ``not_leader``
    so clients rotate to the live leader (docs/ha.md)."""
    return frozenset(
        cmd for cmd, (_roles, _idem, flags, _doc)
        in PROTOCOL_REGISTRY.items() if "passive" in _split(flags))


def render_catalog() -> str:
    """The ``docs/protocol_commands.md`` catalog table, generated from
    the registry (DT012 fails the lint when the committed file drifts)."""
    lines = [
        "# Wire-command catalog",
        "",
        "GENERATED from `dt_tpu/elastic/commands.py` — edit the registry",
        "and regenerate with:",
        "",
        "```",
        "python -m dt_tpu.elastic.commands > docs/protocol_commands.md",
        "```",
        "",
        "dtlint rule DT012 cross-checks this table against the registry "
        "and the",
        "registry against the extracted send sites / handler arms; DT013 "
        "holds the",
        "idempotency class to the token-cache exemption sets (which are "
        "derived",
        "views over the same registry).  Reference gap: ps-lite's "
        "`Control::Command`",
        "enum (`message.h:123`) had no sender/handler audit at all.",
        "",
        "| command | handled by | idempotency | token cache | passive "
        "| notes |",
        "|---|---|---|---|---|---|",
    ]
    for cmd in sorted(PROTOCOL_REGISTRY):
        roles, idem, flags, doc = PROTOCOL_REGISTRY[cmd]
        f = _split(flags)
        cache = "exempt" if "exempt" in f else "cached"
        passive = "yes" if "passive" in f else ""
        note = doc + (" [external senders]" if "external" in f else "")
        lines.append(
            f"| `{cmd}` | {', '.join(sorted(_split(roles)))} | {idem} "
            f"| {cache} | {passive} | {note} |")
    lines.append("")
    return "\n".join(lines)


if __name__ == "__main__":  # pragma: no cover - trivial generator
    print(render_catalog(), end="")
