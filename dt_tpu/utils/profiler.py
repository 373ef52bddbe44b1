"""Profiling with the reference's API surface, on jax.profiler.

Reference: ``python/mxnet/profiler.py`` (set_config/set_state/pause/resume/
dump) over the C++ scoped profiler (``src/profiler/profiler.h:256``), which
emits chrome://tracing JSON.  Here ``jax.profiler`` captures XLA/TPU traces
viewable in Perfetto/TensorBoard — strictly richer than the reference's op
ring buffers (includes compiled-kernel timelines and HBM usage).

The reference's distributed twist — rank 0 remotely driving the profiler on
all *server* processes via kvstore commands (``KVStoreServerProfilerCommand``,
``kvstore_dist.h:102-110``, ``kvstore_dist_server.h:275-322``) — maps to
:func:`set_state_all` / :func:`dump_all`, which broadcast profiler control to
every worker host through the elastic scheduler's control channel; each host
prefixes output with ``rank<N>_`` exactly like the server did
(``kvstore_dist_server.h:307``).
"""

from __future__ import annotations

import os
import threading
from typing import Optional

import jax

_config = {"filename": "profile_output", "aggregate_stats": False}
_running = False
_active_outdir: Optional[str] = None  # where the live/last trace went
#                                       (rank-prefixed in distributed runs)
# start/stop may race between the caller's thread and the elastic client's
# heartbeat thread applying a remote command (dump_all stops locally AND
# broadcasts); transitions are serialized and idempotent under this lock
_state_lock = threading.Lock()


def set_config(filename: str = "profile_output", profile_all: bool = True,
               aggregate_stats: bool = False, **_ignored) -> None:
    """Reference ``mx.profiler.set_config`` — ``filename`` becomes the trace
    output directory."""
    _config["filename"] = filename
    _config["aggregate_stats"] = aggregate_stats


def set_state(state: str = "stop", rank: Optional[int] = None) -> None:
    """Reference ``mx.profiler.set_state('run'|'stop')``."""
    global _running
    if state not in ("run", "stop"):
        raise ValueError(f"state must be run|stop, got {state!r}")
    outdir = _config["filename"]
    if rank is not None:
        outdir = os.path.join(os.path.dirname(outdir) or ".",
                              f"rank{rank}_" + os.path.basename(outdir))
    global _active_outdir
    with _state_lock:
        if state == "run" and not _running:
            jax.profiler.start_trace(outdir)
            _active_outdir = outdir
            _running = True
        elif state == "stop" and _running:
            jax.profiler.stop_trace()
            _running = False


def pause() -> None:
    """Reference ``mx.profiler.pause`` — jax traces can't pause mid-flight;
    mapped to stop (resume starts a fresh trace)."""
    set_state("stop")


def resume() -> None:
    set_state("run")


def dump(finished: bool = True) -> str:
    """Reference ``mx.profiler.dump`` — stops the trace; returns the dir
    the trace was actually written to (rank-prefixed in distributed runs),
    Perfetto-loadable."""
    set_state("stop")
    return _active_outdir or _config["filename"]


class trace:
    """Context manager: ``with profiler.trace("/tmp/tr"): step()``."""

    def __init__(self, outdir: str):
        self.outdir = outdir

    def __enter__(self):
        set_config(filename=self.outdir)
        set_state("run")
        return self

    def __exit__(self, *a):
        set_state("stop")


def annotate(name: str, step_num: Optional[int] = None):
    """Named region in the trace (reference scoped ``ProfileTask``/
    ``ProfileOperator``).  With ``step_num`` the region is one step of a
    loop (``StepTraceAnnotation``): the profiler's tools group the device
    operations under it.  The training loop's spans (``obs/trace.py``)
    open their annotations through here."""
    if step_num is not None:
        return jax.profiler.StepTraceAnnotation(name, step_num=step_num)
    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# multi-host control (the server-profiling feature)
# ---------------------------------------------------------------------------
#
# Protocol (reference ``KVStoreServerProfilerCommand``,
# ``kvstore_dist.h:102-110`` -> ``kvstore_dist_server.h:275-322``): any
# worker posts a ``profile`` command to the elastic scheduler; the
# scheduler buffers it with a sequence number; EVERY worker's heartbeat
# returns unseen commands, which ``WorkerClient._apply_profile_cmd``
# applies locally through :func:`apply_remote` — output paths get a
# ``rank<N>_`` prefix exactly like the reference's server profiles.


def apply_remote(action: str, params: dict, rank: int) -> None:
    """Apply one remote profiler command on this worker (called from the
    elastic client's heartbeat thread)."""
    if action == "set_config":
        set_config(**params)
    elif action == "set_state":
        set_state(params.get("state", "stop"), rank=rank)
    elif action == "pause":
        pause()
    elif action == "resume":
        set_state("run", rank=rank)
    elif action == "dump":
        dump()
    else:
        raise ValueError(f"unknown remote profiler action {action!r}")


def set_config_all(kv, **params) -> None:
    """Reference ``kv.set_server_profiler_config``: broadcast the profiler
    config to every worker via the scheduler; local-only without a
    controller."""
    ctrl = getattr(kv, "_controller", None)
    if ctrl is None or not hasattr(ctrl, "profile_command"):
        set_config(**params)
        return
    ctrl.profile_command("set_config", params)


def set_state_all(kv, state: str) -> None:
    """Reference ``kv.set_server_profiler_state``: broadcast run/stop to
    every worker host (each applies with its rank prefix at its next
    heartbeat — including the caller)."""
    ctrl = getattr(kv, "_controller", None)
    if ctrl is None or not hasattr(ctrl, "profile_command"):
        set_state(state)
        return
    ctrl.profile_command("set_state", {"state": state})


def dump_all(kv) -> str:
    """Broadcast a dump (stop+flush) to every worker; returns the LOCAL
    trace dir (each host writes its own rank-prefixed directory)."""
    ctrl = getattr(kv, "_controller", None)
    if ctrl is not None and hasattr(ctrl, "profile_command"):
        ctrl.profile_command("dump", {})
    return dump()
