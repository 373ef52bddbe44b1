"""Device-plane observability — the XLA compile observatory, HBM/memory
accounting, and OOM/recompile forensics (r18).

The obs stack up to r17 sees the host and the wire — causal spans
(``dt_tpu/obs/trace.py``), health SLOs (``metrics.py``), crash bundles
(``blackbox.py``) — but the compute plane itself was a black box:
"compute" in the critical-path split is just step-minus-blocking-spans,
a hang bundle could not tell a JIT-compile stall from a real wedge, and
there was no compile/memory evidence to act on.  The reference was
even blinder: its profiler needed a live process and saw op timelines
only (``src/profiler/profiler.h:256``,
``kvstore_dist_server.h:275-322``), and its memory story was an offline
static table (``example/memcost``).  Elastic resizing makes the gap
acute: every membership change risks a silent recompile storm and a
transient HBM spike — exactly the per-device costs the resizing loop
must keep bounded (Lin et al., arXiv:1904.12043), and compile-time
visibility is the precondition for compiler-side tier work (TVM,
arXiv:1802.04799).

Four pieces, all hard-off unless ``DT_DEVICE_OBS=1`` (the same
zero-retention + <1.5x off-path contract as the trace/metrics/blackbox
planes; ``tests/test_device_obs.py`` holds the guards):

- **Compile observatory** — :func:`instrument` wraps a jitted step
  (``Module._build_steps``, ``Trainer._build``, ``Predictor``): the
  first call per abstract signature runs the AOT ``lower().compile()``
  path inside a named ``compile.<what>`` span (so the blackbox
  open-span table — and therefore the hang watchdog — can SEE a
  compile in progress) and captures XLA's own ``memory_analysis()``
  (the static estimate, taken at every real compile).  What the build
  took and whether the persistent cache served it are not measured
  here: they are read from the build account's rows
  (``obs/trace.py``, written by listeners of ``jax.monitoring`` with
  every gate off), so the span's ``trace_ms``, ``lower_ms`` and
  ``backend_ms`` and its ``cache`` are jax's own words for this build.
  Off, :func:`instrument` returns the function UNCHANGED.
- **Recompile-cause ledger** — a second compile of the same ``what``
  diffs the new abstract signature against the previous one and emits a
  ``compile.recompile`` event naming the delta (``shape`` / ``dtype`` /
  ``mesh`` / ``donate`` / ``metric`` / ``nargs``, or ``rebuild`` when the
  signature is identical — a fresh ``jax.jit`` object after an elastic
  rebuild, the case the persistent cache exists for).  The chaos straggler drill
  gates ZERO recompiles across share-only policy rebalances on this.
- **Memory plane** — :func:`sample_into` sets per-device
  ``device.hbm_*`` gauges from ``jax.Device.memory_stats()`` with an
  RSS fallback on CPU, plus :class:`~dt_tpu.training.overlap.
  StagingPool` occupancy; :func:`live_buffer_census` groups
  ``jax.live_arrays()`` by shape/dtype with provenance tags from
  registered shape sets (params/opt-state).
- **Forensics** — :func:`maybe_oom_bundle` writes a blackbox bundle
  carrying the live-buffer census before a RESOURCE_EXHAUSTED death;
  the ``device`` blackbox state provider stamps every bundle with the
  compile ledger + memory view; :func:`arm_capture`/:func:`capture_tick`
  run a bounded N-step ``jax.profiler`` trace on demand (the
  ``profile_capture`` wire command, ``dt_tpu/elastic/commands.py``),
  landing it in ``DT_BLACKBOX_DIR`` + ``manifest.jsonl``.

jax-optional throughout: every jax touch is lazy and guarded, so
jax-free tools (``tools/dtop.py``) import this module through the path
shim.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional

from dt_tpu import config
from dt_tpu.obs import trace as obs_trace

# ---------------------------------------------------------------------------
# process-wide enable gate (DT_DEVICE_OBS, overridable in-process)
# ---------------------------------------------------------------------------

_ENABLED_OVERRIDE: Optional[bool] = None
_ENV_ENABLED: Optional[bool] = None


def enabled() -> bool:
    """Whether the device plane is armed for this process
    (``DT_DEVICE_OBS=1`` or an explicit :func:`set_enabled`).  One
    cached-bool check on the fast path."""
    if _ENABLED_OVERRIDE is not None:
        return _ENABLED_OVERRIDE
    global _ENV_ENABLED
    if _ENV_ENABLED is None:
        _ENV_ENABLED = config.env("DT_DEVICE_OBS").strip().lower() \
            in ("1", "true")
    return _ENV_ENABLED


def set_enabled(on: Optional[bool]) -> None:
    """Process-local override (``None`` = follow the env var again)."""
    global _ENABLED_OVERRIDE, _ENV_ENABLED
    _ENABLED_OVERRIDE = on
    if on is None:
        _ENV_ENABLED = None


#: cap on distinct abstract signatures one instrumented fn tracks (a
#: shape-churning caller falls back to the plain jit path beyond it —
#: jit's own cache faces the same churn either way)
_MAX_SIGS = 32
#: bounded recompile-cause ledger entries kept per process
_LEDGER_MAX = 128
#: live-buffer census rows carried in bundles / the blackbox provider
_CENSUS_TOP = 16

# ---------------------------------------------------------------------------
# compile ledger (process-wide: one build history per `what`)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_BY_WHAT: Dict[str, dict] = {}  # what -> {builds, last_sig, ...}; guarded-by: _LOCK
_RECOMPILES: List[dict] = []  # bounded cause ledger; guarded-by: _LOCK
_TOTALS = {"compiles": 0, "recompiles": 0, "ms_total": 0.0,
           "cache_hits": 0, "cache_misses": 0}  # guarded-by: _LOCK
_ARMED = False  # blackbox provider registered; guarded-by: _LOCK


def _arm_once() -> None:
    """Register the blackbox ``device`` state provider the first time
    the armed plane is actually used — every bundle the process writes
    then carries the compile ledger + memory view (OOM forensics ride
    even the generic excepthook trigger)."""
    global _ARMED
    with _LOCK:
        if _ARMED:
            return
        _ARMED = True
    try:
        from dt_tpu.obs import blackbox
        blackbox.register_state("device", _bb_state)
    except Exception:  # noqa: BLE001 — observability is never fatal
        pass


def _bb_state() -> dict:
    out = {"compile": summary(), "compiling": compiling()}
    try:
        out["mem"] = memory_snapshot()
    except Exception:  # noqa: BLE001 — best-effort forensics
        pass
    try:
        out["census"] = live_buffer_census(_CENSUS_TOP)
    except Exception:  # noqa: BLE001
        pass
    return out


def _fast_key(args: tuple) -> tuple:
    """The cheap per-call dispatch key — one ``(shape, dtype)`` tuple
    per pytree leaf, no hashing (the steady-state path runs this every
    step, so it must cost microseconds, not a digest)."""
    leaves: List[Any]
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(args)
    except Exception:  # noqa: BLE001 — jax-free callers (tests)
        leaves = list(args)
    out = []
    for x in leaves:
        sh = getattr(x, "shape", None)
        dt = getattr(x, "dtype", None)
        if sh is None or dt is None:
            import numpy as np
            a = np.asarray(x)
            sh, dt = a.shape, a.dtype
        out.append((tuple(sh), str(dt)))
    return tuple(out)


def _sig_of(args: tuple, meta: Optional[dict],
            key: Optional[tuple] = None) -> Dict[str, Any]:
    """The abstract signature jit recompiles on: per-leaf shape/dtype
    digests plus the call-site's static facts (mesh layout, donation).
    Values never enter — a different float at the same dtype is the
    same signature, matching jit's own cache key.  Computed only at
    compile time (the steady-state path uses :func:`_fast_key`)."""
    key = key if key is not None else _fast_key(args)
    shapes = [k[0] for k in key]
    dtypes = [k[1] for k in key]
    sig = {
        "nargs": len(shapes),
        "shape": hashlib.sha1(repr(shapes).encode()).hexdigest()[:12],
        "dtype": hashlib.sha1(repr(dtypes).encode()).hexdigest()[:12],
        "mesh": str((meta or {}).get("mesh", "")),
        "donate": str((meta or {}).get("donate", "")),
        # what the step returns for the fit call's metric (Module: the
        # names of its per-row statistics, None for the logits)
        "metric": str((meta or {}).get("metric", "")),
    }
    sig["digest"] = hashlib.sha1(
        repr(sorted(sig.items())).encode()).hexdigest()[:12]
    return sig


def _sig_delta(prev: Dict[str, Any], new: Dict[str, Any]) -> List[str]:
    """The named recompile cause: which signature facets changed
    (``rebuild`` = none of them — a fresh jit object re-compiled the
    identical program, the persistent-cache-hit case)."""
    changed = [k for k in ("shape", "dtype", "mesh", "donate", "metric",
                           "nargs")
               if prev.get(k) != new.get(k)]
    return changed or ["rebuild"]


def build_stages(tr: obs_trace.Tracer, mark: int) -> Dict[str, Any]:
    """What the build account (``obs/trace.py``) says of the builds this
    thread has made since ``mark`` (a reading of ``tr.builds()``):
    milliseconds in each stage, and what the persistent cache did for
    the compile — ``miss`` if it was asked and did not serve it, ``hit``
    if it served it, ``off`` if it could not be asked (no directory in
    effect, or ``jax_enable_compilation_cache`` off)."""
    tid = tr._ident()
    at_tid, at_cache = (obs_trace.BUILD_ROW_FIELDS.index(f)
                        for f in ("tid", "cache"))
    rows = [r for r in tr.build_rows(since=mark) if r[at_tid] == tid]
    said = {r[at_cache] for r in rows}
    out = {f"{stage}_ms": round(obs_trace.stage_ns(rows, stage) / 1e6, 3)
           for stage in ("trace", "lower", "backend")}
    out["cache"] = "miss" if "miss" in said else \
        "hit" if "hit" in said else "off"
    return out


def _record_compile(what: str, sig: Dict[str, Any], elapsed_ms: float,
                    cache: str, mem: Optional[dict],
                    tracer: Optional[obs_trace.Tracer] = None,
                    now_ms: Optional[int] = None,
                    stages: Optional[Dict[str, float]] = None
                    ) -> Optional[dict]:
    """Fold one observed compile into the ledger; returns the recompile
    record when this ``what`` had compiled before (the cause event the
    chaos recompile-churn gate counts).  ``stages``: the build's
    ``trace_ms`` / ``lower_ms`` / ``backend_ms``, carried by the record.
    Injectable tracer/clock for deterministic tests."""
    tr = tracer if tracer is not None else obs_trace.tracer()
    ts = int(now_ms if now_ms is not None else time.time() * 1000)
    recompile = None
    with _LOCK:
        ent = _BY_WHAT.setdefault(what, {"builds": 0, "ms_total": 0.0,
                                         "last_sig": None, "mem": None})
        prev = ent["last_sig"]
        ent["builds"] += 1
        ent["ms_total"] = round(ent["ms_total"] + elapsed_ms, 3)
        ent["last_sig"] = dict(sig)
        if mem is not None:
            ent["mem"] = dict(mem)
        _TOTALS["compiles"] += 1
        _TOTALS["ms_total"] = round(_TOTALS["ms_total"] + elapsed_ms, 3)
        if cache == "hit":
            _TOTALS["cache_hits"] += 1
        elif cache == "miss":
            _TOTALS["cache_misses"] += 1
        if prev is not None:
            recompile = {"what": what, "changed": _sig_delta(prev, sig),
                         "prev": prev["digest"], "new": sig["digest"],
                         "elapsed_ms": round(elapsed_ms, 3),
                         **(stages or {}), "cache": cache, "ts_ms": ts}
            _TOTALS["recompiles"] += 1
            _RECOMPILES.append(recompile)
            del _RECOMPILES[:-_LEDGER_MAX]
    tr.counter("compile.compiles")
    if cache == "hit":
        tr.counter("compile.cache_hits")
    elif cache == "miss":
        tr.counter("compile.cache_misses")
    if recompile is not None:
        tr.event("compile.recompile",
                 {k: v for k, v in recompile.items() if k != "ts_ms"})
    return recompile


def summary() -> dict:
    """The process compile-ledger view: totals, per-``what`` build
    counts + last signature + XLA memory estimate, and the bounded
    recompile-cause log — shipped in the heartbeat ``dev`` payload and
    the worker result JSONs the chaos gates read."""
    with _LOCK:
        return {"enabled": enabled(),
                **dict(_TOTALS),
                "whats": sorted(_BY_WHAT),
                "by_what": {w: {"builds": e["builds"],
                                "ms_total": e["ms_total"],
                                "sig": dict(e["last_sig"] or {}),
                                "mem": dict(e["mem"]) if e["mem"]
                                else None}
                            for w, e in sorted(_BY_WHAT.items())},
                "recompile_log": [dict(r) for r in _RECOMPILES[-32:]]}


def compiling_info() -> Optional[Dict[str, Any]]:
    """The oldest OPEN ``compile.*`` span on the process tracer as
    ``{"name", "age_s"}``, or ``None`` — the "is this stall a JIT
    compile" signal, with the age the scheduler's blame demotion is
    bounded by (a worker WEDGED inside a compile must become blamable
    again)."""
    for s in obs_trace.tracer().open_spans():
        if str(s.get("name", "")).startswith("compile."):
            return {"name": s["name"],
                    "age_s": round(float(s.get("age_ms", 0.0)) / 1000.0,
                                   3)}
    return None


def compiling() -> Optional[str]:
    """The open ``compile.*`` span's name, or ``None``."""
    info = compiling_info()
    return info["name"] if info else None


def memory_analysis_row(m) -> Dict[str, float]:
    """XLA buffer-assignment bytes as the canonical MiB row of the
    compile observatory (the reference's ``example/memcost`` analog, live
    on the dtop device board: estimated next to measured HBM).  Field
    availability varies by jax version — ``peak_memory_in_bytes`` is
    absent on some ``CompiledMemoryStats`` builds, where
    temp+args+output is the buffer-assignment upper bound XLA would
    otherwise report."""
    def b(name: str) -> float:
        return float(getattr(m, name, 0) or 0)

    peak = b("peak_memory_in_bytes") or (
        b("temp_size_in_bytes") + b("argument_size_in_bytes")
        + b("output_size_in_bytes"))
    return {
        "temp_mb": round(b("temp_size_in_bytes") / 2**20, 2),
        "peak_mb": round(peak / 2**20, 2),
        "args_mb": round(b("argument_size_in_bytes") / 2**20, 2),
        "output_mb": round(b("output_size_in_bytes") / 2**20, 2),
    }


class _Instrumented:
    """The per-build wrapper :func:`instrument` returns: first call per
    abstract signature compiles AOT inside a ``compile.<what>`` span,
    later calls dispatch the cached executable.  Any AOT surprise
    (an executable stricter than jit about scalar args, an un-lowerable
    callable) falls back to the plain jit path permanently — the plane
    observes, it must never change what runs."""

    def __init__(self, what: str, fn: Callable, meta: Optional[dict]):
        self._what = what
        self._fn = fn
        self._meta = meta
        self._compiled: Dict[str, Any] = {}
        self._fallback = False

    def __getattr__(self, name):
        # callers that poke the jit surface (``.lower`` in tools) reach
        # the wrapped function transparently
        return getattr(self._fn, name)

    def __call__(self, *args):
        if self._fallback:
            return self._fn(*args)
        try:
            key = _fast_key(args)
        except Exception:  # noqa: BLE001 — never break the step
            self._fallback = True
            return self._fn(*args)
        comp = self._compiled.get(key)
        if comp is None:
            try:
                sig = _sig_of(args, self._meta, key=key)
            except Exception:  # noqa: BLE001
                self._fallback = True
                return self._fn(*args)
            return self._first_call(key, sig, args)
        try:
            return comp(*args)
        except (TypeError, ValueError):
            # AOT executables are stricter than jit about ARGUMENT
            # canonicalization (committed layouts, python scalars) —
            # those surface as TypeError/ValueError at dispatch and the
            # jit path handles them; degrade permanently.  Genuine
            # runtime failures (XlaRuntimeError, RESOURCE_EXHAUSTED)
            # must PROPAGATE: silently re-running the step would mask
            # the real error (and with donated buffers the retry would
            # see deleted inputs), defeating the OOM forensics upstream.
            self._fallback = True
            return self._fn(*args)

    def _first_call(self, key: tuple, sig: Dict[str, Any], args: tuple):
        """Compile-and-run for an unseen signature, inside the named
        ``compile.<what>`` span (so the open-span table — and the hang
        watchdog — see the compile in progress).  Returns the CALL's
        output."""
        if len(self._compiled) >= _MAX_SIGS:
            self._fallback = True
            return self._fn(*args)
        tr = obs_trace.tracer()
        t0 = tr.begin(f"compile.{self._what}",
                      {"what": self._what, "digest": sig["digest"]})
        mark = tr.builds()
        tm0 = time.monotonic()
        try:
            comp = self._fn.lower(*args).compile()
        except Exception:  # noqa: BLE001 — not AOT-able: observe the
            # plain jit call's first dispatch instead (the build happens
            # inside it; no memory analysis, its stages still land)
            try:
                out = self._fn(*args)
            finally:
                elapsed = (time.monotonic() - tm0) * 1000.0
                built = build_stages(tr, mark)
                tr.complete_span(f"compile.{self._what}", t0,
                                 {"what": self._what, "aot": False,
                                  **built})
            cache = built.pop("cache")
            _record_compile(self._what, sig, elapsed, cache, None,
                            stages=built)
            self._compiled[key] = self._fn
            return out
        elapsed = (time.monotonic() - tm0) * 1000.0
        built = build_stages(tr, mark)
        mem = None
        try:
            mem = memory_analysis_row(comp.memory_analysis())
        except Exception:  # noqa: BLE001 — CPU backends may not report
            pass
        tr.complete_span(f"compile.{self._what}", t0,
                         {"what": self._what, "digest": sig["digest"],
                          "elapsed_ms": round(elapsed, 1), **built})
        cache = built.pop("cache")
        _record_compile(self._what, sig, elapsed, cache, mem, stages=built)
        try:
            out = comp(*args)
        except (TypeError, ValueError):
            # same dispatch-strictness fallback as the steady-state
            # path (runtime errors propagate); the recorded compile is
            # kept, AOT dispatch is dropped
            self._fallback = True
            return self._fn(*args)
        self._compiled[key] = comp
        return out


def instrument(what: str, fn: Callable,
               meta: Optional[dict] = None) -> Callable:
    """Wrap a jitted callable in the compile observatory.  ``what``
    names the surface (``train_step`` / ``grad_step`` / ... — the
    recompile ledger keys on it); ``meta`` carries the static facts the
    signature diff names (``{"mesh": ..., "donate": ...}``).  With the
    plane off this returns ``fn`` UNCHANGED — the off path costs one
    cached-bool check at build time and nothing per step.  Armed, the
    steady-state call pays a shape-tuple key + the AOT executable's
    python dispatch (tens of microseconds — negligible against a real
    training step; the <1.5x guard in ``tests/test_device_obs.py``
    pins it)."""
    if not enabled():
        return fn
    _arm_once()
    return _Instrumented(what, fn, meta)


# ---------------------------------------------------------------------------
# memory plane: per-device HBM gauges, RSS fallback, staging occupancy,
# live-buffer census with provenance tags
# ---------------------------------------------------------------------------

_STAGING: "weakref.WeakValueDictionary[int, Any]" = \
    weakref.WeakValueDictionary()
_PROVENANCE: Dict[str, Callable[[], set]] = {}  # guarded-by: _LOCK


def register_staging(pool) -> None:
    """Track a :class:`~dt_tpu.training.overlap.StagingPool`'s occupancy
    (weakly — a drained engine's pool must stay collectable)."""
    _STAGING[id(pool)] = pool


def register_provenance(name: str, shapes_fn: Callable[[], set]) -> None:
    """Register a provenance shape set: ``shapes_fn()`` returns the
    ``(shape_str, dtype_str)`` pairs belonging to ``name`` (e.g. the
    model's params), and the live-buffer census tags matching rows —
    the ``example/memcost``-style attribution, live."""
    with _LOCK:
        _PROVENANCE[name] = shapes_fn


def _rss_bytes() -> Optional[int]:
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * (os.sysconf("SC_PAGE_SIZE")
                                               if hasattr(os, "sysconf")
                                               else 4096)
    except (OSError, ValueError, IndexError):
        try:
            import resource
            return resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:  # noqa: BLE001
            return None


def memory_snapshot(devices=None) -> dict:
    """One memory view: per-device HBM stats when the backend reports
    them (``jax.Device.memory_stats()`` — TPU/GPU), host RSS always,
    staging-pool occupancy when any pool is registered.  ``devices`` is
    injectable so tests pin the gauges without a chip."""
    out: Dict[str, Any] = {"devices": []}
    if devices is None:
        try:
            import jax
            devices = jax.local_devices()
        except Exception:  # noqa: BLE001 — jax-free caller
            devices = []
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:  # noqa: BLE001 — CPU backends raise/None
            ms = None
        if not ms:
            continue
        out["devices"].append({
            "id": getattr(d, "id", len(out["devices"])),
            "bytes_in_use": int(ms.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(ms.get("peak_bytes_in_use", 0)),
            "bytes_limit": int(ms.get("bytes_limit", 0))})
    rss = _rss_bytes()
    if rss is not None:
        out["host_rss_bytes"] = int(rss)
    pools = list(_STAGING.values())
    if pools:
        out["staging"] = {
            "bytes": sum(int(getattr(p, "_free_bytes", 0)) for p in pools),
            "outstanding": sum(int(getattr(p, "outstanding", 0))
                               for p in pools),
            "allocated": sum(int(getattr(p, "allocated", 0))
                             for p in pools)}
    return out


def sample_into(reg, devices=None) -> dict:
    """Set the ``device.*`` gauges on a
    :class:`~dt_tpu.obs.metrics.MetricsRegistry` from one memory
    snapshot (the worker ``Sampler``'s hook when both planes are on:
    the gauges then ride the heartbeat export, the Prometheus
    exposition, and the time-series ring).  Returns the snapshot."""
    snap = memory_snapshot(devices=devices)
    for d in snap["devices"]:
        labels = {"device": str(d["id"])}
        reg.gauge("device.hbm_bytes", d["bytes_in_use"], labels=labels)
        reg.gauge("device.hbm_peak_bytes", d["peak_bytes_in_use"],
                  labels=labels)
        if d["bytes_limit"]:
            reg.gauge("device.hbm_limit_bytes", d["bytes_limit"],
                      labels=labels)
    if "host_rss_bytes" in snap:
        reg.gauge("device.host_rss_bytes", snap["host_rss_bytes"])
    st = snap.get("staging")
    if st is not None:
        reg.gauge("device.staging_bytes", st["bytes"])
        reg.gauge("device.staging_outstanding", st["outstanding"])
    return snap


def metrics_hook() -> Optional[Callable[[], None]]:
    """The worker-side :class:`~dt_tpu.obs.metrics.Sampler` hook
    (``None`` when the device plane is off, so the off path adds
    nothing to the sampler)."""
    if not enabled():
        return None
    _arm_once()
    from dt_tpu.obs import metrics as obs_metrics

    def _hook():
        sample_into(obs_metrics.registry())
    return _hook


def live_buffer_census(top: int = _CENSUS_TOP,
                       arrays=None) -> List[dict]:
    """Top live device buffers by total bytes, grouped by
    ``(shape, dtype)`` with a provenance tag when the group matches a
    registered shape set — the "what is actually holding HBM" answer an
    OOM bundle needs.  ``arrays`` is injectable for chip-free tests."""
    import numpy as np
    if arrays is None:
        try:
            import jax
            arrays = jax.live_arrays()
        except Exception:  # noqa: BLE001 — jax-free caller
            arrays = []
    with _LOCK:
        provs = dict(_PROVENANCE)
    tagsets = []
    for name, fn in sorted(provs.items()):
        try:
            tagsets.append((name, set(fn())))
        except Exception:  # noqa: BLE001 — a provider bug loses its
            pass           # tag, never the census
    groups: Dict[tuple, dict] = {}
    for a in arrays:
        try:
            shape = tuple(a.shape)
            dtype = str(a.dtype)
            nbytes = int(np.prod(shape or (1,))) * \
                int(np.dtype(dtype).itemsize)
        except Exception:  # noqa: BLE001 — exotic array types
            continue
        g = groups.setdefault((str(shape), dtype),
                              {"shape": str(shape), "dtype": dtype,
                               "count": 0, "bytes": 0, "tag": ""})
        g["count"] += 1
        g["bytes"] += nbytes
    for g in groups.values():
        for name, shapes in tagsets:
            if (g["shape"], g["dtype"]) in shapes:
                g["tag"] = name
                break
    return sorted(groups.values(),
                  key=lambda g: (-g["bytes"], g["shape"]))[:top]


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------


def is_oom(exc: BaseException) -> bool:
    """Whether ``exc`` is an XLA allocation failure (the
    RESOURCE_EXHAUSTED family — jax surfaces it as XlaRuntimeError with
    the status name in the message)."""
    r = repr(exc)
    return "RESOURCE_EXHAUSTED" in r or "Out of memory" in r


def maybe_oom_bundle(exc: BaseException,
                     host: Optional[str] = None) -> Optional[str]:
    """On a RESOURCE_EXHAUSTED error, write a blackbox bundle carrying
    the live-buffer census + memory snapshot BEFORE the process dies —
    the forensic the wedged-bench zeros never had.  No-op (one bool
    check + one repr) unless both this plane and the blackbox plane are
    armed; returns the bundle path or ``None``."""
    if not enabled() or not is_oom(exc):
        return None
    _arm_once()
    try:
        from dt_tpu.obs import blackbox
        if not blackbox.enabled():
            return None
        extra: Dict[str, Any] = {"error": repr(exc)[-500:]}
        try:
            extra["census"] = live_buffer_census(_CENSUS_TOP)
        except Exception:  # noqa: BLE001
            pass
        try:
            extra["mem"] = memory_snapshot()
        except Exception:  # noqa: BLE001
            pass
        obs_trace.tracer().event("device.oom",
                                 {"error": extra["error"][:200]})
        blackbox.note("device.oom", host=host)
        return blackbox.write_bundle("oom", host=host, fatal=True,
                                     extra=extra)
    except Exception:  # noqa: BLE001 — forensics never take the
        return None    # process down before the real error surfaces


# ---------------------------------------------------------------------------
# on-demand jax.profiler capture (the profile_capture wire command)
# ---------------------------------------------------------------------------

_CAPTURE: Optional[dict] = None  # {steps, left, dir, seq, started}; guarded-by: _LOCK
_CAPTURE_SEQ = 0  # last capture-command seq applied; guarded-by: _LOCK
_WIRE_SEQ = 0  # heartbeat dev-payload ordering (dseq); guarded-by: _LOCK


def capture_seq() -> int:
    """Last ``profile_capture`` command seq this process applied — the
    heartbeat's dedup cursor (the profiler-command ``pseq`` contract)."""
    with _LOCK:
        return _CAPTURE_SEQ


def handle_capture_cmds(cmds, host: Optional[str] = None) -> int:
    """Apply capture commands delivered on the heartbeat (seq-guarded:
    an at-least-once re-delivery is a no-op).  Returns how many armed."""
    armed = 0
    for c in cmds or ():
        try:
            if arm_capture(int(c.get("steps", 8)), seq=int(c["seq"]),
                           host=host):
                armed += 1
        except (KeyError, TypeError, ValueError):
            continue
    return armed


def arm_capture(steps: int, seq: int = 0, outdir: Optional[str] = None,
                host: Optional[str] = None) -> bool:
    """Arm a bounded N-step ``jax.profiler`` capture; the trace starts
    on the next :func:`capture_tick` and stops ``steps`` ticks later,
    landing under ``DT_BLACKBOX_DIR`` with a manifest row.  Seq-guarded
    against heartbeat re-delivery; one capture at a time."""
    global _CAPTURE, _CAPTURE_SEQ
    if not enabled():
        return False
    _arm_once()
    from dt_tpu.obs import blackbox
    with _LOCK:
        if seq and seq <= _CAPTURE_SEQ:
            return False
        if _CAPTURE is not None:
            # one at a time; the pending one finishes.  The seq cursor
            # is NOT advanced: wire_payload keeps reporting the old
            # cseq, so the at-least-once heartbeat re-delivery arms
            # this command once the slot frees instead of dropping it.
            return False
        if seq:
            _CAPTURE_SEQ = seq
        d = outdir or os.path.join(blackbox.bundle_dir(),
                                   f"profile-{seq or int(time.time())}")
        _CAPTURE = {"steps": max(1, int(steps)), "left": max(1, int(steps)),
                    "dir": d, "seq": seq, "started": False,
                    "host": host}
    blackbox.note("profile.capture", phase="armed", steps=steps,
                  host=host)
    return True


def _start_trace(d: str) -> None:
    import jax
    os.makedirs(d, exist_ok=True)
    jax.profiler.start_trace(d)


def _stop_trace() -> None:
    import jax
    jax.profiler.stop_trace()


def capture_tick() -> None:
    """One training-step tick for the on-demand capture (called from
    ``Module.fit``'s step loop, next to the watchdog beat).  One global
    ``None`` check when no capture is armed."""
    global _CAPTURE
    if _CAPTURE is None:
        return
    with _LOCK:
        cap = _CAPTURE
        if cap is None:
            return
        if not cap["started"]:
            cap["started"] = True
            start = True
            stop = False
        else:
            cap["left"] -= 1
            start = False
            stop = cap["left"] <= 0
            if stop:
                _CAPTURE = None
    try:
        if start:
            _start_trace(cap["dir"])
        if stop:
            _stop_trace()
            from dt_tpu.obs import blackbox
            obs_trace.tracer().event("profile.capture",
                                     {"steps": cap["steps"],
                                      "dir": cap["dir"],
                                      "seq": cap["seq"]})
            blackbox.note("profile.capture", phase="done",
                          steps=cap["steps"], dir=cap["dir"])
            blackbox.manifest_append({
                "kind": "profile_capture",
                "ts_ms": int(time.time() * 1000), "pid": os.getpid(),
                "host": cap.get("host"), "trigger": "profile.capture",
                "steps": cap["steps"], "seq": cap["seq"],
                "dir": cap["dir"]})
    except Exception:  # noqa: BLE001 — a profiler failure must never
        # break the step loop; drop the capture and note the failure
        with _LOCK:
            _CAPTURE = None
        try:
            from dt_tpu.obs import blackbox
            blackbox.note("profile.capture", phase="failed",
                          dir=cap.get("dir"))
        except Exception:  # noqa: BLE001
            pass


def capture_abort() -> None:
    """Close out a capture the step loop cannot finish (``Module.fit``
    exits before ``steps`` more ticks: job end, eviction, health halt).
    The profiler session is stopped and the manifest records the
    truncated capture — an operator's ``queued: true`` must never end
    in a silently-open trace with no row.  One global ``None`` check
    when nothing is armed."""
    global _CAPTURE
    if _CAPTURE is None:
        return
    with _LOCK:
        cap = _CAPTURE
        _CAPTURE = None
    if cap is None or not cap["started"]:
        return
    try:
        _stop_trace()
        from dt_tpu.obs import blackbox
        done = cap["steps"] - cap["left"]
        obs_trace.tracer().event("profile.capture",
                                 {"steps": done, "dir": cap["dir"],
                                  "seq": cap["seq"], "aborted": True})
        blackbox.note("profile.capture", phase="aborted",
                      steps=done, dir=cap["dir"])
        blackbox.manifest_append({
            "kind": "profile_capture", "aborted": True,
            "ts_ms": int(time.time() * 1000), "pid": os.getpid(),
            "host": cap.get("host"), "trigger": "profile.capture",
            "steps": done, "requested_steps": cap["steps"],
            "seq": cap["seq"], "dir": cap["dir"]})
    except Exception:  # noqa: BLE001 — teardown is best-effort
        pass


# ---------------------------------------------------------------------------
# wire payload (heartbeat `dev` section) + test reset
# ---------------------------------------------------------------------------


def wire_payload() -> Optional[dict]:
    """The small per-heartbeat device view the scheduler ingests into
    its ``obs_dump``/``health`` device section: compile totals, the
    compiling-now flag (the fleet-hang detector demotes a compiling
    worker's blame), the latest memory snapshot, and the capture-dedup
    cursor.  ``None`` when the plane is off."""
    global _WIRE_SEQ
    if not enabled():
        return None
    _arm_once()
    with _LOCK:
        compile_view = {**{k: _TOTALS[k] for k in
                           ("compiles", "recompiles", "cache_hits",
                            "cache_misses")},
                        "ms_total": _TOTALS["ms_total"],
                        "whats": sorted(_BY_WHAT),
                        "est": next(
                            (dict(e["mem"]) for _, e in
                             sorted(_BY_WHAT.items(),
                                    key=lambda kv:
                                    -(kv[1]["mem"] or {})
                                    .get("peak_mb", 0.0))
                             if e["mem"]), None)}
        cseq = _CAPTURE_SEQ
        _WIRE_SEQ += 1
        dseq = _WIRE_SEQ
    info = compiling_info()
    # dseq orders the payloads on the at-least-once heartbeat channel:
    # a delayed/duplicated old beat must not roll the scheduler's view
    # back (the hm-export gseq contract)
    out = {"dseq": dseq, "cseq": cseq,
           "compiling": info["name"] if info else None,
           "compiling_age_s": info["age_s"] if info else 0.0,
           "compile": compile_view}
    try:
        out["mem"] = memory_snapshot()
    except Exception:  # noqa: BLE001 — the payload ships without it
        pass
    return out


def _reset_for_tests() -> None:
    """Drop the process ledger/capture/provenance state (tests only —
    the ledger is process-shared like the blackbox ring)."""
    global _CAPTURE, _CAPTURE_SEQ, _ARMED, _WIRE_SEQ
    with _LOCK:
        _BY_WHAT.clear()
        _RECOMPILES.clear()
        for k in _TOTALS:
            _TOTALS[k] = 0 if k != "ms_total" else 0.0
        _PROVENANCE.clear()
        _CAPTURE = None
        _CAPTURE_SEQ = 0
        _WIRE_SEQ = 0
        _ARMED = False
    _STAGING.clear()
    try:
        from dt_tpu.obs import blackbox
        blackbox.unregister_state("device", _bb_state)
    except Exception:  # noqa: BLE001
        pass
