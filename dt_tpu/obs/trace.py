"""Near-zero-overhead structured tracing + metrics core.

The reference's only observability was the per-process chrome-trace
profiler (``src/profiler/profiler.h:256``) with remote control plumbed
through kvstore commands (``KVStoreServerProfilerCommand``,
``kvstore_dist.h:102-110``, ``kvstore_dist_server.h:275-322``) — op-level
timelines, nothing about the *job*: how long a membership change stalls
training, where allreduce rounds wait, which retries/faults fired.  This
module is the job-level substrate: a thread-safe per-process span /
counter / event API over a bounded ring buffer, exported through the
elastic heartbeat channel (the same channel the profiler control already
rides) and merged by the scheduler into one chrome://tracing timeline
(``dt_tpu/obs/export.py``).

Design points
-------------

- **Hard-off by default.**  Tracing is enabled by ``DT_OBS=1``
  (``dt_tpu.config.ENV_REGISTRY``) or :func:`set_enabled`; disabled
  ``span()``/``event()`` calls return a shared no-op and retain nothing
  (``tests/test_obs.py`` asserts the fast path allocates nothing
  measurable).  *Counters* stay live either way — they replace ad-hoc
  always-on counters like the scheduler's transport stats.
- **Bounded ring.**  At most ``DT_OBS_RING`` records are retained;
  overflow drops the OLDEST record and bumps ``dropped`` (never raises,
  never blocks the instrumented path on a slow consumer).
- **Clocks.**  Timestamps are wall-clock (cross-process mergeable on one
  machine — same trust model as the reference's per-node traces);
  durations come from the monotonic clock.  Both are injectable for
  deterministic tests.
- **Nesting** rides a per-tracer ``contextvars.ContextVar``: a span's
  record carries its parent span id, and events attach to the enclosing
  span, without any thread-local bookkeeping at the call sites.
- **The step account** (:class:`StepAccount`) is counter-class: live with
  tracing off.  ``Module.fit`` writes one row per iteration of its step
  loop into a second bounded ring (``DT_OBS_RING`` rows): the wall-clock
  start and the monotonic nanoseconds of each phase in
  :data:`STEP_PHASES`, which sum to the iteration exactly.  With tracing
  on the same boundaries are spans, children of the iteration's ``step``
  span, and ``jax.profiler`` annotations.
- **The build account** is the step account's other half, live the same
  way: one row for every trace, lowering and compile (or cache read) that
  jax itself reports (:data:`BUILD_ROW_FIELDS`, written by listeners of
  ``jax.monitoring``: nothing is wrapped), and one row for every ``fit``
  call (:data:`FIT_ROW_FIELDS`: entry, iterations, exit), all on the step
  rows' clock.  With tracing on each is also a completed span
  (``build.trace`` / ``build.lower`` / ``build.backend``; ``fit`` with
  ``fit.enter`` and ``fit.exit``) at the row's own readings.

Record schema (flat tuples, ring/wire-compact)::

    ("X", rseq, name, ts_us, dur_us, tid, span_id, parent_id, attrs)  span
    ("i", rseq, name, ts_us, 0,      tid, event_id, parent_id, attrs) event

Account rows are flat tuples too, their fields named by
:data:`STEP_ROW_FIELDS`, :data:`BUILD_ROW_FIELDS` and :data:`FIT_ROW_FIELDS`.

``rseq`` increases strictly in buffer order — the heartbeat export's
at-least-once dedup key (the scheduler ignores records at-or-below the
last ``rseq`` it ingested for a (host, incarnation) track).
"""

from __future__ import annotations

import contextvars
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from dt_tpu import config

# ---------------------------------------------------------------------------
# process-wide enable gate (DT_OBS, overridable in-process)
# ---------------------------------------------------------------------------

_ENABLED_OVERRIDE: Optional[bool] = None
_ENV_ENABLED: Optional[bool] = None


def enabled() -> bool:
    """Whether tracing is on for this process (``DT_OBS=1`` or an explicit
    :func:`set_enabled`).  One global-read + compare on the fast path."""
    if _ENABLED_OVERRIDE is not None:
        return _ENABLED_OVERRIDE
    global _ENV_ENABLED
    if _ENV_ENABLED is None:
        _ENV_ENABLED = config.env("DT_OBS").strip().lower() in ("1", "true")
    return _ENV_ENABLED


def set_enabled(on: Optional[bool]) -> None:
    """Process-local override (``None`` = follow the env var again) — the
    in-process analog of exporting ``DT_OBS`` to a subprocess worker."""
    global _ENABLED_OVERRIDE, _ENV_ENABLED
    _ENABLED_OVERRIDE = on
    if on is None:
        _ENV_ENABLED = None


# The r16 flight recorder (dt_tpu/obs/blackbox.py) arms the OPEN-SPAN
# table alone even when tracing is off — a crash bundle's "died 40 s
# into allreduce" evidence must not require DT_OBS.  blackbox registers
# its (cached-bool) enabled() here at import; the hook indirection keeps
# this module free of the circular import.  With the hook armed, spans
# enter/leave the open table but record NOTHING in the ring.
_ARM_OPEN_HOOK: Callable[[], bool] = lambda: False


def set_open_span_arm(fn: Optional[Callable[[], bool]]) -> None:
    """Arm the open-span table independently of the trace gate (the
    blackbox plane's hook; ``None`` disarms)."""
    global _ARM_OPEN_HOOK
    _ARM_OPEN_HOOK = fn or (lambda: False)


# ---------------------------------------------------------------------------
# trace origin (r13 causal tracing): the track name this process's records
# will appear under in the merged job dump.  WorkerClient sets it to its
# "host#incarnation" track key at construction; everything else (the
# in-process scheduler, tools) defaults to the control-plane track —
# matching how Scheduler.obs_dump merges the process tracer.  The origin
# rides the wire as half of the trace context (protocol.request "_tc"),
# so a server-side handler span can name the exact client track+span it
# serves and the export can join the two with chrome flow events.
# ---------------------------------------------------------------------------

_ORIGIN: Optional[str] = None


def set_origin(origin: Optional[str]) -> None:
    """Name this process's trace track (``None`` = back to the default)."""
    global _ORIGIN
    _ORIGIN = origin or None


def origin() -> str:
    """This process's track name for cross-process trace context."""
    return _ORIGIN or "control-plane"


# ---------------------------------------------------------------------------
# flush hooks (crash-path export: a worker about to os._exit pushes its
# buffered records to the scheduler so injected crashes still appear on
# the job timeline — registered by WorkerClient)
# ---------------------------------------------------------------------------

_FLUSH_HOOKS: List[Callable[[], None]] = []
_FLUSH_LOCK = threading.Lock()


def register_flush(fn: Callable[[], None]) -> None:
    with _FLUSH_LOCK:
        if fn not in _FLUSH_HOOKS:
            _FLUSH_HOOKS.append(fn)


def unregister_flush(fn: Callable[[], None]) -> None:
    with _FLUSH_LOCK:
        if fn in _FLUSH_HOOKS:
            _FLUSH_HOOKS.remove(fn)


def flush() -> None:
    """Best-effort: run every registered flush hook (never raises — the
    caller may be half a millisecond from ``os._exit``)."""
    with _FLUSH_LOCK:
        hooks = list(_FLUSH_HOOKS)
    for fn in hooks:
        try:
            fn()
        except Exception:
            pass


class _NoopSpan:
    """Shared do-nothing context manager: the disabled fast path returns
    this singleton, so a skipped span allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_SPAN = _NoopSpan()

#: bound on the open-span table (leaked begin() tokens shed oldest-first)
_OPEN_MAX = 256


class _Span:
    """A live span; created only when the tracer is enabled."""

    __slots__ = ("_tr", "name", "attrs", "_t0w", "_t0m", "_sid", "_parent",
                 "_tok", "_ann")

    def __init__(self, tr: "Tracer", name: str, attrs: Optional[dict],
                 ann=None):
        self._tr = tr
        self.name = name
        self.attrs = attrs
        # a jax.profiler annotation entered and left with the span, so
        # that a profiler session carries it on its own timeline
        self._ann = ann

    def __enter__(self):
        return self.open(self._tr._wall(), self._tr._mono())

    def open(self, t0w: int, t0m: int) -> "_Span":
        """Enter at clock readings the caller has already taken (the
        step account reads each clock once per phase boundary)."""
        tr = self._tr
        self._t0w = t0w
        self._t0m = t0m
        self._parent = tr._ctx.get()
        self._sid = tr._next_seq()
        self._tok = tr._ctx.set(self._sid)
        tr._open_add(self._sid, self.name, t0w, t0m, self._parent,
                     self.attrs)
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self.close(self._tr._mono())
        return False

    def close(self, t1m: int) -> None:
        """Leave at a monotonic reading the caller has already taken."""
        tr = self._tr
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        tr._ctx.reset(self._tok)
        tr._open_pop(self._sid)
        if not tr.on():
            return  # open-table-only mode (blackbox armed, DT_OBS=0)
        dur_us = max(t1m - self._t0m, 0) // 1000
        tr._push(("X", None, self.name, self._t0w // 1000, dur_us,
                  tr._ident(), self._sid, self._parent,
                  self.attrs))


def _annotation(name: str, step_num: Optional[int] = None):
    """The ``jax.profiler`` annotation of a span, through
    ``utils/profiler.annotate``; imported on first use so that this
    module stays importable without jax (the scheduler, ``dtop``)."""
    from dt_tpu.utils import profiler
    return profiler.annotate(name, step_num=step_num)


# ---------------------------------------------------------------------------
# the step account: where each iteration of ``Module.fit`` spent its time
# ---------------------------------------------------------------------------

#: the phases of one iteration of ``fit``'s step loop (``obs/names.py`` says
#: what each covers); the iteration is always in exactly one of them, so a
#: row's phases sum to its length
STEP_PHASES = ("step.input", "step.place", "step.dispatch", "step.sync",
               "step.fetch", "step.metric", "step.callback", "step.hooks")
#: one account row: the ``fit`` call (a per-tracer count), the epoch, the
#: iteration within it, the step it dispatched and the batch whose metric
#: it flushed (host-side counts; ``None`` where it did neither: the first
#: iteration flushes nothing, the last dispatches nothing), its wall-clock
#: start, its monotonic length, then the monotonic nanoseconds in each
#: phase
STEP_ROW_FIELDS = ("fit", "epoch", "iteration", "dispatched", "flushed",
                   "wall_ns", "total_ns") + STEP_PHASES
_PHASE_INDEX = {name: i for i, name in enumerate(STEP_PHASES)}
_HOOKS = _PHASE_INDEX["step.hooks"]
#: one row a ``fit`` call, written at its exit (every way out): its number,
#: its entry on the wall clock, its monotonic length, and that length's four
#: parts, which sum to it: from the entry to the first iteration's begin
#: (bind, the steps built for the metric, resume, drain and watchdog
#: installs, the first barrier, the iterator's reset), inside iterations
#: (its step rows' lengths added up), between one epoch's last iteration
#: and the next one's first (snapshot, evaluation, callbacks, barrier), and
#: from the last iteration's close to the return; then its iterations.  A
#: call that never reached an iteration is all entry
FIT_ROW_FIELDS = ("fit", "wall_ns", "total_ns", "enter_ns", "steps_ns",
                  "between_ns", "exit_ns", "iterations")


class StepAccount:
    """One ``fit`` call's writer of account rows (:meth:`Tracer.
    step_account`), used by the loop's own thread only.

    ``begin`` opens an iteration at the top of the step loop (closing the
    one before at the same clock reading), ``phase`` moves it into another
    phase, ``end`` closes the last one; whatever is not inside a named
    phase is ``step.hooks``.  A boundary costs one monotonic clock read
    and writes into slots made once per ``fit`` call; a row is one tuple
    and one append.  With tracing on (or the blackbox's open-span table
    armed) every iteration is also a ``step`` span and every phase a
    child span of it, opened and closed at the account's own readings.

    The writer is made at the call's entry and ``exit`` writes the call's
    own row (:data:`FIT_ROW_FIELDS`): two more clock boundaries a call,
    and what it needs of the iterations is kept where an epoch's first one
    begins and its last one ends, never in between."""

    __slots__ = ("_tr", "fit", "_ns", "_cur", "_t", "_t0m", "_t0w",
                 "_live", "epoch", "iteration", "dispatched", "flushed",
                 "_step_span", "_phase_span", "_enter_w", "_enter_m",
                 "_first_m", "_last_m", "_epoch_m", "_epoch_i",
                 "_steps_ns", "_iterations", "_thread", "_outer")

    def __init__(self, tr: "Tracer", fit: int):
        self._tr = tr
        self.fit = fit
        self._ns = [0] * len(STEP_PHASES)
        self._live = False
        self._step_span = self._phase_span = None
        #: set by the loop: the step this iteration dispatched, the batch
        #: whose metric it flushed
        self.dispatched: Optional[int] = None
        self.flushed: Optional[int] = None
        # the call's own row: its first iteration's begin and its last
        # one's close, the nanoseconds and the count of its iterations
        self._first_m = self._last_m = None
        self._steps_ns = self._iterations = 0
        # build rows written by this thread while the call is open carry
        # its number (a table by thread, not a ContextVar: a variable set
        # in the loop's context would make every ContextVar lookup of the
        # process a little dearer, numpy's error state among them)
        self._thread = tr._ident()
        self._outer = tr._open_fits.get(self._thread)
        tr._open_fits[self._thread] = fit
        self._enter_m, self._enter_w = tr._mono(), tr._wall()

    def begin(self, epoch: int, iteration: int,
              step_num: Optional[int] = None) -> Optional[tuple]:
        """Top of an iteration.  Returns the row of the iteration this
        closes, if one was open.  ``step_num`` numbers the iteration in a
        profiler session (``StepTraceAnnotation``)."""
        tr = self._tr
        now, wall = tr._mono(), tr._wall()
        if self._live:
            row = self._close(now)
        else:   # an epoch's first iteration
            row = None
            self._epoch_m, self._epoch_i = now, iteration
            if self._first_m is None:
                self._first_m = now
        self.epoch, self.iteration = epoch, iteration
        self.dispatched = self.flushed = None
        self._t0m = self._t = now
        self._t0w = wall
        self._cur = _HOOKS
        self._live = True
        if tr.on() or _ARM_OPEN_HOOK():
            self._step_span = tr.span(
                "step", {"epoch": epoch, "iteration": iteration},
                annotate=True, step_num=step_num).open(wall, now)
            self._phase_span = tr.span(
                "step.hooks", annotate=True).open(wall, now)
        return row

    def phase(self, name: str) -> None:
        """The iteration leaves the phase it was in and enters ``name``."""
        now = self._tr._mono()
        self._ns[self._cur] += now - self._t
        self._t = now
        self._cur = _PHASE_INDEX[name]
        if self._phase_span is not None:
            self._phase_span.close(now)
            self._phase_span = self._tr.span(name, annotate=True).open(
                self._tr._wall(), now)

    def end(self) -> Optional[tuple]:
        """Close the open iteration, if any (the loop's end, and every
        way out of ``fit``: an iteration that an exception leaves still
        writes its row, with the phases it got to).  Returns its row."""
        if not self._live:
            return None
        now = self._tr._mono()
        self._steps_ns += now - self._epoch_m
        self._iterations += self.iteration - self._epoch_i + 1
        self._last_m = now
        return self._close(now)

    def exit(self) -> Optional[tuple]:
        """The ``fit`` call leaves, by any way out: write its row (once)
        and return it.  With tracing on the row is also a ``fit`` span with
        ``fit.enter`` and ``fit.exit`` children, at the row's readings."""
        if self._thread is None:
            return None
        self.end()
        tr = self._tr
        now = tr._mono()
        if self._outer is None:
            tr._open_fits.pop(self._thread, None)
        else:   # a call made inside another call's callback
            tr._open_fits[self._thread] = self._outer
        self._thread = None
        total = now - self._enter_m
        if self._first_m is None:
            enter, steps, out = total, 0, 0
        else:
            enter = self._first_m - self._enter_m
            steps, out = self._steps_ns, now - self._last_m
        row = (self.fit, self._enter_w, total, enter, steps,
               total - enter - steps - out, out, self._iterations)
        tr._push_fit_row(row)
        if tr.on():
            attrs = {"fit": self.fit}
            sid = tr.ended_span("fit", self._enter_w, total,
                                {**attrs, "iterations": self._iterations})
            tr.ended_span("fit.enter", self._enter_w, enter, attrs, sid)
            tr.ended_span("fit.exit", self._enter_w + total - out, out,
                          attrs, sid)
        return row

    def _close(self, now: int) -> tuple:
        ns = self._ns
        ns[self._cur] += now - self._t
        row = (self.fit, self.epoch, self.iteration, self.dispatched,
               self.flushed, self._t0w, now - self._t0m) + tuple(ns)
        for i in range(len(ns)):
            ns[i] = 0
        self._live = False
        if self._step_span is not None:
            self._phase_span.close(now)
            self._step_span.attrs = {
                "epoch": self.epoch, "iteration": self.iteration,
                "dispatched": self.dispatched, "flushed": self.flushed}
            self._step_span.close(now)
            self._step_span = self._phase_span = None
        self._tr._push_step_row(row)
        return row


class Tracer:
    """One span/event/counter sink with a bounded ring buffer.

    The process has one default instance (:func:`tracer`); servers that
    aggregate (Scheduler, RangeServer) construct their own so their
    control-plane records and counters stay per-instance (tests churn
    through many servers in one process).
    """

    def __init__(self, name: str = "process",
                 capacity: Optional[int] = None,
                 wall_clock: Optional[Callable[[], int]] = None,
                 mono_clock: Optional[Callable[[], int]] = None,
                 enabled: Optional[bool] = None,
                 ident: Optional[Callable[[], int]] = None):
        """``enabled``: ``True``/``False`` pins this instance regardless of
        the process gate; ``None`` follows :func:`enabled`.  Clocks return
        integer nanoseconds; ``ident`` returns the recording thread's id
        (both injectable for deterministic tests — r16 blackbox bundles
        and their digest-named files must serialize byte-identically
        under pinned inputs)."""
        self.name = name
        self._cap = max(1, int(capacity if capacity is not None
                               else int(config.env("DT_OBS_RING"))))
        self._wall = wall_clock or time.time_ns
        self._mono = mono_clock or time.monotonic_ns
        self._ident = ident or threading.get_ident
        self._enabled = enabled
        self._lock = threading.Lock()
        self._records: deque = deque()  # guarded-by: _lock
        self._dropped = 0  # guarded-by: _lock
        self._seq = 0  # guarded-by: _lock
        self._counters: Dict[str, int] = {}  # guarded-by: _lock
        # live (entered-but-not-exited) spans, keyed by span id — the
        # r16 flight-recorder snapshot (blackbox bundles capture "what
        # was this process in the middle of" at death).  Bounded: a
        # begin() whose complete_span never runs (exception paths) must
        # not leak entries forever.
        self._open: Dict[int, dict] = {}  # guarded-by: _lock
        # the step account's rows (StepAccount); live with tracing off,
        # bounded like the record ring, oldest dropped first
        self._step_rows: deque = deque(maxlen=self._cap)  # guarded-by: _lock
        self._fits = 0  # guarded-by: _lock
        # the build account's rows (the listeners below) and the fit
        # calls' own rows; live with tracing off, bounded the same way
        self._build_rows: deque = deque(maxlen=self._cap)  # guarded-by: _lock
        self._builds = 0  # rows ever written; guarded-by: _lock
        self._fit_rows: deque = deque(maxlen=self._cap)  # guarded-by: _lock
        self._ctx: contextvars.ContextVar = contextvars.ContextVar(
            f"dt_obs_span_{id(self)}", default=None)
        # thread -> the number of the fit call it has open (each thread
        # writes its own key only)
        self._open_fits: Dict[int, int] = {}

    # -- gate -------------------------------------------------------------

    def on(self) -> bool:
        return self._enabled if self._enabled is not None else enabled()

    # -- recording --------------------------------------------------------

    def _next_seq(self) -> int:
        with self._lock:
            self._seq += 1
            return self._seq

    def _push(self, rec: tuple) -> None:
        """Append one record, assigning its ``rseq`` (strictly increasing
        in buffer order — the export dedup key); overflow drops the
        oldest record and counts it, never raises."""
        with self._lock:
            self._seq += 1
            rec = (rec[0], self._seq) + rec[2:]
            if len(self._records) >= self._cap:
                self._records.popleft()
                self._dropped += 1
            self._records.append(rec)

    def span(self, name: str, attrs: Optional[dict] = None,
             annotate: bool = False, step_num: Optional[int] = None):
        """Context manager recording a complete ("X") span on exit; the
        disabled path returns a shared no-op singleton.  With only the
        blackbox open-span hook armed, the span enters/leaves the open
        table (crash evidence) but records nothing.  ``annotate``: with
        tracing on the span also enters a ``jax.profiler``
        ``TraceAnnotation`` of its name (``StepTraceAnnotation`` where
        ``step_num`` is given), so that any profiler session carries it;
        for the training loop's spans only, since it imports jax."""
        on = self.on()
        if not on and not _ARM_OPEN_HOOK():
            return _NOOP_SPAN
        return _Span(self, name, attrs,
                     _annotation(name, step_num) if annotate and on
                     else None)

    def now(self) -> Optional[Tuple[int, int]]:
        """(wall_ns, mono_ns) start token for :meth:`complete_span`, or
        ``None`` when tracing is off — lets call sites thread a span
        through code that can't be re-indented under a ``with``."""
        if not self.on():
            return None
        return (self._wall(), self._mono())

    def begin(self, name: Optional[str] = None,
              attrs: Optional[dict] = None) -> Optional[Tuple[int, int,
                                                              int]]:
        """Like :meth:`now`, but also pre-allocates the span's id —
        ``(wall_ns, mono_ns, span_id)`` — so the id can be propagated
        (e.g. over the wire as trace context) BEFORE the span completes.
        ``None`` when tracing is off: the disabled path allocates
        nothing, exactly like :meth:`now`.

        With ``name``, the in-flight span is additionally registered in
        the open-span table until its :meth:`complete_span` — the r16
        flight-recorder snapshot (:meth:`open_spans`): a crash bundle
        can then say "this process died 40 s into ``allreduce``", which
        the completed-record ring by definition cannot.

        With tracing off but the blackbox open-span hook armed, a NAMED
        begin still registers (and returns a token so its
        :meth:`complete_span` pops it) — open-table only, no record;
        callers gating extra work on the token (e.g. the wire trace
        context) must also check :meth:`on`."""
        if not self.on():
            if name is None or not _ARM_OPEN_HOOK():
                return None
        t0w, t0m = self._wall(), self._mono()
        sid = self._next_seq()
        if name is not None:
            self._open_add(sid, name, t0w, t0m, self._ctx.get(), attrs)
        return (t0w, t0m, sid)

    def complete_span(self, name: str,
                      t0: Optional[Tuple[int, ...]],
                      attrs: Optional[dict] = None) -> None:
        """Record a span begun at ``t0`` (= :meth:`now` or
        :meth:`begin`); no-op on ``None`` (tracing was off when the span
        would have started).  A :meth:`begin` token's pre-allocated id
        becomes the record's ``span_id`` — the export's cross-process
        flow-join key."""
        if t0 is None:
            return
        if len(t0) > 2:
            self._open_pop(t0[2])
        if not self.on():
            return  # open-table-only token (blackbox armed, DT_OBS=0)
        dur_us = max(self._mono() - t0[1], 0) // 1000
        self._push(("X", None, name, t0[0] // 1000, dur_us,
                    self._ident(),
                    t0[2] if len(t0) > 2 else None,
                    self._ctx.get(), attrs))

    def ended_span(self, name: str, wall_ns: int, dur_ns: int,
                   attrs: Optional[dict] = None,
                   parent: Optional[int] = None) -> Optional[int]:
        """Record a span that has already ended, at readings the caller
        holds (an account row's): its start on the wall clock and its
        length.  Returns its id, for a child's ``parent`` (default: the
        enclosing open span); ``None`` with tracing off."""
        if not self.on():
            return None
        sid = self._next_seq()
        self._push(("X", None, name, wall_ns // 1000,
                    max(dur_ns, 0) // 1000, self._ident(), sid,
                    parent if parent is not None else self._ctx.get(),
                    attrs))
        return sid

    # -- open-span table (r16 flight recorder, dt_tpu/obs/blackbox.py) ----

    def _open_add(self, sid: int, name: str, t0w: int, t0m: int,
                  parent: Optional[int],
                  attrs: Optional[dict]) -> None:
        with self._lock:
            if len(self._open) >= _OPEN_MAX:
                # a leaked begin() (its complete_span skipped by an
                # exception path) must not grow this forever; shed the
                # OLDEST entry — the newest opens are the death evidence
                self._open.pop(next(iter(self._open)))
            self._open[sid] = {"name": name, "ts_us": t0w // 1000,
                               "mono_ns": t0m,
                               "tid": self._ident(),
                               "parent": parent, "attrs": attrs}

    def _open_pop(self, sid: int) -> None:
        with self._lock:
            self._open.pop(sid, None)

    def abandon(self, t0: Optional[Tuple[int, ...]]) -> None:
        """Discard a named :meth:`begin` token without recording a span
        — failure paths that will never reach :meth:`complete_span`
        (e.g. a wire attempt that raised) drop their open-table entry
        here so a later bundle doesn't show phantom in-flight work."""
        if t0 is not None and len(t0) > 2:
            self._open_pop(t0[2])

    def open_spans(self) -> List[dict]:
        """Snapshot of the spans currently in flight — context-manager
        spans between ``__enter__``/``__exit__`` and named :meth:`begin`
        tokens whose :meth:`complete_span` has not run — ordered oldest
        first, each with its age on the monotonic clock.  This is the
        blackbox bundle's "open-span stack at death": nested spans
        reconstruct via ``parent``/``sid``, cross-thread ones via
        ``tid``."""
        now_m = self._mono()
        with self._lock:
            items = sorted(self._open.items(),
                           key=lambda kv: (kv[1]["mono_ns"], kv[0]))
        return [{"sid": sid, "name": e["name"], "ts_us": e["ts_us"],
                 "age_ms": round(max(now_m - e["mono_ns"], 0) / 1e6, 3),
                 "tid": e["tid"], "parent": e["parent"],
                 "attrs": e["attrs"]}
                for sid, e in items]

    def event(self, name: str, attrs: Optional[dict] = None) -> None:
        """Instant ("i") event, attached to the enclosing span if any."""
        if not self.on():
            return
        self._push(("i", None, name, self._wall() // 1000, 0,
                    self._ident(), None, self._ctx.get(), attrs))

    # -- the step account (live even when tracing is off) -----------------

    def step_account(self) -> StepAccount:
        """A writer of account rows for one ``fit`` call, numbered from 1
        in this tracer's order of calls."""
        with self._lock:
            self._fits += 1
            return StepAccount(self, self._fits)

    def _push_step_row(self, row: tuple) -> None:
        with self._lock:
            self._step_rows.append(row)

    def step_rows(self, fit: Optional[int] = None) -> List[tuple]:
        """The retained account rows, oldest first (of one ``fit`` call
        where ``fit`` is given; ``-1``: of the newest one)."""
        with self._lock:
            rows = list(self._step_rows)
        if fit is None or not rows:
            return rows
        if fit < 0:
            fit = rows[-1][0]
        return [r for r in rows if r[0] == fit]

    def _push_fit_row(self, row: tuple) -> None:
        with self._lock:
            self._fit_rows.append(row)

    def fit_rows(self) -> List[tuple]:
        """The retained rows of the ``fit`` calls that have left, oldest
        first (:data:`FIT_ROW_FIELDS`)."""
        with self._lock:
            return list(self._fit_rows)

    # -- the build account (live even when tracing is off) ----------------

    def _push_build_row(self, row: tuple) -> None:
        with self._lock:
            self._builds += 1
            self._build_rows.append(row)

    def builds(self) -> int:
        """How many build rows this tracer was ever given: a mark for
        :meth:`build_rows` to read on from."""
        with self._lock:
            return self._builds

    def build_rows(self, since: int = 0) -> List[tuple]:
        """The retained build rows, oldest first (:data:`BUILD_ROW_FIELDS`);
        with ``since`` (a reading of :meth:`builds`) those written after
        it."""
        with self._lock:
            rows = list(self._build_rows)
            new = self._builds - max(since, 0)
        return rows[max(len(rows) - new, 0):] if new > 0 else []

    # -- counters (live even when tracing is off) -------------------------

    def counter(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def get_counter(self, name: str, default: int = 0) -> int:
        with self._lock:
            return self._counters.get(name, default)

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def reset_counters(self) -> None:
        """Zero the live counters (tests: the process tracer is shared
        across a whole pytest session, so exact-count asserts must start
        from a clean slate whatever ran before — the r15 fix for the
        test-order dependency where obs tests failed after overlap/ha
        tests had already bumped ``allreduce.rounds`` etc.)."""
        with self._lock:
            self._counters.clear()

    # -- export -----------------------------------------------------------

    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def snapshot(self) -> Dict[str, Any]:
        """Non-destructive view: {name, records, counters, dropped}."""
        with self._lock:
            return {"name": self.name, "records": list(self._records),
                    "counters": dict(self._counters),
                    "dropped": self._dropped}

    def drain(self, max_records: Optional[int] = None) -> List[tuple]:
        """Remove and return up to ``max_records`` OLDEST records (the
        heartbeat flush takes bounded bites so one message stays small)."""
        with self._lock:
            if max_records is None or max_records >= len(self._records):
                out = list(self._records)
                self._records.clear()
            else:
                out = [self._records.popleft()
                       for _ in range(max_records)]
            return out


# ---------------------------------------------------------------------------
# process-default tracer
# ---------------------------------------------------------------------------

_DEFAULT: Optional[Tracer] = None
_DEFAULT_LOCK = threading.Lock()


def tracer() -> Tracer:
    """The process-wide default tracer (one worker process = one track).
    Making it is also when the build account starts to listen, in a
    process that has jax loaded."""
    global _DEFAULT
    if _DEFAULT is None:
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                _DEFAULT = Tracer(name="process")
    if not _LISTENING:
        _listen_for_builds()
    return _DEFAULT


# ---------------------------------------------------------------------------
# the build account: every trace, lowering and compile, as jax reports them
# ---------------------------------------------------------------------------

#: the ``jax.monitoring`` events that end a stage of a build -> the stage
BUILD_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
#: one build row: the ``fit`` call that the building thread had open
#: (``None`` outside one), the function's name (the same in all three
#: stages: the module name's ``jit(...)`` is cut), the stage, its start on
#: the wall clock (jax's own ``time.time()``: the step rows' clock) and its
#: length, then for ``backend`` whether the persistent cache served it
#: (``hit``; it was asked and did not: ``miss``; it is disabled or has no
#: directory: ``off``),
#: the seconds the retrieval took and the compile seconds jax says it saved
#: (``None`` in the other stages and without a hit), and the building
#: thread.  Every lowering and every backend compile is a row; a trace
#: that ran inside another stage (every ``jnp`` call inside a traced
#: function is a ``jit`` of its own, traced inside its caller's trace) is
#: one only if it took :data:`NESTED_ROW_NS` or more, so that a kernel's
#: own trace is named and a model's thousands of small ones are not:
#: seconds in a stage are the union of its rows' intervals
#: (:func:`stage_ns`), not their sum
BUILD_ROW_FIELDS = ("fit", "fun", "stage", "wall_ns", "dur_ns", "cache",
                    "retrieval_s", "saved_s", "tid")
NESTED_ROW_NS = 100_000_000
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "asked",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "wrote",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}
_LISTENING = False


class _Building(threading.local):
    """What this thread's builds have said so far: the stages that are
    open, and the cache's words since the last ``backend`` stage ended."""
    open = 0

    def __init__(self):
        self.cache = {}


_BUILDING = _Building()


def _on_stage_begin(event: str, start: float, **_) -> None:
    if event in BUILD_STAGES:
        _BUILDING.open += 1


def _on_cache_event(event: str, **_) -> None:
    what = _CACHE_EVENTS.get(event)
    if what is not None:
        _BUILDING.cache[what] = True


def _on_cache_seconds(event: str, secs: float, **_) -> None:
    what = _CACHE_SECONDS.get(event)
    if what is not None:
        _BUILDING.cache[what] = secs


def _on_stage(event: str, start: float, end: float, fun_name: str = "",
              **_) -> None:
    """A stage of a build has ended: one row on the process tracer, and
    with tracing on one ``build.<stage>`` span at the same readings."""
    stage = BUILD_STAGES.get(event)
    if stage is None:
        return
    mine = _BUILDING
    mine.open = max(mine.open - 1, 0)
    cache = retrieval = saved = None
    if stage == "backend":
        said = mine.cache
        # jax asks the cache wherever it is enabled, with or without a
        # directory to ask: a request that nothing came of counts as a
        # miss only where a directory is in effect
        cache = "hit" if "hit" in said else "miss" if "wrote" in said or (
            "asked" in said and
            sys.modules["jax"].config.jax_compilation_cache_dir) else "off"
        retrieval, saved = said.get("retrieval_s"), said.get("saved_s")
        said.clear()
    tr = _DEFAULT
    wall_ns, dur_ns = int(start * 1e9), int((end - start) * 1e9)
    if tr is None or (mine.open and stage == "trace"
                      and dur_ns < NESTED_ROW_NS):
        return
    fun = str(fun_name)
    for head in ("jit(", "pmap("):      # the module's name -> the function's
        if fun.startswith(head) and fun.endswith(")"):
            fun = fun[len(head):-1]
    fit = tr._open_fits.get(tr._ident())
    tr._push_build_row((fit, fun, stage, wall_ns, dur_ns, cache, retrieval,
                        saved, tr._ident()))
    if tr.on():
        tr.ended_span(f"build.{stage}", wall_ns, dur_ns,
                      {"fun": fun, "fit": fit, "cache": cache})


def _listen_for_builds() -> None:
    """Register the build account's four ``jax.monitoring`` listeners, once
    a process, as soon as the process tracer is asked for with jax loaded
    (``training/module.py`` asks at import, so before a ``Module`` builds
    anything; a process without jax never listens and never imports it).
    They stay for the life of the process and write to whichever tracer is
    the process's then.

    jax emits these events where a program is built and nowhere in a step:
    a build costs a begin and an end call for each of its three stages and
    for each ``jit`` traced inside it (a dictionary lookup and a counter,
    under a microsecond each beside a trace of milliseconds), and with the
    persistent cache on two or three calls of each of the cache's; a step
    that is already built costs none (``tests/test_build_account.py`` holds
    both).  Nothing is wrapped: the rows describe the ``jit`` call as the
    caller made it."""
    global _LISTENING
    if "jax" not in sys.modules:
        return
    with _DEFAULT_LOCK:
        if _LISTENING:
            return
        try:
            from jax import monitoring
        except ImportError:   # jax is half imported: ask again later
            return
        monitoring.register_scalar_listener(_on_stage_begin)
        monitoring.register_event_time_span_listener(_on_stage)
        monitoring.register_event_duration_secs_listener(_on_cache_seconds)
        monitoring.register_event_listener(_on_cache_event)
        _LISTENING = True


def stage_ns(rows, stage: str) -> int:
    """Nanoseconds that ``rows`` (build rows) spent in ``stage``: the
    union of their intervals, since an inner function's trace is inside
    its caller's."""
    total, upto = 0, 0
    for r in sorted((r for r in rows if r[2] == stage),
                    key=lambda r: r[3]):
        end = r[3] + r[4]
        if end > upto:
            total += end - max(r[3], upto)
            upto = end
    return total
