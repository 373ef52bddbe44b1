"""Readers of the program's own account of its steps.

``Module.fit`` leaves one row per iteration of its step loop on the process
tracer (``dt_tpu/obs/trace.py`` ``StepAccount``; live with tracing off): the
wall-clock start and the nanoseconds in each phase of ``STEP_PHASES``, which
sum to the iteration.  The window is the last ``fit`` call of the run (the
reference after it goes through no ``fit``), and it runs from one batch-end
callback to another, the last of which raises out of ``fit``.  So the
window's rows are the last ``ctx["steps"]`` rows that flushed a metric: the
callback is the last thing an iteration does, and they cover the window to
within what one iteration does after it.

A program without the account (a checkout from before it) gives every reader
here None, and the harness leaves the metric out of the line.
"""


def select(rows, steps):
    """Of one ``fit`` call's rows (dicts, oldest first), the last ``steps``
    that reached their batch-end callback; None where there are fewer (the
    ring dropped them, or the rows are another call's)."""
    flushed = [r for r in rows if r["flushed"] is not None]
    if steps <= 0 or len(flushed) < steps:
        return None
    return flushed[-steps:]


def phase_ms(rows, phase):
    """Milliseconds per step in ``phase``, over the window's rows."""
    return sum(r[phase] for r in rows) / len(rows) / 1e6


def unaccounted(rows, window_s):
    """The share (%) of the window's wall time that the rows' phases do not
    cover.  The window closes when the last row's callback starts (the
    clock reads the time first and raises last), so that callback is not
    the window's."""
    covered = sum(r["total_ns"] for r in rows) - rows[-1]["step.callback"]
    return 100.0 * (window_s - covered / 1e9) / window_s


def window_rows(ctx):
    """The window's rows from the process tracer, or None."""
    from dt_tpu.obs import trace
    fields = getattr(trace, "STEP_ROW_FIELDS", None)
    tracer = trace.tracer()
    if fields is None or not hasattr(tracer, "step_rows") or \
            not ctx["window_s"]:
        return None
    return select([dict(zip(fields, r)) for r in tracer.step_rows(fit=-1)],
                  ctx["steps"])


def phase_ms_per_step(ctx, m):
    rows = window_rows(ctx)
    return None if rows is None else phase_ms(rows, m["args"]["phase"])


def unaccounted_pct(ctx, m):
    rows = window_rows(ctx)
    return None if rows is None else unaccounted(rows, ctx["window_s"])
