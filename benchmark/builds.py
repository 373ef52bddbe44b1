"""Readers of the program's own account of what it built and of its ``fit``
calls: the set-up, from the program's side.

Beside the step rows (``spans.py``) the process tracer holds one row for every
trace, lowering and backend compile (or persistent-cache read) that jax
reports (``dt_tpu/obs/trace.py`` ``BUILD_ROW_FIELDS``: the ``fit`` call that
was open, the function, the stage, its start on ``time.time_ns``'s clock and
its nanoseconds, and for a backend stage the cache's word) and one row for
every ``fit`` call (``FIT_ROW_FIELDS``: its entry on the same clock, its
nanoseconds, and their parts: entry to first iteration, iterations, between
epochs, last iteration to return).  All live with every gate off.

The window is the last ``fit`` call of the run (the reference after it goes
through no ``fit``), so the set-up is every row that starts before that
call's entry: what ``setup_s`` covers, from inside.  A trace of a function
called inside another's lies inside it, so seconds in a stage are the union
of the rows' intervals.  Besides the metrics, a traced run's log gets one
``# build`` line for each row of half a second or more, one ``# fit`` line
for each set-up call, and ``# setup_unnamed_s``: what of those calls'
``step.dispatch`` no build row names.

A program without these rows (a checkout from before them) gives every
reader here None, and the harness leaves the metric out of the line.
"""

SLOW_ROW_S = 0.5
KEY = "builds.account"      # where the run's context keeps what was read


def union_ns(rows, stage=None):
    """Nanoseconds the rows (dicts) cover, of one stage or of all."""
    total = upto = 0
    for r in sorted((r for r in rows if stage in (None, r["stage"])),
                    key=lambda r: r["wall_ns"]):
        end = r["wall_ns"] + r["dur_ns"]
        if end > upto:
            total += end - max(r["wall_ns"], upto)
            upto = end
    return total


def split(fits, builds, steps):
    """The rows of a run (dicts, oldest first) -> the set-up's: the ``fit``
    calls before the last one, the build rows that start before its entry,
    those calls' step rows; None without a ``fit`` call.  ``steps`` is None
    where the ring no longer holds every step row of those calls."""
    if not fits:
        return None
    window = fits[-1]
    calls = fits[:-1]
    numbers = {f["fit"] for f in calls}
    mine = [r for r in steps if r["fit"] in numbers]
    if len(mine) != sum(f["iterations"] for f in calls):
        mine = None
    return {"window": window, "fits": calls, "steps": mine,
            "builds": [b for b in builds
                       if b["wall_ns"] < window["wall_ns"]],
            "later": [b for b in builds
                      if b["wall_ns"] >= window["wall_ns"]]}


def closure(acc):
    """Where the set-up calls' seconds went, by name: their entries and
    exits, each phase of their step rows, and of ``step.dispatch`` what the
    build rows inside those calls' iterations cover; the rest of it is
    ``unnamed_s``.  None without every step row."""
    if acc["steps"] is None:
        return None
    phases = {}
    for r in acc["steps"]:
        for k, v in r.items():
            if k.startswith("step."):
                phases[k] = phases.get(k, 0) + v
    looping = {f["fit"]: f["wall_ns"] + f["enter_ns"] for f in acc["fits"]}
    inside = [b for b in acc["builds"] if b["fit"] in looping]
    in_steps = [b for b in inside if b["wall_ns"] >= looping[b["fit"]]]
    out = {"fit_s": sum(f["total_ns"] for f in acc["fits"]) / 1e9,
           "entry_exit_s": entry_exit_ns(acc["fits"]) / 1e9,
           "builds_in_entry_s": (union_ns(inside) - union_ns(in_steps)) / 1e9,
           "builds_in_steps_s": union_ns(in_steps) / 1e9}
    out.update({k.replace("step.", "") + "_s": v / 1e9
                for k, v in phases.items()})
    out["unnamed_s"] = out.get("dispatch_s", 0.0) - out["builds_in_steps_s"]
    return out


def entry_exit_ns(fits):
    return sum(f["enter_ns"] + f["between_ns"] + f["exit_ns"] for f in fits)


def _say(acc, ctx):
    for when, rows in (("setup", acc["builds"]), ("later", acc["later"])):
        for b in rows:
            if b["dur_ns"] >= SLOW_ROW_S * 1e9:
                print(f"# build fit={b['fit']} fun={b['fun']} "
                      f"stage={b['stage']} s={b['dur_ns'] / 1e9:.3f} "
                      f"cache={b['cache']} when={when}", flush=True)
    for f in acc["fits"]:
        print(f"# fit fit={f['fit']} s={f['total_ns'] / 1e9:.3f} "
              f"enter_s={f['enter_ns'] / 1e9:.3f} "
              f"steps_s={f['steps_ns'] / 1e9:.3f} "
              f"exit_s={(f['between_ns'] + f['exit_ns']) / 1e9:.3f} "
              f"iterations={f['iterations']}", flush=True)
    named = closure(acc)
    if named is not None:
        print(f"# setup_unnamed_s={named.pop('unnamed_s'):.3f} " + " ".join(
            f"{k}={v:.3f}" for k, v in named.items()), flush=True)
    warm = ctx.get("setup", {}).get("warm_s")
    if warm is not None:
        print(f"# setup_benchmark_own_s="
              f"{warm - sum(f['total_ns'] for f in acc['fits']) / 1e9:.3f} "
              f"(setup.warm_s less the fit calls inside it)", flush=True)


def account(ctx):
    """The set-up's rows from the process tracer, read once a run (and said
    once in its log); None where the program writes none."""
    if KEY in ctx:
        return ctx[KEY]
    ctx[KEY] = None
    from dt_tpu.obs import trace
    tracer = trace.tracer()
    fields = [getattr(trace, name, None) for name in
              ("FIT_ROW_FIELDS", "BUILD_ROW_FIELDS", "STEP_ROW_FIELDS")]
    if None in fields or not hasattr(tracer, "fit_rows") \
            or not hasattr(tracer, "build_rows"):
        return None
    fits, builds, steps = (
        [dict(zip(f, r)) for r in rows] for f, rows in zip(
            fields, (tracer.fit_rows(), tracer.build_rows(),
                     tracer.step_rows())))
    ctx[KEY] = acc = split(fits, builds, steps)
    if acc is not None:
        _say(acc, ctx)
    return acc


def stage_s(ctx, m):
    """Seconds of the set-up in ``args.stage`` (trace, lower, backend)."""
    acc = account(ctx)
    return None if acc is None else \
        union_ns(acc["builds"], m["args"]["stage"]) / 1e9


def cache_hit_pct(ctx, m):
    """The share (%) of the set-up's backend stages that the persistent
    cache served."""
    acc = account(ctx)
    said = [b["cache"] for b in (acc["builds"] if acc else ())
            if b["stage"] == "backend"]
    return 100.0 * said.count("hit") / len(said) if said else None


def fit_s(ctx, m):
    acc = account(ctx)
    return None if acc is None else \
        sum(f["total_ns"] for f in acc["fits"]) / 1e9


def fit_entry_exit_s(ctx, m):
    acc = account(ctx)
    return None if acc is None else entry_exit_ns(acc["fits"]) / 1e9


def first_steps_wait_s(ctx, m):
    """``step.fetch`` of the set-up calls' step rows: the host waiting for
    the device to run the compared steps."""
    acc = account(ctx)
    if acc is None or acc["steps"] is None:
        return None
    return sum(r["step.fetch"] for r in acc["steps"]) / 1e9
