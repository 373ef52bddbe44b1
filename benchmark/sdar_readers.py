"""Readers of what the routed expert layers counted in the window's ``fit``
call (``Module.step_counters``, read through the job: by layer, over the
steps flushed, the sum and the largest step's value of each column: the
assignments to each held expert, their sum, those that found no room in the
buffer, all the layer made).  The counters reach the host with the metric's
statistics; a program without them (the parent) reads as nothing."""


def _layers(ctx):
    counted = getattr(getattr(ctx["job"], "mod", None), "step_counters",
                      None)
    if not counted:
        return None
    return [c for name, c in sorted(counted.items())
            if name.endswith("/moe") and c["steps"]] or None


def held_load_share_pct(ctx, m):
    """Assignments to held experts over all made (``T x k``), over the
    window's steps and layers: 100 x held / published is even."""
    layers = _layers(ctx)
    if layers is None:
        return None
    return 100.0 * sum(c["sum"][-3] for c in layers) \
        / sum(c["sum"][-1] for c in layers)


def fullest_over_mean_load(ctx, m):
    """The fullest held expert's assignments over the held experts' mean,
    over the window's steps: the layer where it is largest."""
    layers = _layers(ctx)
    if layers is None:
        return None
    return max(c["sum"][:-3].max() / max(c["sum"][:-3].mean(), 1e-9)
               for c in layers)


def buffer_fill_pct(ctx, m):
    """Rows of the static buffer that held an assignment, over
    ``buffer_rows``: the mean over the window's steps and layers."""
    layers = _layers(ctx)
    if layers is None:
        return None
    rows = ctx["cfg"]["buffer_rows"]
    placed = sum(c["sum"][-3] - c["sum"][-2] for c in layers)
    return 100.0 * placed / (rows * sum(c["steps"] for c in layers))


def overflow_assignments(ctx, m):
    """Assignments to held experts dropped for want of room, over the whole
    window and every layer: 0, or the run's losses are not the model's."""
    layers = _layers(ctx)
    if layers is None:
        return None
    return float(sum(c["sum"][-2] for c in layers))
