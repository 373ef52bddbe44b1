"""Readers of what the attention layers under a window counted in the
window's ``fit`` call (``Module.step_counters``, read through the job: by
layer, over the steps flushed, the sum of each column of
``models.routed_lm.WIN_COUNTERS``: the pairs a head's band needs, the pairs
of the tiles the forward and the backward kernel run, each kernel's tiles
run and the tiles ``causal`` alone would run).  The counts are static (the
rule's own sums); they reach the host with the metric's statistics, and a
program without them (the parent) reads as nothing."""


def _layers(ctx):
    counted = getattr(getattr(ctx["job"], "mod", None), "step_counters",
                      None)
    if not counted:
        return None
    return [c for name, c in sorted(counted.items())
            if name.endswith("/win") and c["steps"]] or None


def needed_pairs_pct(ctx, m):
    """Pairs the band needs over the pairs of the tiles the two kernels
    run, both passes, over the window's steps and layers."""
    layers = _layers(ctx)
    if layers is None:
        return None
    return 100.0 * sum(2 * c["sum"][0] for c in layers) \
        / sum(c["sum"][1] + c["sum"][2] for c in layers)


def tiles_run_pct(ctx, m):
    """Tiles the two flash kernels run under the band over the tiles
    ``causal`` alone would run with the same tiles, over the window's steps
    and layers."""
    layers = _layers(ctx)
    if layers is None:
        return None
    return 100.0 * sum(c["sum"][3] + c["sum"][5] for c in layers) \
        / sum(c["sum"][4] + c["sum"][6] for c in layers)
