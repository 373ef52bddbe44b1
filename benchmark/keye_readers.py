"""Readers of what the attention layers with an index counted in the
window's ``fit`` call (``Module.step_counters``, read through the job: by
layer, over the steps flushed, the sum of each column of
``models.routed_lm.DSA_COUNTERS``: pairs selected and causal pairs, tiles run
and tiles ``causal`` alone would run in the forward and in the backward
kernel, the layer's KL term in millionths).  The counters reach the host with
the metric's statistics; a program without them (the parent) reads as
nothing."""


def _layers(ctx):
    counted = getattr(getattr(ctx["job"], "mod", None), "step_counters",
                      None)
    if not counted:
        return None
    return [c for name, c in sorted(counted.items())
            if name.endswith("/dsa") and c["steps"]] or None


def selected_pairs_pct(ctx, m):
    """Pairs the selections held over the causal pairs, over the window's
    steps and layers: ``sum min(t + 1, k) / sum (t + 1)`` where every row
    selects exactly ``k`` (ties with the k-th score add to it)."""
    layers = _layers(ctx)
    if layers is None:
        return None
    return 100.0 * sum(c["sum"][0] for c in layers) \
        / sum(c["sum"][1] for c in layers)


def tiles_run_pct(ctx, m):
    """Tiles the two flash kernels ran over the tiles ``causal`` alone would
    run, over the window's steps and layers: 100 where every causal tile
    holds a selected pair."""
    layers = _layers(ctx)
    if layers is None:
        return None
    return 100.0 * sum(c["sum"][2] + c["sum"][4] for c in layers) \
        / sum(c["sum"][3] + c["sum"][5] for c in layers)


def indexer_kl(ctx, m):
    """The KL term as the objective has it: summed over the layers, the mean
    over the batch's positions, the mean over the window's steps."""
    layers = _layers(ctx)
    if layers is None:
        return None
    return sum(c["sum"][6] / c["steps"] for c in layers) \
        / (1e6 * ctx["traffic"]["batch"])
