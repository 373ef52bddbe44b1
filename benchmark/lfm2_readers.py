"""Readers for the decoder of gated short convolutions: device time under
any one of several scopes, and what the routed layers under a selection bias
counted in the window's ``fit`` call (``Module.step_counters``, read through
the job: by layer, over the steps flushed, the sum of each column of
``parallel.moe.BIAS_COUNTERS``: the picks that ``top_k(scores)`` alone would
not have made, and all the picks).  The counts reach the host with the
metric's statistics; a program without them (the parent) reads as nothing."""

import readers


def scopes_ms_per_step(ctx, m):
    """``readers:scope_ms_per_step`` summed over the scopes of
    ``args.any_of`` (each a string a path holds; the scopes are beside one
    another, so no operation is under two), each without ``args.lacks``;
    None where the trace has no scopes or none of these."""
    args = m["args"]
    parts = [readers.scope_ms_per_step(ctx, {"args": {
        "holds": [scope], "lacks": args.get("lacks", ())}})
        for scope in args["any_of"]]
    if None in parts or not any(parts):
        return None
    return sum(parts)


def bias_moved_assignments_pct(ctx, m):
    """Assignments the selection bias moved over all made, over the window's
    steps and layers."""
    counted = getattr(getattr(ctx["job"], "mod", None), "step_counters",
                      None) or {}
    layers = [c for name, c in sorted(counted.items())
              if name.endswith("/moe_bias") and c["steps"]]
    if not layers:
        return None
    return 100.0 * sum(c["sum"][0] for c in layers) \
        / sum(c["sum"][1] for c in layers)
