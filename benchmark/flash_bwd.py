"""The flash attention's backward kernel (PR 31): what the mathematics needs
of it, from shapes alone, and its roofline share in either language-model
cell (the kernel's events are named ``flash_bwd`` in both)."""

import readers


def flash_backward_ops_bytes(batch, heads, seq, head_dim, itemsize):
    """Causal attention backward for (batch, seq, heads, head_dim): the five
    products over the lower triangle (the scores again, dv, dp, dk, dq),
    counted once however many passes a kernel makes; q, k, v, the output and
    its gradient read and dq, dk, dv written once, plus one float32
    log-sum-exp per row."""
    ops = batch * heads * 5 * (2 * seq * seq * head_dim) // 2
    nbytes = batch * heads * seq * (8 * head_dim * itemsize + 4)
    return ops, nbytes


def roofline_pct(ctx, m):
    """``readers:kernel_roofline_pct`` for a kernel that two configurations
    with different keys call: the heads are the first of ``args.heads`` the
    configuration has."""
    args, cfg = m["args"], ctx["cfg"]
    heads = next(cfg[k] for k in args["heads"] if k in cfg)
    return readers.kernel_roofline_pct(
        ctx, {**m, "args": {**args, "shape": {**args["shape"],
                                              "heads": heads}}})
