"""What the flash kernels' tests share since crossed tiles are walked in
sub-blocks (``attention._Edge``): inputs that would show one skipped
allowed sub-block, the two kernels against a dense masked softmax at a
given ``sub``, and the walk's sums counted one sub-block at a time.  No
test of its own."""

import numpy as np
import jax
import jax.numpy as jnp

from dt_tpu.obs import metrics as obs_metrics
from dt_tpu.ops.pallas import attention as attn


def spiky_inputs(allowed, d: int, seed: int = 0, heads: int = 1):
    """``(q, k, v, do)`` of ``(heads, S, d)`` float32 for the rule's
    ``allowed`` (S, S) matrix: small random scores, and each query's only
    large ones at its first and its last allowed key (the ends of its row
    of every sub-block walk), so that a kernel that skipped a sub-block
    holding either is off by a large part of the values."""
    allowed = np.asarray(allowed)
    s = allowed.shape[0]
    rng = np.random.RandomState(seed)
    codes = rng.randn(heads, s, d)
    codes /= np.linalg.norm(codes, axis=-1, keepdims=True)
    first = allowed.argmax(axis=1)
    last = s - 1 - allowed[:, ::-1].argmax(axis=1)
    q = 12.0 * d ** 0.5 * (codes[:, first] + codes[:, last]) \
        + 0.3 * rng.randn(heads, s, d)
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return f32(q), f32(codes), f32(rng.randn(heads, s, d)), \
        f32(rng.randn(heads, s, d))


def dense(q, k, v, allowed):
    """The oracle over ``(heads, S, d)``: a softmax over the allowed
    keys."""
    scores = jnp.einsum("hqd,hkd->hqk", q, k) * q.shape[-1] ** -0.5
    probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,hkd->hqd", probs, v)


def kernels_match(allowed, d: int, sub, *, oracle=dense, mask=None,
                  causal=False, block=None, selection=None, atol=3e-5):
    """Forward, dq, dk and dv of the two Pallas calls (interpret mode) at
    sub-blocks of ``sub`` against ``oracle(q, k, v, allowed)`` on the spiky
    inputs; ``block`` forces square tiles of that side on both passes."""
    q, k, v, do = spiky_inputs(allowed, d)
    kw = dict(scale=d ** -0.5, causal=causal, interpret=True, mask=mask,
              selection=selection, sub=sub, block_q=block, block_k=block)
    out, lse = attn._flash_fwd_pallas(q, k, v, **kw)
    want, wants, gap = _oracle_side(oracle, q, k, v, do, np.asarray(allowed))
    np.testing.assert_allclose(out, want, atol=atol, err_msg="forward")
    grads = attn._flash_bwd_pallas(q, k, v, out, lse, do, **kw)
    for name, got, ref in zip(("dq", "dk", "dv"), grads, wants):
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(got, ref, atol=atol * max(scale, 1.0),
                                   err_msg=name)
    # the inputs do show a sub-block left out: the first allowed key's
    # value is a large part of every row's result
    assert float(gap) > 1e-2


def _oracle_side(oracle, q, k, v, do, allowed):
    """What ``kernels_match`` holds the kernels to, in one jitted program:
    the oracle's output, its three gradients under the cotangent ``do``, and
    how far the dense result moves, at the least, in the rows with more than
    one allowed key when each row's first allowed key is struck out."""
    without = np.array(allowed)
    rows = np.flatnonzero(without.sum(axis=1) > 1)
    without[rows, without[rows].argmax(axis=1)] = False

    allowed, without = jnp.asarray(allowed), jnp.asarray(without)

    @jax.jit
    def run(q, k, v, do):
        want, vjp = jax.vjp(lambda *a: oracle(*a, allowed), q, k, v)
        gap = jnp.abs(dense(q, k, v, without)
                      - dense(q, k, v, allowed))[:, rows]
        return want, vjp(do), gap.max(axis=-1).min()
    return run(q, k, v, do)


def walked_by_hand(allowed_fn, s: int, block: int, sub: int):
    """``(run, crossed, computed)`` as ``attention.computed_tiles`` gives
    them, counted from ``allowed_fn(q_pos, k_pos)`` one ``block x block``
    tile at a time: a tile no pair of which is allowed does not run, one
    all of whose pairs are is computed whole, and any other by the
    sub-blocks of ``sub`` a side that hold an allowed pair.  With them the
    allowed pairs in all."""
    n, m = s // block, block // sub
    run = crossed = pairs = sub_blocks = 0
    for qi in range(n):
        q_pos = (qi * block + np.arange(block))[:, None]
        for ki in range(n):
            tile = np.asarray(allowed_fn(
                q_pos, (ki * block + np.arange(block))[None, :]))
            if not tile.any():
                continue
            run += 1
            pairs += int(tile.sum())
            if not tile.all():
                crossed += 1
                sub_blocks += int(tile.reshape(m, sub, m, sub).any(
                    axis=(1, 3)).sum())
    return run, crossed, run - crossed + sub_blocks / m ** 2, pairs


def pairs_computed_gauges(trace):
    """The ``flash.pairs_computed_pct`` and ``flash.bwd_pairs_computed_pct``
    gauges (name -> value) that calling ``trace()`` sets, with its ``#
    flash_*tiles`` notes made anew."""
    obs_metrics.set_enabled(True)
    try:
        obs_metrics.registry().clear()
        attn._note_tiles.cache_clear()
        attn._flash_fwd_pallas.clear_cache()
        attn._flash_bwd_pallas.clear_cache()
        trace()
        return {name: value for name, _, value in
                obs_metrics.registry().gauges_export()
                if name.endswith("pairs_computed_pct")}
    finally:
        obs_metrics.set_enabled(None)
        obs_metrics.registry().clear()
        attn._note_tiles.cache_clear()


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it, a kernel's
    own body apart."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub)


def out_and_grads(f, args, loss):
    """``f(*args)`` and the gradient of ``loss(f(*args))`` in every argument,
    from one jitted trace: the kernel under ``f`` is traced and compiled once
    for both, and an oracle runs as one program and not op by op."""
    def run(*a):
        out = f(*a)
        return loss(out), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        run, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return out, grads


def placed(**kw):
    """``flash_attention(**kw)`` over (B, S, H, D) arguments handed to it as
    the projections' arrays, q (B, S, H * D) and k, v (B, SK, KV * D) with
    ``heads=H``: the rank that takes the kernels' in-place layout.  The
    result viewed (B, S, H, D) again (a log-sum-exp beside it as it is)."""
    def run(q, k, v):
        flat = lambda t: t.reshape(*t.shape[:2], -1)  # noqa: E731
        out = attn.flash_attention(flat(q), flat(k), flat(v),
                                   heads=q.shape[2], **kw)
        if isinstance(out, tuple):
            return (out[0].reshape(q.shape),) + tuple(out[1:])
        return out.reshape(q.shape)
    return run
