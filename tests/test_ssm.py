"""``ops/ssm.py`` at a small size on the CPU: the chunked scan against the
one-position recurrence of the benchmark's plain reference
(``benchmark/configs/granite-4.0-h-micro_reference.py``), forward and
gradients; the causal convolution; the gated norm; grouped-query attention
through the flash kernel (interpret mode) against plain softmax.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from dt_tpu.models import hybrid_lm
from dt_tpu.ops import ssm

from hybrid_small import REF, SMALL


def _scan_inputs(length, groups, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    b, h, p, n = 2, 4, 3, 5
    return (jax.random.normal(ks[0], (b, length, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, length, h))),
            -jnp.exp(jax.random.normal(ks[2], (h,))),
            jax.random.normal(ks[3], (b, length, groups, n)),
            jax.random.normal(ks[4], (b, length, groups, n)))


def _one_position_at_a_time(x, dt, a, b, c):
    """The reference's recurrence, a sequence at a time."""
    return jax.vmap(lambda xs, ds, bs, cs: REF.recurrence(
        xs, ds, jnp.log(-a), bs, cs))(x, dt, b, c)


@pytest.mark.parametrize("length,chunk,groups", [
    (16, 4, 1),     # the chunk divides the length
    (13, 4, 2),     # it does not: the tail is padded with dt = 0
    (12, 12, 1),    # one chunk of the whole length
    (10, 256, 1),   # a chunk longer than the sequence
    (24, 8, 4)])    # a group a head
def test_chunked_scan_is_the_recurrence(length, chunk, groups):
    args = _scan_inputs(length, groups, seed=length)
    weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def both(f):
        """The scan's output and the gradient of every input, one program."""
        return jax.jit(lambda *t: (f(*t), jax.grad(
            lambda *u: jnp.sum(f(*u) * weights), argnums=(0, 1, 2, 3, 4))(
                *t)))(*args)

    got, grads = both(lambda *t: ssm.ssd_scan(*t, chunk=chunk))
    want, want_grads = both(_one_position_at_a_time)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for name, g, w in zip(("x", "dt", "a", "b", "c"), grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4, err_msg=name)


def test_causal_conv_sees_only_the_past():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    bias = jax.random.normal(jax.random.PRNGKey(2), (6,))
    got = ssm.causal_conv1d(x, w, bias)
    want = jnp.stack([REF._conv(seq, w, bias) for seq in x])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # by hand: position 1 sees positions 0 and 1 under the last two taps
    np.testing.assert_allclose(
        got[:, 1], x[:, 0] * w[2] + x[:, 1] * w[3] + bias, rtol=1e-5,
        atol=1e-6)
    later = x.at[:, 5:].add(1.0)
    np.testing.assert_array_equal(ssm.causal_conv1d(later, w, bias)[:, :5],
                                  got[:, :5])


def test_gated_norm_normalises_after_the_gate():
    y = jax.random.normal(jax.random.PRNGKey(0), (3, 16))
    z = jax.random.normal(jax.random.PRNGKey(1), (3, 16))
    scale = jnp.linspace(0.5, 1.5, 16)
    got = ssm.gated_rms_norm(y, z, scale, 1e-5)
    gated = y * jax.nn.silu(z)
    want = gated / jnp.sqrt(jnp.mean(gated ** 2, -1, keepdims=True) + 1e-5) \
        * scale
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("scale", [1 / 64, None])
def test_grouped_query_attention_through_the_flash_kernel(scale):
    """Four query heads over two key-value heads through the Pallas kernel
    (interpret mode here) against plain softmax of ``scale * q k^T``; the
    sequence is padded to the kernel's block."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 72, 32)) * 3.0

    def attn(kind):
        return hybrid_lm.GroupedQueryAttention(
            num_heads=4, num_kv_heads=2, head_dim=8,
            scale=8 ** -0.5 if scale is None else scale, attention=kind)

    params = attn(None).init(jax.random.PRNGKey(1), x)
    assert params["params"]["k_proj"]["kernel"].shape == (32, 16)
    got, want = (jax.jit(attn(kind).apply)(params, x)
                 for kind in ("flash", None))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # against the reference's, a key-value head with its query heads at a
    # time, from the same four matrices
    p = params["params"]
    blk = {"wq": p["q_proj"]["kernel"], "wk": p["k_proj"]["kernel"],
           "wv": p["v_proj"]["kernel"], "wo": p["o_proj"]["kernel"]}
    cfg = {**SMALL, "attention_multiplier": 8 ** -0.5 if scale is None
           else scale}
    ref = jnp.stack([REF.attention_mixer(seq, blk, cfg, lambda a: a)
                     for seq in x])
    np.testing.assert_allclose(want, ref, rtol=2e-4, atol=2e-5)
    grads = [jax.jit(jax.grad(
        lambda pr, kind=kind: jnp.sum(attn(kind).apply(pr, x) ** 2)))(params)
        for kind in ("flash", None)]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4),
        *grads)
