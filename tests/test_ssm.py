"""``ops/ssm.py`` at a small size on the CPU: the chunked scan against the
one-position recurrence of the benchmark's plain reference
(``benchmark/configs/granite-4.0-h-micro_reference.py``), forward and
gradients, through the XLA body at widths in the units and through the
Pallas kernels of ``ops/pallas/ssd.py`` (interpret mode) at the narrowest
widths they take; the causal convolution; the gated norm; grouped-query
attention through the flash kernel (interpret mode) against plain softmax.
"""

import logging

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from dt_tpu.models import hybrid_lm
from dt_tpu.obs import metrics as obs_metrics
from dt_tpu.ops import ssm
from dt_tpu.ops.pallas import ssd

from hybrid_small import REF, SMALL


def _scan_inputs(length, groups, seed, b=2, h=4, p=3, n=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (b, length, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, length, h))),
            -jnp.exp(jax.random.normal(ks[2], (h,))),
            jax.random.normal(ks[3], (b, length, groups, n)),
            jax.random.normal(ks[4], (b, length, groups, n)))


def _one_position_at_a_time(x, dt, a, b, c):
    """The reference's recurrence, a sequence at a time."""
    return jax.vmap(lambda xs, ds, bs, cs: REF.recurrence(
        xs, ds, jnp.log(-a), bs, cs))(x, dt, b, c)


def _value_and_gradients(f, args, weights):
    """The scan's output and the gradient of every input, one program."""
    return jax.jit(lambda *t: (f(*t), jax.grad(
        lambda *u: jnp.sum(f(*u).astype(jnp.float32) * weights),
        argnums=(0, 1, 2, 3, 4))(*t)))(*args)


@pytest.mark.parametrize("length,chunk,groups", [
    (16, 4, 1),     # the chunk divides the length
    (13, 4, 2),     # it does not: the tail is padded with dt = 0
    (12, 12, 1),    # one chunk of the whole length
    (10, 256, 1),   # a chunk longer than the sequence
    (24, 8, 4)])    # a group a head
def test_chunked_scan_is_the_recurrence(length, chunk, groups):
    args = _scan_inputs(length, groups, seed=length)
    weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    got, grads = _value_and_gradients(
        lambda *t: ssm.ssd_scan(*t, chunk=chunk), args, weights)
    want, want_grads = _value_and_gradients(_one_position_at_a_time, args,
                                            weights)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for name, g, w in zip(("x", "dt", "a", "b", "c"), grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4, err_msg=name)


ORACLES = {"recurrence": _one_position_at_a_time,
           "xla": lambda *t: ssm.ssd_scan_xla(*t, chunk=128)}


@pytest.mark.parametrize("oracle", sorted(ORACLES))
@pytest.mark.parametrize("length,groups,dtype", [
    (128, 1, "float32"),     # one chunk
    (384, 1, "float32"),     # several: the state is carried
    (200, 2, "float32"),     # a tail padded with dt = 0; a group a head pair
    (256, 2, "float32"),
    (256, 1, "bfloat16"),    # bfloat16 operands against the float32 oracle
    (200, 2, "bfloat16")])
def test_the_kernels_are_the_recurrence(length, groups, dtype, oracle):
    """``ssd_scan`` at the narrowest widths the kernels take (heads of 64,
    states of 128, chunks of 128; the Pallas interpreter here): the value
    and all five gradients against the one-position recurrence and against
    ``ssd_scan_xla``, both in float32."""
    args = _scan_inputs(length, groups, seed=length, b=2, h=2 * groups,
                        p=64, n=128)
    assert ssd.head_block(2 * groups, groups, 64, 128, 128, 4) == 2
    weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    cast = lambda t: [v.astype(dtype) if v.ndim == 4 else v  # noqa: E731
                      for v in t]
    got, grads = _value_and_gradients(
        lambda *t: ssm.ssd_scan(*cast(t), chunk=128), args, weights)
    want, want_grads = _value_and_gradients(ORACLES[oracle], args, weights)
    assert got.dtype == jnp.dtype(dtype)
    if dtype == "float32":
        # test_chunked_scan_is_the_recurrence's tolerances, the absolute
        # ones times the array's largest element (sums of 128 terms here)
        top = lambda w: float(jnp.max(jnp.abs(w)))  # noqa: E731
        np.testing.assert_allclose(got, want, rtol=2e-4,
                                   atol=2e-5 * top(want))
        for name, g, w in zip(("x", "dt", "a", "b", "c"), grads, want_grads):
            np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4 * top(w),
                                       err_msg=name)
        return
    # eight bits of mantissa in every operand: a hundredth of each array's
    # largest element (a wrong term or a dropped chunk is of order one)
    for name, g, w in zip(("y", "x", "dt", "a", "b", "c"), (got, *grads),
                          (want, *want_grads)):
        gap = float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                    / jnp.max(jnp.abs(w)))
        assert gap < 2e-2, (name, gap)


def test_a_shape_off_the_lane_tiles_falls_back_and_says_so(caplog):
    """Which path a call took is recorded at trace time: a ``# ssd_scan``
    debug line with the head block (None: ``ssd_scan_xla``) and the gauges
    ``ssd.kernel_calls`` and ``ssd.xla_calls``."""
    obs_metrics.set_enabled(True)
    try:
        obs_metrics.registry().clear()
        before = list(ssd._calls)
        toy = _scan_inputs(16, 1, seed=0)           # heads of 3, states of 5
        wide = _scan_inputs(128, 1, seed=0, b=1, h=2, p=64, n=128)
        with caplog.at_level(logging.DEBUG, logger="dt_tpu"):
            got = ssm.ssd_scan(*toy, chunk=4)
            np.testing.assert_array_equal(
                got, ssm.ssd_scan_xla(*toy, chunk=4))
            gauges = {g[0]: g[2] for g in obs_metrics.registry()
                      .gauges_export() if g[0].startswith("ssd.")}
            assert gauges == {"ssd.kernel_calls": before[0],
                              "ssd.xla_calls": before[1] + 1}
            ssm.ssd_scan(*wide, chunk=128)
        lines = [r.getMessage() for r in caplog.records
                 if r.getMessage().startswith("# ssd_scan")]
        assert lines == [
            "# ssd_scan b=2 l=16 h=4 p=3 g=1 n=5 q=4 dtype=float32 hb=None",
            "# ssd_scan b=1 l=128 h=2 p=64 g=1 n=128 q=128 dtype=float32 "
            "hb=2"]
        gauges = {g[0]: g[2] for g in obs_metrics.registry().gauges_export()
                  if g[0].startswith("ssd.")}
        assert gauges == {"ssd.kernel_calls": before[0] + 1,
                          "ssd.xla_calls": before[1] + 1}
    finally:
        obs_metrics.set_enabled(None)
        obs_metrics.registry().clear()


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
