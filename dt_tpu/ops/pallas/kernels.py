"""Pallas TPU batch-norm kernels (the flash attention is in
``attention.py``).

Reference target (SURVEY.md §7 translation table): fused BN + activation
epilogue (``src/operator/nn/batch_norm.cu:1``; cuDNN fused BN-ReLU).  The
2-bit quantizer and the LSTM pointwise cell the reference also hand-wrote
have no kernel here: on the chip the fused XLA quantizer was faster and the
LSTM cell tied (``PALLAS_TPU_r02.jsonl``), so ``parallel/compression.py``
and ``ops/rnn.py`` are those paths.

Each kernel has the same semantics as its jnp oracle in ``dt_tpu.ops`` and
is tested against it in interpreter mode (CPU) and compiled mode (TPU).
``interpret`` defaults to True off-TPU.

Design notes: the kernels are VPU elementwise work tiled as (rows x
128-lane) blocks; the convolutions that FEED them stay in XLA where the MXU
scheduling is already optimal — fusing the epilogue is the part XLA
sometimes leaves on the table.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# ---------------------------------------------------------------------------
# Fused BN (+ optional ReLU) inference epilogue
# ---------------------------------------------------------------------------


def _bn_act_kernel(x_ref, scale_ref, bias_ref, out_ref, *, relu: bool):
    # scale/bias are precomputed (gamma*rsqrt(var+eps), beta - mean*scale):
    # one multiply-add per element, then the activation — a single VPU pass.
    y = x_ref[:] * scale_ref[:] + bias_ref[:]
    if relu:
        y = jnp.maximum(y, 0.0)
    out_ref[:] = y


def fused_bn_inference(x: jax.Array, gamma: jax.Array, beta: jax.Array,
                       mean: jax.Array, var: jax.Array, *,
                       eps: float = 1e-5, relu: bool = False,
                       block_rows: int = 256,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Inference-mode BN (+ReLU) over the trailing channel axis.

    ``x``: (..., C) any leading shape.  Equivalent to
    ``dt_tpu.ops.nn.batch_norm(training=False)`` (+ relu).
    """
    if interpret is None:
        interpret = _default_interpret()
    orig_shape = x.shape
    c = x.shape[-1]
    x2 = x.reshape(-1, c)
    n = x2.shape[0]
    if n == 0:
        return x

    scale = (gamma * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    bias = (beta - mean * gamma * jax.lax.rsqrt(var + eps)).astype(x.dtype)

    rows = min(block_rows, n)
    padded = _round_up(n, rows)
    if padded != n:
        x2 = jnp.pad(x2, ((0, padded - n), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_bn_act_kernel, relu=relu),
        out_shape=jax.ShapeDtypeStruct((padded, c), x.dtype),
        grid=(padded // rows,),
        in_specs=[
            pl.BlockSpec((rows, c), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((c,), lambda i: (0,), memory_space=pltpu.VMEM),
            pl.BlockSpec((c,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rows, c), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(x2, scale, bias)
    return out[:n].reshape(orig_shape)


# ---------------------------------------------------------------------------
# Fused BN TRAINING step (stats + normalize in two VMEM passes)
# ---------------------------------------------------------------------------


def _bn_partials_kernel(x_ref, sum_ref, sumsq_ref):
    # Mosaic requires the last two block dims to tile (8, 128) or equal
    # the array's, and a (1, c) block over (nblk, c) does neither — so
    # each block leaves 8 sublane-strided partial rows (row r sums block
    # rows r, r+8, ...: whole-vreg adds, no cross-sublane reduce) and the
    # wrapper's sum over all rows finishes the reduction
    x = x_ref[:].astype(jnp.float32)
    x = x.reshape(x.shape[0] // 8, 8, x.shape[1])
    sum_ref[:] = jnp.sum(x, axis=0)
    sumsq_ref[:] = jnp.sum(x * x, axis=0)


def _bn_train_fwd_impl(x, gamma, beta, running_mean, running_var,
                       momentum, eps, block_rows, interpret):
    if interpret is None:
        interpret = _default_interpret()
    orig_shape = x.shape
    c = x.shape[-1]
    x2 = x.reshape(-1, c)
    n = x2.shape[0]
    rows = _round_up(min(block_rows, n), 8)
    padded = _round_up(n, rows)
    x2p = jnp.pad(x2, ((0, padded - n), (0, 0))) if padded != n else x2

    # pass 1: per-block partial sums (padding rows are zeros -> harmless;
    # the divide uses the REAL row count)
    nblk = padded // rows
    sums, sumsqs = pl.pallas_call(
        _bn_partials_kernel,
        out_shape=(jax.ShapeDtypeStruct((nblk * 8, c), jnp.float32),
                   jax.ShapeDtypeStruct((nblk * 8, c), jnp.float32)),
        grid=(nblk,),
        in_specs=[pl.BlockSpec((rows, c), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec((8, c), lambda i: (i, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((8, c), lambda i: (i, 0),
                                memory_space=pltpu.VMEM)),
        interpret=interpret,
    )(x2p)
    mean = jnp.sum(sums, axis=0) / n
    # E[x^2] - mean^2 cancels catastrophically in f32 for large-mean /
    # small-variance channels and can come out slightly NEGATIVE, which
    # NaNs the rsqrt below (this kernel is the default-on train path).
    # Clamp to 0: the true variance is >= 0 by definition.
    var = jnp.maximum(jnp.sum(sumsqs, axis=0) / n - mean * mean, 0.0)

    # pass 2: the same fused scale/bias VMEM pass as the eval kernel
    inv = jax.lax.rsqrt(var + eps)
    scale = (gamma * inv).astype(x.dtype)
    bias = (beta - mean * gamma * inv).astype(x.dtype)
    y = pl.pallas_call(
        functools.partial(_bn_act_kernel, relu=False),
        out_shape=jax.ShapeDtypeStruct((padded, c), x.dtype),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((rows, c), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((c,), lambda i: (0,), memory_space=pltpu.VMEM),
            pl.BlockSpec((c,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rows, c), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(x2p, scale, bias)[:n].reshape(orig_shape)

    # running-stat update is a stop-gradient side channel (reference
    # batch_norm-inl.h convention; stats are aux params)
    new_mean = running_mean * momentum + mean * (1.0 - momentum)
    new_var = running_var * momentum + var * (1.0 - momentum)
    return y, new_mean, new_var, mean, var


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def fused_bn_train(x: jax.Array, gamma: jax.Array, beta: jax.Array,
                   running_mean: jax.Array, running_var: jax.Array,
                   momentum: float = 0.9, eps: float = 1e-5,
                   block_rows: int = 256,
                   interpret: Optional[bool] = None):
    """TRAINING-mode BN over the trailing channel axis, Pallas-fused.

    Two VMEM passes (block-partial sums -> fused normalize), the same
    split the reference's ``src/operator/nn/batch_norm.cu`` train kernel
    makes.  Semantics match ``dt_tpu.ops.nn.batch_norm(training=True)``:
    returns ``(y, new_running_mean, new_running_var)`` with the
    reference's ``moving*m + batch*(1-m)`` update.

    Differentiable via a custom VJP: backward recomputes x_hat from the
    saved (x, mean, var) with plain jnp (XLA fuses the reductions), the
    standard BN backward.  Running-stat outputs are stop-gradient except
    for their ``momentum * old`` passthrough.
    """
    y, new_mean, new_var, _, _ = _bn_train_fwd_impl(
        x, gamma, beta, running_mean, running_var, momentum, eps,
        block_rows, interpret)
    return y, new_mean, new_var


def _bn_train_fwd(x, gamma, beta, running_mean, running_var, momentum,
                  eps, block_rows, interpret):
    y, new_mean, new_var, mean, var = _bn_train_fwd_impl(
        x, gamma, beta, running_mean, running_var, momentum, eps,
        block_rows, interpret)
    return (y, new_mean, new_var), (x, gamma, mean, var)


def _bn_train_bwd(momentum, eps, block_rows, interpret, res, cts):
    x, gamma, mean, var = res
    gy, gmean, gvar = cts
    axes = tuple(range(x.ndim - 1))
    n = 1
    for a in axes:
        n *= x.shape[a]
    x32 = x.astype(jnp.float32)
    gy32 = gy.astype(jnp.float32)
    inv = jax.lax.rsqrt(var + eps)
    x_hat = (x32 - mean) * inv
    dbeta = jnp.sum(gy32, axis=axes)
    dgamma = jnp.sum(gy32 * x_hat, axis=axes)
    dx = (gamma * inv / n) * (n * gy32 - dbeta - x_hat * dgamma)
    # running stats: only the momentum*old passthrough carries gradient
    d_rm = gmean * momentum
    d_rv = gvar * momentum
    return (dx.astype(x.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype), d_rm, d_rv)


fused_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)
