"""Pallas TPU kernels for the paths the reference hand-wrote CUDA for.

Reference targets (SURVEY.md §7 translation table):
- fused BN + activation epilogue (``src/operator/nn/batch_norm.cu:1``; cuDNN
  fused BN-ReLU)
- 2-bit gradient quantize/dequantize (``src/kvstore/gradient_compression.cu``)
- fused LSTM cell pointwise stage (``cudnn_rnn-inl.h`` fused elementwise)

Each kernel has the same semantics as its jnp oracle in ``dt_tpu.ops`` /
``dt_tpu.parallel.compression`` and is tested against it in interpreter mode
(CPU) and compiled mode (TPU).  ``interpret`` defaults to True off-TPU.

Design notes: all kernels are VPU elementwise/pack work tiled as
(rows x 128-lane) blocks; the matmuls that FEED them (conv, gate projections)
stay in XLA where the MXU scheduling is already optimal — fusing the epilogue
is the part XLA sometimes leaves on the table.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

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


# ---------------------------------------------------------------------------
# 2-bit gradient compression
# ---------------------------------------------------------------------------

from dt_tpu.parallel.compression import CODES_PER_WORD as _CODES  # noqa: E402
# (same wire format as the numpy/jnp oracles in parallel.compression)


def _quant2_kernel(x_ref, packed_ref, resid_ref, *, threshold: float):
    x = x_ref[:]  # (W, 16) block of grad+residual
    codes = jnp.where(x >= threshold, jnp.uint32(1),
                      jnp.where(x <= -threshold, jnp.uint32(2),
                                jnp.uint32(0)))
    decoded = jnp.where(codes == 1, threshold,
                        jnp.where(codes == 2, -threshold, 0.0))
    resid_ref[:] = x - decoded.astype(x.dtype)
    # pack via an int32 sum: Mosaic has no unsigned reductions on real TPU
    # (interpret mode accepted uint32 — round-2 drive finding).  The 2-bit
    # fields are disjoint, so wrapping int32 addition is carry-free and
    # bit-identical to the uint32 sum; bitcast restores the wire dtype.
    shifts = jax.lax.broadcasted_iota(jnp.int32, codes.shape, 1) * 2
    packed_i32 = jnp.sum(codes.astype(jnp.int32) << shifts, axis=1,
                         dtype=jnp.int32, keepdims=True)
    packed_ref[:] = jax.lax.bitcast_convert_type(packed_i32, jnp.uint32)


def quantize_2bit(grad: jax.Array, residual: jax.Array,
                  threshold: float = 0.5, block_words: int = 512,
                  interpret: Optional[bool] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Pallas 2-bit quantize: same contract as
    ``dt_tpu.parallel.compression.quantize_2bit`` (flat grad+residual ->
    packed uint32 words + new residual)."""
    if interpret is None:
        interpret = _default_interpret()
    flat = (grad + residual).ravel()
    n = flat.shape[0]
    words = _round_up(n, _CODES) // _CODES
    wpad = _round_up(words, block_words)
    x = jnp.pad(flat, (0, wpad * _CODES - n)).reshape(wpad, _CODES)

    packed, resid = pl.pallas_call(
        functools.partial(_quant2_kernel, threshold=threshold),
        out_shape=(jax.ShapeDtypeStruct((wpad, 1), jnp.uint32),
                   jax.ShapeDtypeStruct((wpad, _CODES), flat.dtype)),
        grid=(wpad // block_words,),
        in_specs=[pl.BlockSpec((block_words, _CODES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec((block_words, 1), lambda i: (i, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((block_words, _CODES), lambda i: (i, 0),
                                memory_space=pltpu.VMEM)),
        interpret=interpret,
    )(x)
    new_residual = resid.ravel()[:n].reshape(grad.shape) \
        .astype(residual.dtype)
    return packed.ravel()[:words], new_residual


def _dequant2_kernel(packed_ref, out_ref, *, threshold: float):
    p = packed_ref[:]  # (W, 1) uint32
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (p.shape[0], _CODES), 1) * 2
    codes = (p >> shifts) & jnp.uint32(3)
    out_ref[:] = jnp.where(codes == 1, threshold,
                           jnp.where(codes == 2, -threshold, 0.0)
                           ).astype(out_ref.dtype)


def dequantize_2bit(packed: jax.Array, n: int, threshold: float = 0.5,
                    dtype=jnp.float32, block_words: int = 512,
                    interpret: Optional[bool] = None) -> jax.Array:
    if interpret is None:
        interpret = _default_interpret()
    words = packed.shape[0]
    wpad = _round_up(words, block_words)
    p = jnp.pad(packed, (0, wpad - words)).reshape(wpad, 1)
    out = pl.pallas_call(
        functools.partial(_dequant2_kernel, threshold=threshold),
        out_shape=jax.ShapeDtypeStruct((wpad, _CODES), dtype),
        grid=(wpad // block_words,),
        in_specs=[pl.BlockSpec((block_words, 1), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((block_words, _CODES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(p)
    return out.ravel()[:n]


# ---------------------------------------------------------------------------
# Fused LSTM cell pointwise stage
# ---------------------------------------------------------------------------


def _lstm_point_kernel(gates_ref, c_ref, h_out_ref, c_out_ref, *, hidden: int):
    g = gates_ref[:].astype(jnp.float32)  # (B, 4H) pre-activation
    i = jax.nn.sigmoid(g[:, 0 * hidden:1 * hidden])
    f = jax.nn.sigmoid(g[:, 1 * hidden:2 * hidden])
    gg = jnp.tanh(g[:, 2 * hidden:3 * hidden])
    o = jax.nn.sigmoid(g[:, 3 * hidden:4 * hidden])
    c_new = f * c_ref[:].astype(jnp.float32) + i * gg
    h_out_ref[:] = (o * jnp.tanh(c_new)).astype(h_out_ref.dtype)
    c_out_ref[:] = c_new.astype(c_out_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def lstm_pointwise(gates: jax.Array, c: jax.Array,
                   block_rows: int = 256,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Fused i/f/g/o activations + state update after the gate matmul.

    ``gates``: (B, 4H) = x@Wx + h@Wh + b; ``c``: (B, H).  Returns (h', c').
    Matches ``dt_tpu.ops.rnn.lstm_cell`` post-matmul math (gate order
    i,f,g,o).  One VMEM pass instead of ~10 separate HLO elementwise ops —
    the fusion cuDNN's fused LSTM did for the reference.

    Differentiable: a custom VJP recomputes the cheap activations on the
    backward pass (jnp ops, XLA-fused) so the fused cell trains — the
    rematerialize-activations strategy cuDNN's LSTM backward uses.
    """
    return _lstm_pointwise_fwd(gates, c, block_rows, interpret)[0]


def _lstm_pointwise_fwd(gates, c, block_rows, interpret):
    if interpret is None:
        interpret = _default_interpret()
    orig_gates = gates  # residual keeps the PRIMAL dtype for the cotangent
    gates = gates.astype(jnp.float32)  # nonlinearities read f32 pre-acts
    b, four_h = gates.shape
    hidden = four_h // 4
    # tile over batch so gates blocks fit VMEM at large B*H
    rows = min(block_rows, b)
    padded = _round_up(b, rows)
    gates_p, c_p = gates, c
    if padded != b:
        gates_p = jnp.pad(gates, ((0, padded - b), (0, 0)))
        c_p = jnp.pad(c, ((0, padded - b), (0, 0)))
    h_out, c_out = pl.pallas_call(
        functools.partial(_lstm_point_kernel, hidden=hidden),
        out_shape=(jax.ShapeDtypeStruct((padded, hidden), jnp.float32),
                   jax.ShapeDtypeStruct((padded, hidden), c.dtype)),
        grid=(padded // rows,),
        in_specs=[pl.BlockSpec((rows, four_h), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((rows, hidden), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec((rows, hidden), lambda i: (i, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((rows, hidden), lambda i: (i, 0),
                                memory_space=pltpu.VMEM)),
        interpret=interpret,
    )(gates_p, c_p)
    return (h_out[:b], c_out[:b]), (orig_gates, c)


def _lstm_pointwise_bwd(block_rows, interpret, res, cts):
    """LSTM cell backward from the saved pre-activations (recompute the
    activations — VPU-cheap — instead of storing four per-gate tensors)."""
    gates, c = res
    gh, gc_out = cts
    c32 = c.astype(jnp.float32)
    gh = gh.astype(jnp.float32)
    gc_out = gc_out.astype(jnp.float32)
    gates_dtype = gates.dtype
    gates = gates.astype(jnp.float32)
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
    g = jnp.tanh(g)
    c_new = f * c32 + i * g
    tc = jnp.tanh(c_new)
    dc_new = gc_out + gh * o * (1.0 - tc * tc)
    d_i = dc_new * g * i * (1.0 - i)
    d_f = dc_new * c32 * f * (1.0 - f)
    d_g = dc_new * i * (1.0 - g * g)
    d_o = gh * tc * o * (1.0 - o)
    d_gates = jnp.concatenate([d_i, d_f, d_g, d_o],
                              axis=-1).astype(gates_dtype)
    d_c = (dc_new * f).astype(c.dtype)
    return d_gates, d_c


lstm_pointwise.defvjp(_lstm_pointwise_fwd, _lstm_pointwise_bwd)


def lstm_cell_fused(x: jax.Array, h: jax.Array, c: jax.Array, w,
                    interpret: Optional[bool] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Drop-in for ``dt_tpu.ops.rnn.lstm_cell``: XLA matmul (MXU) + Pallas
    fused pointwise stage.  Gate pre-activations stay f32 into the kernel
    (matching the oracle's precision); outputs take x/c dtypes."""
    gates = (jnp.matmul(x, w.wx) + jnp.matmul(h, w.wh)).astype(jnp.float32) \
        + w.b
    h_new, c_new = lstm_pointwise(gates, c.astype(jnp.float32),
                                  interpret=interpret)
    # same output dtypes as the oracle rnn.lstm_cell (both follow x.dtype)
    return h_new.astype(x.dtype), c_new.astype(x.dtype)
