"""Recurrent ops: LSTM/GRU/vanilla cells and fused multi-layer RNN.

Reference: fused RNN operator ``src/operator/rnn.cc:1`` + ``rnn_impl.h`` (CPU)
and ``cudnn_rnn-inl.h`` (GPU), modes rnn_relu|rnn_tanh|lstm|gru, with
multi-layer and bidirectional support.  TPU-native design: the time loop is a
``lax.scan`` (single compiled step, no unrolling), the four LSTM gates are one
fused ``(B, I+H) @ (I+H, 4H)`` matmul on the MXU, and layers stack as a Python
loop over scans (layer count is static).  Gate order follows the reference's
cuDNN convention: i, f, g(c~), o for LSTM; r, z, n for GRU.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array


class LSTMWeights(NamedTuple):
    """One layer's packed weights: wx (I, 4H), wh (H, 4H), b (4H,)."""
    wx: Array
    wh: Array
    b: Array


class GRUWeights(NamedTuple):
    wx: Array  # (I, 3H)
    wh: Array  # (H, 3H)
    bx: Array  # (3H,)
    bh: Array  # (3H,)


def lstm_cell(x: Array, h: Array, c: Array, w: LSTMWeights) -> Tuple[Array, Array]:
    """One LSTM step.  Gate order i,f,g,o (reference ``rnn_impl.h`` LstmForward)."""
    # Matmuls stay in input dtype (bf16 hits the MXU at full rate); only the
    # gate nonlinearities run in f32 for numerical stability.
    gates = (jnp.matmul(x, w.wx) + jnp.matmul(h, w.wh)).astype(jnp.float32) + w.b
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    g = jnp.tanh(g)
    o = jax.nn.sigmoid(o)
    new_c = f * c.astype(jnp.float32) + i * g
    new_h = o * jnp.tanh(new_c)
    return new_h.astype(x.dtype), new_c.astype(x.dtype)


def gru_cell(x: Array, h: Array, w: GRUWeights) -> Array:
    """One GRU step.  Gate order r,z,n with cuDNN-style separate hidden bias
    (reference ``rnn_impl.h`` GruForward)."""
    gx = jnp.matmul(x, w.wx).astype(jnp.float32) + w.bx
    gh = jnp.matmul(h, w.wh).astype(jnp.float32) + w.bh
    rx, zx, nx = jnp.split(gx, 3, axis=-1)
    rh, zh, nh = jnp.split(gh, 3, axis=-1)
    r = jax.nn.sigmoid(rx + rh)
    z = jax.nn.sigmoid(zx + zh)
    n = jnp.tanh(nx + r * nh)
    new_h = (1.0 - z) * n + z * h.astype(jnp.float32)
    return new_h.astype(x.dtype)


def vanilla_cell(x: Array, h: Array, wx: Array, wh: Array, b: Array,
                 act: str = "tanh") -> Array:
    """rnn_relu / rnn_tanh mode."""
    pre = (jnp.matmul(x, wx) + jnp.matmul(h, wh)).astype(jnp.float32) + b
    out = jnp.tanh(pre) if act == "tanh" else jax.nn.relu(pre)
    return out.astype(x.dtype)


def lstm(x: Array, h0: Array, c0: Array, weights: Sequence[LSTMWeights],
         reverse: bool = False) -> Tuple[Array, Array, Array]:
    """Multi-layer unidirectional LSTM over a sequence.

    ``x``: (T, B, I); ``h0``/``c0``: (L, B, H).  Returns (outputs (T,B,H),
    hT (L,B,H), cT (L,B,H)).  Equivalent capability to the reference fused RNN
    op (``src/operator/rnn.cc``) in lstm mode.
    """
    outs = x
    hs, cs = [], []
    for layer, w in enumerate(weights):
        def step(carry, xt):
            h, c = carry
            h, c = lstm_cell(xt, h, c, w)
            return (h, c), h
        seq = jnp.flip(outs, 0) if reverse else outs
        (hT, cT), ys = lax.scan(step, (h0[layer], c0[layer]), seq)
        outs = jnp.flip(ys, 0) if reverse else ys
        hs.append(hT)
        cs.append(cT)
    return outs, jnp.stack(hs), jnp.stack(cs)


def gru(x: Array, h0: Array, weights: Sequence[GRUWeights],
        reverse: bool = False) -> Tuple[Array, Array]:
    """Multi-layer unidirectional GRU; see :func:`lstm`."""
    outs = x
    hs = []
    for layer, w in enumerate(weights):
        def step(h, xt):
            h = gru_cell(xt, h, w)
            return h, h
        seq = jnp.flip(outs, 0) if reverse else outs
        hT, ys = lax.scan(step, h0[layer], seq)
        outs = jnp.flip(ys, 0) if reverse else ys
        hs.append(hT)
    return outs, jnp.stack(hs)


def bidirectional_lstm(x: Array, h0: Array, c0: Array,
                       fwd: Sequence[LSTMWeights],
                       bwd: Sequence[LSTMWeights]) -> Tuple[Array, Array, Array]:
    """Bidirectional multi-layer LSTM (reference ``bidirectional=True``).
    ``h0``/``c0``: (2L, B, H), interleaved fwd/bwd per layer; output is
    concat(fwd, bwd) per step, feeding the next layer (cuDNN semantics)."""
    outs = x
    hs, cs = [], []
    for layer in range(len(fwd)):
        yf, hf, cf = lstm(outs, h0[2 * layer:2 * layer + 1],
                          c0[2 * layer:2 * layer + 1], [fwd[layer]])
        yb, hb, cb = lstm(outs, h0[2 * layer + 1:2 * layer + 2],
                          c0[2 * layer + 1:2 * layer + 2], [bwd[layer]],
                          reverse=True)
        outs = jnp.concatenate([yf, yb], axis=-1)
        hs += [hf[0], hb[0]]
        cs += [cf[0], cb[0]]
    return outs, jnp.stack(hs), jnp.stack(cs)


def init_lstm_weights(rng: Array, num_layers: int, input_size: int,
                      hidden_size: int, dtype=jnp.float32) -> list:
    """Uniform(-1/sqrt(H), 1/sqrt(H)) init, cuDNN-style."""
    ws = []
    scale = 1.0 / jnp.sqrt(hidden_size)
    for layer in range(num_layers):
        i = input_size if layer == 0 else hidden_size
        rng, k1, k2, k3 = jax.random.split(rng, 4)
        ws.append(LSTMWeights(
            wx=jax.random.uniform(k1, (i, 4 * hidden_size), dtype, -scale, scale),
            wh=jax.random.uniform(k2, (hidden_size, 4 * hidden_size), dtype,
                                  -scale, scale),
            b=jnp.zeros((4 * hidden_size,), dtype),
        ))
    return ws
