"""State-space (Mamba-2) mixer parts: the causal depthwise convolution, the
selective state-space recurrence as a chunked scan, and the gated RMSNorm.
The reference's recurrent ceiling is the cuDNN fused RNN
(``src/operator/cudnn_rnn-inl.h:1``; SURVEY §5.7), one position at a time;
this is the recurrence that trains in parallel over the sequence.

The recurrence, per head (``x_t`` of ``P`` channels, ``B_t`` and ``C_t`` of
``N`` state channels shared by the heads of a group, a scalar step ``dt_t > 0``
and a scalar ``a < 0``)::

    S_t = exp(a dt_t) S_{t-1} + dt_t x_t B_t^T        (P x N, S_0 = 0)
    y_t = S_t C_t

``ssd_scan`` computes it in chunks of ``chunk`` positions (Dao & Gu 2024,
"state-space duality"): inside a chunk as masked products of ``C B^T`` with
the decays' cumulative sums, between chunks by carrying the chunk states.
Decays, cumulative sums and the carried states are float32 whatever the
compute type; the four products take operands in ``x``'s type and accumulate
in float32.  Two bodies compute it, chosen by the shapes alone: the Pallas
kernels of ``ops/pallas/ssd.py`` (``ssd_fwd`` and a hand-written backward,
``ssd_bwd``: a chunk's squares stay in VMEM and nothing is differentiated as
written) where the chunk, the state width and a lane tile's heads are whole
lane tiles, and ``ssd_scan_xla`` (plain ``jax.numpy`` and a ``lax.scan`` over
the chunk states, differentiated as written) otherwise, which is what widths
in the units take.  The convolution and the gated norm are plain
``jax.numpy`` that XLA lowers; the scopes ``conv1d``, ``ssd_scan`` and
``gated_norm`` that ``models/hybrid_lm.py`` puts around these calls are what
a device trace finds them by.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from dt_tpu.ops.pallas import ssd

F32 = jnp.float32


def causal_conv1d(x, weight, bias=None):
    """Causal depthwise convolution along the sequence.

    ``x`` (B, L, C), ``weight`` (K, C), ``bias`` (C,) or None:
    ``y_t = sum_k weight[k] * x[t - (K - 1) + k] + bias`` with ``x`` zero
    before the first position (``weight[K - 1]`` multiplies the current
    position: torch ``Conv1d(groups=C, padding=K-1)`` cut to ``L``).  K
    shifted multiply-adds, which XLA fuses into one pass.
    """
    k, l = weight.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + l] * weight[i].astype(x.dtype) for i in range(k))
    return y if bias is None else y + bias.astype(x.dtype)


def ssd_scan(x, dt, a, b, c, *, chunk: int):
    """The selective state-space recurrence above, chunked.

    ``x`` (B, L, H, P); ``dt`` (B, L, H), positive (after the softplus);
    ``a`` (H,), negative; ``b``, ``c`` (B, L, G, N) with ``G`` dividing
    ``H`` (head ``h`` reads group ``h // (H // G)``).  Returns ``y``
    (B, L, H, P) in ``x``'s type, without the skip term ``D x``.  ``L`` need
    not be a multiple of ``chunk``: the tail is padded with ``dt = 0``, which
    neither decays nor feeds the state.

    Which path runs follows from the shapes alone: the Pallas kernels where
    ``ssd.head_block`` has a head block for them (the chunk, the state width
    and a lane tile's heads whole lane tiles), ``ssd_scan_xla`` otherwise.
    """
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    q = min(int(chunk), l)
    hb = ssd.head_block(h, g, p, n, q, x.dtype.itemsize)
    ssd.note_path((bsz, l, h, p, g, n, q, x.dtype.name), hb)
    if hb is None:
        return ssd_scan_xla(x, dt, a, b, c, chunk=chunk)
    return ssd.ssd_scan_pallas(x, dt, a, b, c, q=q, hb=hb)


def ssd_scan_xla(x, dt, a, b, c, *, chunk: int):
    """``ssd_scan`` as plain ``jax.numpy``, differentiated as written: the
    path for shapes the kernels do not take, and the tests' second oracle.
    A chunk's decays are a (B, chunks, H, chunk, chunk) float32 array in
    HBM here, and the chunk states a ``lax.scan``."""
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    k = h // g
    q = min(int(chunk), l)
    pad = (-l) % q
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (l + pad) // q
    dtype = x.dtype
    # chunked views; heads split into (group, head of the group)
    x32 = x.reshape(bsz, nc, q, g, k, p).astype(F32)
    bc = b.reshape(bsz, nc, q, g, n).astype(dtype)
    cc = c.reshape(bsz, nc, q, g, n).astype(dtype)
    dth = jnp.moveaxis(dt.astype(F32).reshape(bsz, nc, q, h), 2, 3)
    # log-decay of each position and its running sum inside the chunk
    cs = jnp.cumsum(dth * a.astype(F32)[:, None], axis=-1)   # (B, c, H, q)

    def per_position(w):
        """(B, c, H, q) -> (B, c, q, g, k, 1): a weight of x's positions."""
        return jnp.moveaxis(w, 3, 2).reshape(bsz, nc, q, g, k, 1)

    # -- inside a chunk: y_i += sum_{j<=i} (C_i.B_j) exp(cs_i - cs_j) dt_j x_j
    cb = jnp.einsum("bcign,bcjgn->bcgij", cc, bc, preferred_element_type=F32)
    seg = cs[..., :, None] - cs[..., None, :]                # (B, c, H, i, j)
    lower = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    m = (cb[:, :, :, None] * decay.reshape(bsz, nc, g, k, q, q)).astype(dtype)
    xdt = (x32 * per_position(dth)).astype(dtype)
    y = jnp.einsum("bcgkij,bcjgkp->bcigkp", m, xdt,
                   preferred_element_type=F32)
    # -- what each chunk adds to the state at its own end
    to_end = jnp.exp(cs[..., -1:] - cs)                      # (B, c, H, q)
    xe = (x32 * per_position(dth * to_end)).astype(dtype)
    states = jnp.einsum("bcqgkp,bcqgn->bcgkpn", xe, bc,
                        preferred_element_type=F32)
    # -- between chunks: S_c = exp(sum of the chunk's log-decays) S_{c-1} + ...
    chunk_decay = jnp.exp(cs[..., -1]).reshape(bsz, nc, g, k, 1, 1)

    def step(s, inp):
        dec, add = inp
        return dec * s + add, s          # emit the state entering the chunk

    _, entering = lax.scan(
        step, jnp.zeros((bsz, g, k, p, n), F32),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                  # (B, c, g, k, P, N)
    # -- what the entering state gives each position of the chunk
    y_off = jnp.einsum("bcqgn,bcgkpn->bcqgkp", cc, entering.astype(dtype),
                       preferred_element_type=F32)
    y = y + y_off * per_position(jnp.exp(cs))
    return y.reshape(bsz, nc * q, h, p)[:, :l].astype(dtype)


def gated_rms_norm(y, z, scale, eps: float = 1e-5, groups: int = 1):
    """``rms(y * silu(z)) * scale`` over the last axis (the norm comes after
    the gate), computed in float32, returned in ``y``'s type.  With
    ``groups`` the last axis is that many equal runs of channels and each is
    normed by its own mean square (Mamba-2's norm of a mixer with ``groups``
    groups of B and C: a chip that holds whole groups norms them as the
    whole mixer would); one group is the norm over all channels."""
    v = y.astype(F32) * jax.nn.silu(z.astype(F32))
    if groups == 1:
        v = v * lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True)
                          + eps)
    else:
        by_group = v.reshape(v.shape[:-1] + (groups, v.shape[-1] // groups))
        v = (by_group * lax.rsqrt(jnp.mean(
            jnp.square(by_group), axis=-1, keepdims=True) + eps)).reshape(
                v.shape)
    return (v * scale.astype(F32)).astype(y.dtype)
