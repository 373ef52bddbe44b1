"""Ring attention — sequence/context parallelism over the mesh.

Beyond the reference (its ceiling is the bucketed cuDNN LSTM,
``src/operator/cudnn_rnn-inl.h:1``; SURVEY.md §5.7), but
first-class here: long sequences shard over a mesh axis, and attention runs
as a ring — each device holds one query block resident and passes K/V blocks
around the ring with ``ppermute`` over ICI, accumulating streaming-softmax
partial results (Liu et al. 2023 ring attention; the flash-attention
log-sum-exp accumulation makes the blockwise pass exact, not approximate).

Memory per device: O(S/N · S/N) attention scores instead of O(S·S); K/V
transfer overlaps with the block computation (XLA schedules the collective
permute concurrently with the matmuls).

Layout: ``x``: (B, S, D) with S sharded over ``axis_name``.  Causal masking
uses global block offsets derived from ``jax.lax.axis_index``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

NEG_INF = -1e30


def _block_attend(q, k, v, *, scale, causal, q_offset, k_offset):
    """Scores for one (q-block, k-block) pair + streaming-softmax stats.

    Returns (out_unnormalized, row_max, row_sumexp) in f32.
    q: (B, Sq, H, Dh); k/v: (B, Sk, H, Dh).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        qpos = q_offset + jnp.arange(sq)
        kpos = k_offset + jnp.arange(sk)
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # (B, H, Sq)
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: exp(NEG_INF - NEG_INF) = 1 would pollute; zero them
    p = jnp.where(m[..., None] <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out, m, l


def _ring_attention_sharded(q, k, v, *, axis_name, scale, causal):
    """Per-device body under shard_map: local q resident, k/v circulate."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    sq = q.shape[1]
    q_offset = idx * sq

    acc = jnp.zeros(q.shape[:1] + (sq,) + q.shape[2:], jnp.float32)
    row_max = jnp.full((q.shape[0], q.shape[2], sq), NEG_INF)
    row_sum = jnp.zeros((q.shape[0], q.shape[2], sq))

    def step(i, carry):
        acc, row_max, row_sum, k_cur, v_cur = carry
        # K/V block currently held came from device (idx - i) mod n
        src = (idx - i) % n
        k_offset = src * k_cur.shape[1]
        out, m, l = _block_attend(q, k_cur, v_cur, scale=scale,
                                  causal=causal, q_offset=q_offset,
                                  k_offset=k_offset)
        new_max = jnp.maximum(row_max, m)
        # rescale both accumulators to the new max (flash-attention merge)
        alpha = jnp.exp(jnp.where(row_max <= NEG_INF / 2, NEG_INF,
                                  row_max - new_max))
        beta = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m - new_max))
        row_sum = row_sum * alpha + l * beta
        acc = acc * jnp.moveaxis(alpha, 1, -1)[..., None] \
            + out * jnp.moveaxis(beta, 1, -1)[..., None]
        # rotate K/V around the ring (device d sends to d+1)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc, new_max, row_sum, k_nxt, v_nxt

    acc, row_max, row_sum, _, _ = jax.lax.fori_loop(
        0, n, step, (acc, row_max, row_sum, k, v))
    denom = jnp.maximum(row_sum, 1e-20)
    return (acc / jnp.moveaxis(denom, 1, -1)[..., None]).astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                   *, axis_name: str = "data", causal: bool = False,
                   scale: Optional[float] = None) -> jax.Array:
    """Exact attention with sequence sharded over ``axis_name``.

    ``q``/``k``/``v``: (B, S, H, Dh) global shapes; S must divide by the
    axis size.  Returns (B, S, H, Dh) with the same sharding.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(_ring_attention_sharded, axis_name=axis_name,
                          scale=scale, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def full_attention(q, k, v, *, causal=False, scale=None):
    """Single-device oracle (same math, no ring)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)) \
        .astype(q.dtype)
