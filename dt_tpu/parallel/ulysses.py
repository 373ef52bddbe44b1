"""Ulysses-style all-to-all sequence parallelism.

Beyond the reference's RNN ceiling (``src/operator/cudnn_rnn-inl.h:1``,
SURVEY.md §5.7).  The second of the two canonical long-context schemes
(DeepSpeed-Ulysses, Jacobs et al. 2023): instead of circulating K/V around a ring
(``dt_tpu.parallel.ring_attention``), two ``all_to_all`` collectives
re-partition between sequence-sharded and head-sharded layouts:

    (B, S/n, H, D)  --all_to_all-->  (B, S, H/n, D)
    full attention per local head group (exact, no streaming softmax)
    (B, S, H/n, D)  --all_to_all-->  (B, S/n, H, D)

Tradeoff vs ring: 2 all-to-alls of activation size vs (n-1) K/V permutes;
needs ``num_heads % axis_size == 0``; local attention sees the FULL sequence
(better MXU utilization for moderate S, higher peak memory O(S²/n) scores).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from dt_tpu.parallel.ring_attention import full_attention


def _ulysses_sharded(q, k, v, *, axis_name, scale, causal):
    # local shapes: (B, S/n, H, D)
    # all_to_all: split heads across devices, gather sequence
    def seq_to_head(x):
        # split axis=2 (heads) into n parts, concat axis=1 (sequence)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def head_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    # (B, S, H/n, D): exact attention over the full sequence per head group
    out = full_attention(qh, kh, vh, causal=causal, scale=scale)
    return head_to_seq(out)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                      *, axis_name: str = "data", causal: bool = False,
                      scale: Optional[float] = None) -> jax.Array:
    """Exact attention, sequence sharded over ``axis_name`` via all-to-all.

    ``q``/``k``/``v``: (B, S, H, Dh) global; S and H must divide by the axis
    size.  Same contract as :func:`ring_attention` — pick per workload.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    n = mesh.shape[axis_name]
    if q.shape[2] % n:
        raise ValueError(
            f"num_heads {q.shape[2]} must divide by axis size {n} for "
            f"ulysses; use ring_attention for head counts < axis size")
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(_ulysses_sharded, axis_name=axis_name, scale=scale,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
