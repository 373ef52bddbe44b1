"""Pipeline parallelism (GPipe-style) over a mesh axis.

Beyond the reference: its only model parallelism was manual per-layer
``group2ctx`` device placement with cross-device copies
(``example/model-parallel/``, ``python/mxnet/module/executor_group.py:143``,
SURVEY.md §2.3) — no microbatch scheduling.
Here: stages are sharded over a ``pipe`` mesh axis (stage-stacked params,
leading dim = num_stages), microbatches stream through the ring with
``ppermute``, and the whole schedule is one ``lax.scan`` inside ``shard_map``
— so ``jax.grad`` differentiates straight through it (GPipe's synchronous
schedule; activation memory bounded by remat if desired).

Latency: M microbatches through S stages take M + S - 1 ticks (the usual
bubble); throughput approaches S-way model scaling as M >> S.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def _pipeline_sharded(stacked_params, x, *, stage_fn, num_micro, axis_name):
    """Per-device body.  ``stacked_params``: local (1, ...) stage slice;
    ``x``: (M, mb, ...) microbatches (replicated).  Returns (T, mb, ...)
    per-tick outputs of THIS device's stage."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    params_local = jax.tree_util.tree_map(lambda p: p[0], stacked_params)
    ticks = num_micro + n - 1
    mb_shape = x.shape[1:]
    pad = jnp.zeros((ticks - num_micro,) + mb_shape, x.dtype)
    x_padded = jnp.concatenate([x, pad], axis=0)

    def tick(recv, t):
        # stage 0 reads the t-th microbatch; later stages read the ring
        inp = jnp.where(idx == 0,
                        lax.dynamic_index_in_dim(x_padded, t, 0,
                                                 keepdims=False),
                        recv)
        out = stage_fn(params_local, inp)
        # shift down the pipe: device i -> i+1 (last stage sends nowhere;
        # absent pairs deliver zeros, which stage 0 ignores)
        nxt = lax.ppermute(out, axis_name,
                           [(i, i + 1) for i in range(n - 1)])
        return nxt, out

    _, ys = lax.scan(tick, jnp.zeros(mb_shape, x.dtype),
                     jnp.arange(ticks))
    return ys[None]  # (1, T, mb, ...) — leading axis = this stage


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stacked_params: Any, x: jax.Array, mesh: Mesh,
                   axis_name: str = "pipe",
                   batch_axis: str = None) -> jax.Array:
    """Run ``x`` (microbatches: (M, mb, ...)) through S pipeline stages.

    ``stacked_params``: pytree whose leaves have leading dim S (stage-
    stacked; shard it over ``axis_name``).  ``stage_fn(params_i, h) -> h``
    is one stage's forward.  Returns (M, mb, ...) — the last stage's
    outputs.  Differentiable; use inside a jitted loss.

    ``batch_axis``: optional DATA-parallel mesh axis the microbatch dim
    is sharded over — dp x pp composition: each (pipe, data) device
    coordinate runs its stage on its batch shard, ppermute rides the
    pipe axis only, and GSPMD averages gradients over the data axis as
    usual.
    """
    num_micro = x.shape[0]
    n = mesh.shape[axis_name]
    num_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if num_stages != n:
        raise ValueError(
            f"stacked params carry {num_stages} stages but the "
            f"{axis_name!r} axis has {n} devices; they must match (fold "
            f"multiple layers into one stage_fn to run more layers per "
            f"device)")
    pspec = jax.tree_util.tree_map(
        lambda _: P(axis_name), stacked_params)
    rest = (None,) * (x.ndim - 2)
    xspec = P(None, batch_axis, *rest) if batch_axis else P()
    yspec = P(axis_name, None, batch_axis, *rest) if batch_axis \
        else P(axis_name)
    fn = shard_map(
        functools.partial(_pipeline_sharded, stage_fn=stage_fn,
                          num_micro=num_micro, axis_name=axis_name),
        mesh=mesh,
        in_specs=(pspec, xspec),
        out_specs=yspec,
        check_vma=False)
    ys = fn(stacked_params, x)          # (S, T, mb, ...)
    # the last stage's outputs, offset by its fill latency (S-1 ticks)
    return ys[n - 1, n - 1:n - 1 + num_micro]


def sequential_apply(stage_fn, stacked_params, x):
    """Single-device oracle: apply the S stages in order to every
    microbatch (``x``: (M, mb, ...))."""
    s = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    h = x
    for i in range(s):
        params_i = jax.tree_util.tree_map(lambda p: p[i], stacked_params)
        h = jax.vmap(lambda hh: stage_fn(params_i, hh))(h)
    return h
