"""Grouped matrix products over a sorted buffer, as Pallas kernels.

``grouped_matmul(lhs (M, K), rhs (G, K, N), group_sizes (G,))`` is
``out[r] = lhs[r] @ rhs[group(r)]`` for rows sorted by group: what
``jax.lax.ragged_dot`` computes, and what a routed layer's experts are
(``parallel/moe.py`` ``RoutedExperts``: three of them a layer over the
buffer its router sorted; the reference places whole layers on devices by
hand, ``python/mxnet/module/executor_group.py:143``, and has no layer whose
rows choose their weights).  XLA's own kernel for ``ragged_dot`` runs at
33 to 39% of the matrix unit's pace on a v5e whatever the sizes (PERF.md
section 6, PR 34 and PR 38); these follow the shape of
``jax.experimental.pallas.ops.tpu.megablox`` (``gmm``, ``tgmm``) with a
grid that does not read the sizes.

**Visits.**  The rows are cut into tiles of ``tm``.  A tile that lies in
one group is visited once; a tile that ``n`` groups share is visited ``n``
times, once a group, with the other groups' rows masked.  With ``G``
groups there are at most ``M / tm + G - 1`` visits, and the grid has
exactly that many whatever ``group_sizes`` holds (``visits``): the visits
the sizes do not need are run fully masked, so a call costs the same
whatever a router decided.  Which group and which row tile a visit has
comes from two int32 tables made in jax before the call (``visit_tables``)
and read by the index maps through scalar prefetch, with the groups'
offsets for the masks.

**Three products, two kernels.**

* ``grouped_mm`` — ``out (M, N) = lhs @ rhs[g]``, or with ``turned`` ``out
  (M, K) = lhs (M, N) @ rhs[g]^T``: the same kernel contracting the
  matrices' other side, no copy of them made.  A visit holds a row tile,
  its group's whole matrix (fetched again only when the group changes) and
  the result tile, which stays in VMEM over the visits that share it.
* ``grouped_mm_t`` — ``out[g] (K, N) = lhs[rows of g]^T @ rhs[rows of g]``:
  accumulates over a group's visits in a float32 VMEM scratch and writes
  the matrix at the group's end; an empty group is visited once and gets
  zeros.

Operands are multiplied in the narrower of the two operand types (bfloat16
in a model; a float32 cotangent is rounded in the kernel, as XLA's default
precision rounds it on the TPU), accumulated in float32.  ``grouped_matmul``
is a ``jax.custom_vjp``: ``d_lhs`` is the turned product of the cotangent,
``d_rhs`` the transposed one.

**Tiles from the shape.**  ``row_tile`` picks ``tm``; a shape it has no
tile for (a side that 128 does not divide, matrices over the budget) takes
``jax.lax.ragged_dot``, which is also what the toy sizes of the tests take.
The transposed product has a budget of its own, ``VMEM_BUDGET_T``: it holds
the whole result with a float32 accumulator of its size, 33.5 MiB at the
widest experts a model here has (8 groups of 2,048 x 1,792), which the
compiler takes under the call's ``vmem_limit_bytes`` and which runs faster
than the same product cut into column passes.
Off the TPU the kernels run in the Pallas interpreter.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dt_tpu.ops.pallas.attention import VMEM_BUDGET
from dt_tpu.ops.pallas.kernels import _default_interpret

logger = logging.getLogger("dt_tpu")

_LANES = 128
# the candidate row tiles, largest first.  No 512 or 1,024: on a v5e a call's
# time follows the rows its visits hold, (m / tm + G - 1) x tm, at about 84%
# of the matrix unit's pace whatever the tile, so the smaller tile's fewer
# rows in shared tiles win (PERF.md section 6, PR 38: the sweep over 256,
# 512 and 1,024 at the two routed cells' shapes: 256 ties 512 at 24,576
# rows and beats it by 2 to 5% at 49,152; 1,024 loses 7 to 20%).  At 32
# groups of 2,048 x 512 over 24,576 rows (PR 41: the sweep over 128, 256 and
# 512; value / d_lhs / d_rhs, ms a call on the host clock): 0.534 / 0.546 /
# 0.585 at 128, 0.500 / 0.524 / 0.584 at 256, 0.527 / 0.580 / 0.640 at 512:
# 256 stays.  At 8 groups of 2,048 x 1,792 over 24,576 rows (PR 43: the sweep
# over 128 and 256; ms a call on the host clock, gate/up then down): value
# 1.135 / 1.094 at 256 against 1.094 / 1.100 at 128 (within 4%, either way
# round); d_lhs 1.079 / 1.079 against 1.093 / 1.095; d_rhs 1.127 / 1.135
# against 1.138 / 1.147: 256 stays there too (XLA's ragged_dot: 2.23 / 1.95,
# 2.48 / 3.03, 2.46 / 2.79)
ROW_TILES = (256, 128)
# what a visit of the transposed product may hold by vmem_bytes' reckoning:
# the result matrix twice and a float32 accumulator of its size come to 33.5
# MiB at 2,048 x 1,792 with tm 256 (33.75 at 1,792 x 2,048), and Mosaic
# takes that under the call's vmem_limit_bytes, 48 MiB (PR 43, on the chip).
# Held whole the product reads 1.127 / 1.135 ms a call; in two column passes
# of 896 under VMEM_BUDGET, each reading the rows again, 1.197 / 1.177 (the
# same sweep): the grid gets a column axis when a shape comes that Mosaic
# refuses whole
VMEM_BUDGET_T = 36 << 20


def vmem_bytes(tm: int, k: int, n: int, lhs_itemsize: int,
               rhs_itemsize: int, out_itemsize: int,
               transposed: bool = False) -> int:
    """VMEM one visit holds, reckoned from the shapes.  ``grouped_mm``: the
    double-buffered row tile ``tm x k``, matrix ``k x n`` and result tile
    ``tm x n``, and the product's float32 ``tm x n`` before it is stored.
    ``grouped_mm_t`` (``transposed``): the double-buffered row tiles ``tm x
    k`` and ``tm x n`` and result matrix ``k x n``, and the float32
    accumulator ``k x n``."""
    if transposed:
        return 2 * (tm * k * lhs_itemsize + tm * n * rhs_itemsize
                    + k * n * out_itemsize) + k * n * 4
    return 2 * (tm * k * lhs_itemsize + k * n * rhs_itemsize
                + tm * n * out_itemsize) + tm * n * 4


def row_tile(m: int, k: int, n: int, lhs_itemsize: int, rhs_itemsize: int,
             out_itemsize: int, transposed: bool = False):
    """The row tile ``tm`` for ``m`` rows against ``k x n`` matrices: the
    largest of ``ROW_TILES`` that divides ``m`` and keeps ``vmem_bytes``
    within ``VMEM_BUDGET`` (``VMEM_BUDGET_T`` for the ``transposed``
    product); None where ``k`` or ``n`` is not whole lane tiles of 128 or no
    candidate fits (the caller then takes ``jax.lax.ragged_dot``).

    The arithmetic at the routed cells' widths (``k x n`` = 2,048 x 768,
    bfloat16 in, float32 out, ``tm`` 256): 2 x (1 + 3 + 0.75) + 0.75 = 10.25
    MiB; turned (a float32 cotangent in, bfloat16 out) 2 x (0.75 + 3 + 1) + 2
    = 11.5 MiB; transposed 2 x (1 + 0.75 + 3) + 6 = 15.5 MiB.  A visit is
    then 0.81 GFLOP, 4.1 us at 197 TFLOP/s, and moves 1.75 MB, 2.1 us at 819
    GB/s: the matrix unit sets the pace.  The ``G - 1`` shared tiles cost
    ``m / tm + 15`` visits for ``m / tm``: +8% at 49,152 rows and +16% at
    24,576 with 256 (+16% and +31% with 512).

    The same at the third routed cell's (``k x n`` = 2,048 x 512, 32 groups,
    24,576 rows, ``tm`` 256): 2 x (1 + 2 + 0.5) + 0.5 = 7.5 MiB; turned 2 x
    (0.5 + 2 + 1) + 2 = 9 MiB; transposed 2 x (1 + 0.5 + 2) + 4 = 11 MiB.  A
    visit is 0.54 GFLOP, 2.7 us at the peak, and the ``G - 1`` = 31 shared
    tiles make 127 visits of 96 tiles, +32% (223 of 192 with 128, +16% of
    twice the visits; 79 of 48 with 512, +65%).  A group is 512 rows at even
    load, two row tiles, so its 2 MiB matrix is fetched for two visits'
    work: a call moves 101 MB of rows, 67 MB of matrices and 50 MB of
    float32 result, 0.27 ms at 819 GB/s against 0.26 ms of operations at
    the peak.  Bytes and operations balance, neither hides the other whole,
    and the kernels read 45 to 52% of the peak alone and 61% in the cell's
    step (0.44 ms a call; PERF.md section 6, PR 41) where the 768-wide
    experts' 16 groups read 75 and 84.

    At the widest experts' (``k x n`` = 2,048 x 1,792, 8 groups, 24,576
    rows, ``tm`` 256): 2 x (1 + 7 + 1.75) + 1.75 = 21.25 MiB; turned 2 x
    (1.75 + 7 + 1) + 2 = 21.5 MiB; transposed 2 x (1 + 1.75 + 7) + 14 = 33.5
    MiB (30.75 at ``tm`` 128): over ``VMEM_BUDGET`` and within
    ``VMEM_BUDGET_T``.  A visit is 1.88 GFLOP, 9.5 us at the peak, and moves
    2.75 MB of rows and result, 3.4 us; a group is 2,048 rows at even load,
    eight row tiles for one fetch of its 7 MiB matrix, and the ``G - 1`` = 7
    shared tiles make 103 visits of 96, +7%: a call is 180 GFLOP, 0.92 ms at
    the peak, and reads 81 to 85% of it alone (1.08 to 1.14 ms; PERF.md
    section 6, PR 43).

    At experts that work in a latent (``k x n`` = 1,024 x 2,688 for ``up``
    and 2,688 x 1,024 for ``down``, 8 groups, a buffer of 4,224 rows: 1.5
    times an even load of 2,816, which 256 does not divide, so ``tm`` is
    128 and the buffer 33 row tiles): 2 x (0.25 + 5.25 + 1.31) + 1.31 =
    14.9 MiB for ``up`` and 13.3 for ``down``; turned 14.1 both; transposed
    2 x (0.25 + 1.31 + 5.25) + 10.5 = 24.1 MiB and 23.3, within
    ``VMEM_BUDGET_T``; Mosaic takes all six
    (``tests/test_grouped_compile_tpu.py``).  A visit is 0.70 GFLOP, 3.6 us
    at the peak; a group is 352 rows at even load, under three row tiles
    for one fetch of its 5.25 MiB matrix, and the ``G - 1`` = 7 shared
    tiles make 40 visits of 33, +21%.  A call is 23.3 GFLOP, 0.118 ms at
    the peak, and moves 8.7 MB of rows, 44 MB of matrices and 45 MB of
    float32 result, 0.120 ms at 819 GB/s: operations and bytes balance as
    at 32 groups of 2,048 x 512, with the matrices, not the rows, most of
    the bytes; in the cell's step a call takes 0.206 ms, 58% of that
    (PERF.md section 5, PR 47)."""
    if k % _LANES or n % _LANES:
        return None
    budget = VMEM_BUDGET_T if transposed else VMEM_BUDGET
    for tm in ROW_TILES:
        if m % tm == 0 and vmem_bytes(tm, k, n, lhs_itemsize, rhs_itemsize,
                                      out_itemsize, transposed) <= budget:
            return tm
    return None


def visits(m: int, tm: int, groups: int) -> int:
    """The grid's row-tile visits: every tile once and one more for each
    boundary between groups, as if each lay inside a tile.  A Python
    constant: it does not read the sizes."""
    return m // tm + groups - 1


def visit_tables(group_sizes, m: int, tm: int, visit_empty: bool = False):
    """``group_sizes`` (G,) int32 -> (offsets (G + 2,), group_ids (V,),
    tile_ids (V,)) for ``V = visits(m, tm, G)``.

    ``offsets[g]`` is group ``g``'s first row and ``offsets[g + 1]`` the row
    after its last; ``offsets[G + 1] = offsets[G]`` gives the group id ``G``,
    "no group", an empty range.  Visit ``v`` multiplies row tile
    ``tile_ids[v]`` for group ``group_ids[v]``: the groups in order, each
    over the tiles its rows touch (an empty group over none, or with
    ``visit_empty`` over the one its offset lies in, so that the transposed
    kernel writes its zeros).  The visits left over have group ``G``: they
    walk on over the tiles after the last group's end, if the sizes do not
    fill the buffer (those rows come out zero, as ``ragged_dot`` has them),
    and then stay on the last tile; every row of such a visit is masked."""
    g = group_sizes.shape[0]
    v = visits(m, tm, g)
    tiles_m = m // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, tiles_m - 1)
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                      1 if visit_empty else 0)
    upto = jnp.cumsum(count)
    total = upto[-1]
    at = jnp.arange(v, dtype=jnp.int32)
    # the groups whose visits end at or before this one: one fused compare
    # where ``searchsorted`` is a loop on the device
    group_ids = jnp.sum(at[:, None] >= upto[None, :], axis=1,
                        dtype=jnp.int32)
    inside = jnp.minimum(group_ids, g - 1)
    tile_ids = jnp.where(
        at < total, first[inside] + at - (upto - count)[inside],
        # past the last group: on from the tile after the last row in a group
        (ends[-1] + tm - 1) // tm + at - total)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends, ends[-1:]])
    return offsets, group_ids, jnp.minimum(tile_ids, tiles_m - 1)


def _rows_of_visit(offsets, group_ids, tile_ids, v, tm, width):
    """(tm, width) bool: the rows of visit ``v``'s tile that are in its
    group."""
    group = group_ids[v]
    row = tile_ids[v] * tm + jax.lax.broadcasted_iota(jnp.int32,
                                                      (tm, width), 0)
    return (row >= offsets[group]) & (row < offsets[group + 1])


def _mm_kernel(offsets, group_ids, tile_ids, lhs_ref, rhs_ref, out_ref, *,
               tm, turned, compute_dtype):
    v = pl.program_id(0)
    product = jax.lax.dot_general(
        lhs_ref[...].astype(compute_dtype),
        rhs_ref[...].astype(compute_dtype),
        (((1,), (1 if turned else 0,)), ((), ())),
        preferred_element_type=jnp.float32)
    product = product.astype(out_ref.dtype)
    mine = _rows_of_visit(offsets, group_ids, tile_ids, v, tm,
                          out_ref.shape[1])
    # the result tile stays in VMEM over the visits that share it: the first
    # of them starts it from zeros, the later ones keep the rows they found
    first = (v == 0) | (tile_ids[jnp.maximum(v - 1, 0)] != tile_ids[v])
    kept = jnp.where(first, jnp.zeros_like(product), out_ref[...])
    out_ref[...] = jnp.where(mine, product, kept)


def _mm_t_kernel(offsets, group_ids, tile_ids, lhs_ref, rhs_ref, out_ref,
                 acc_ref, *, tm, groups, compute_dtype):
    v = pl.program_id(0)
    last = pl.num_programs(0) - 1
    # a left-over visit ("no group") goes on with the last group's matrix
    held = lambda i: jnp.minimum(group_ids[i], groups - 1)  # noqa: E731
    group = held(v)

    @pl.when((v == 0) | (held(jnp.maximum(v - 1, 0)) != group))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mine = _rows_of_visit(offsets, group_ids, tile_ids, v, tm,
                          rhs_ref.shape[1])
    rhs = rhs_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...].astype(compute_dtype),
        jnp.where(mine, rhs, jnp.zeros_like(rhs)).astype(compute_dtype),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when((v == last) | (held(jnp.minimum(v + 1, last)) != group))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _rows_spec(tm, width):
    """The row tile of an (M, ``width``) array that a visit has."""
    return pl.BlockSpec((tm, width), lambda v, off, gid, tid: (tid[v], 0))


def _matrix_spec(g, k, n):
    """The whole matrix of a visit's group in a (``g``, ``k``, ``n``) array;
    a left-over visit ("no group") stays on the last group's."""
    return pl.BlockSpec((None, k, n), lambda v, off, gid, tid: (
        jnp.minimum(gid[v], g - 1), 0, 0))


def _visit_call(kernel, name, tables, lhs, rhs, *, in_specs, out_specs,
                out_shape, flops, interpret, scratch_shapes=()):
    """One ``pallas_call`` over the visits: the three tables by scalar
    prefetch, the grid as long as they are."""
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=tables[1].shape,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * VMEM_BUDGET),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=0,
            bytes_accessed=sum(a.size * jnp.dtype(a.dtype).itemsize
                               for a in (lhs, rhs, out_shape))),
        interpret=interpret,
    )(*tables, lhs, rhs)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "turned", "out_dtype", "tm", "interpret"))
def _grouped_mm(lhs, rhs, group_sizes, *, turned, out_dtype, tm, interpret):
    """``lhs (M, K) @ rhs[g] (K, N)`` -> (M, N), or ``turned`` ``lhs (M, N)
    @ rhs[g]^T`` -> (M, K).  Jitted and inlined as the flash kernels are: a
    model's layers share one trace of the body."""
    m, c = lhs.shape
    g, k, n = rhs.shape
    p = k if turned else n
    assert c == (n if turned else k), (lhs.shape, rhs.shape, turned)
    kern = functools.partial(
        _mm_kernel, tm=tm, turned=turned,
        compute_dtype=_narrower(lhs.dtype, rhs.dtype))
    return _visit_call(
        kern, "grouped_mm", visit_tables(group_sizes, m, tm), lhs, rhs,
        in_specs=[_rows_spec(tm, c), _matrix_spec(g, k, n)],
        out_specs=_rows_spec(tm, p),
        out_shape=jax.ShapeDtypeStruct((m, p), out_dtype),
        flops=2 * m * k * n, interpret=interpret)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "out_dtype", "tm", "interpret"))
def _grouped_mm_t(lhs, rhs, group_sizes, *, out_dtype, tm, interpret):
    """``lhs (M, K)``, ``rhs (M, N)`` -> (G, K, N): for each group the
    product of its rows, ``lhs[rows]^T @ rhs[rows]``."""
    m, k = lhs.shape
    n = rhs.shape[1]
    g = group_sizes.shape[0]
    kern = functools.partial(
        _mm_t_kernel, tm=tm, groups=g,
        compute_dtype=_narrower(lhs.dtype, rhs.dtype))
    return _visit_call(
        kern, "grouped_mm_t",
        visit_tables(group_sizes, m, tm, visit_empty=True), lhs, rhs,
        in_specs=[_rows_spec(tm, k), _rows_spec(tm, n)],
        out_specs=_matrix_spec(g, k, n),
        out_shape=jax.ShapeDtypeStruct((g, k, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((k, n), jnp.float32)],
        flops=2 * m * k * n, interpret=interpret)


def _narrower(a, b):
    """The type two operands are multiplied in: the one of fewer bytes."""
    a, b = jnp.dtype(a), jnp.dtype(b)
    return a if a.itemsize <= b.itemsize else b


@functools.lru_cache(maxsize=None)
def _note_tiles(shape, tiles) -> None:
    """Once per distinct shape, what the products were traced with: a ``#
    grouped_tiles`` debug line (``tm`` None: ``jax.lax.ragged_dot``)."""
    logger.debug("# grouped_tiles m=%d k=%d n=%d groups=%d dtype=%s tm=%s",
                 *shape, tiles)


def _tiles(lhs, rhs, out_dtype):
    """The three products' row tiles (forward, turned, transposed) for a
    call's shapes and types, or None where any of them has none: one
    decision for the value and both gradients."""
    m, k = lhs.shape
    g, _, n = rhs.shape
    a, b, o = (jnp.dtype(t).itemsize for t in (lhs.dtype, rhs.dtype,
                                               out_dtype))
    tiles = (row_tile(m, k, n, a, b, o), row_tile(m, n, k, o, b, a),
             row_tile(m, k, n, a, o, b, transposed=True))
    tiles = None if None in tiles else tiles
    _note_tiles((m, k, n, g, lhs.dtype.name), tiles)
    return tiles


def _ragged_dot(lhs, rhs, group_sizes, out_dtype):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(lhs, rhs, group_sizes, preferred_element_type=jnp.float32):
    """``out[r] = lhs[r] @ rhs[group(r)]``: ``lhs`` (M, K) with its rows
    sorted by group, ``rhs`` (G, K, N), ``group_sizes`` (G,) int32 rows a
    group; rows past the sizes' sum come out zero.  The result (M, N) in
    ``preferred_element_type``, accumulated in float32.  The Pallas kernels
    of this file where ``row_tile`` has a tile for the shape, else
    ``jax.lax.ragged_dot``; differentiable in ``lhs`` and ``rhs`` either
    way."""
    tiles = _tiles(lhs, rhs, preferred_element_type)
    if tiles is None:
        return _ragged_dot(lhs, rhs, group_sizes, preferred_element_type)
    return _grouped_mm(lhs, rhs, group_sizes, turned=False,
                       out_dtype=jnp.dtype(preferred_element_type),
                       tm=tiles[0], interpret=_default_interpret())


def _fwd(lhs, rhs, group_sizes, preferred_element_type):
    return grouped_matmul(lhs, rhs, group_sizes, preferred_element_type), \
        (lhs, rhs, group_sizes)


def _bwd(preferred_element_type, residuals, d_out):
    lhs, rhs, group_sizes = residuals
    tiles = _tiles(lhs, rhs, preferred_element_type)
    if tiles is None:
        _, pull = jax.vjp(lambda a, b: _ragged_dot(
            a, b, group_sizes, preferred_element_type), lhs, rhs)
        return (*pull(d_out), None)
    interpret = _default_interpret()
    d_lhs = _grouped_mm(d_out, rhs, group_sizes, turned=True,
                        out_dtype=lhs.dtype, tm=tiles[1], interpret=interpret)
    d_rhs = _grouped_mm_t(lhs, d_out, group_sizes, out_dtype=rhs.dtype,
                          tm=tiles[2], interpret=interpret)
    return d_lhs, d_rhs, None


grouped_matmul.defvjp(_fwd, _bwd)
