"""The grouped products' Pallas kernels (``dt_tpu/ops/pallas/grouped.py``) in
the interpreter at small shapes that meet their alignment: the value and the
gradients against ``jax.lax.ragged_dot`` and against a loop over the groups
in float32, the fallback, the visit tables, and the grid as a constant."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from dt_tpu.ops.pallas import grouped

# 640 rows: 128 is the only candidate tile that divides them, so five row
# tiles and, with four groups, eight visits
M, K, N, G, TM = 640, 128, 256, 4, 128

SIZES = {
    "even": [160, 160, 160, 160],
    "on-the-tiles-edges": [128, 256, 128, 128],     # the spare visits masked
    "skewed": [500, 20, 100, 20],
    "an-empty-group-in-the-middle": [300, 0, 200, 140],
    "the-last-group-holds-padding": [50, 30, 40, 520],
    "every-boundary-inside-one-tile": [130, 3, 5, 502],
    "empty-at-both-ends": [0, 300, 340, 0],
    "rows-past-the-last-group": [100, 50, 20, 30],  # zeros, as ragged_dot's
}


def _operands(dtype, m=M, k=K, n=N, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(m, k), dtype),
            jnp.asarray(rng.randn(G, k, n) * 0.1, dtype),
            jnp.asarray(rng.randn(m, n), jnp.float32))


def _value_and_gradients(product):
    def run(lhs, rhs, sizes, d_out):
        out, pull = jax.vjp(lambda a, b: product(a, b, sizes), lhs, rhs)
        return (out, *pull(d_out))
    return jax.jit(run)


def _ragged_dot(lhs, rhs, sizes):
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)


def _loop(lhs, rhs, sizes, d_out):
    """Group by group in float32 on the host."""
    lhs, rhs, d_out = (np.asarray(a, np.float32) for a in (lhs, rhs, d_out))
    out, d_lhs, d_rhs = np.zeros_like(d_out), np.zeros_like(lhs), \
        np.zeros_like(rhs)
    start = 0
    for g, size in enumerate(sizes):
        rows = slice(start, start + size)
        out[rows] = lhs[rows] @ rhs[g]
        d_lhs[rows] = d_out[rows] @ rhs[g].T
        d_rhs[g] = lhs[rows].T @ d_out[rows]
        start += size
    return out, d_lhs, d_rhs


def _gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))


# one compilation a type for every case: the sizes are data
_KERNELS = _value_and_gradients(grouped.grouped_matmul)
_XLA = _value_and_gradients(_ragged_dot)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(SIZES))
def test_value_and_gradients_match_ragged_dot_and_a_loop(case, dtype):
    lhs, rhs, d_out = _operands(dtype)
    assert grouped._tiles(lhs, rhs, jnp.float32) == (TM, TM, TM)
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    got = _KERNELS(lhs, rhs, sizes, d_out)
    assert [a.dtype for a in got] == [jnp.float32, dtype, dtype]
    # float32 operands: the same products in another order.  bfloat16: the
    # value's products are exact and summed in float32; the kernels round the
    # float32 cotangent to the operands' type (XLA's default precision on
    # the TPU does; on the CPU it does not) and the gradients are written in
    # it, 2^-8 a rounding
    value, gradients = (1e-5, 1e-5) if dtype == jnp.float32 else (1e-5, 1e-2)
    for name, want in (("ragged_dot", _XLA(lhs, rhs, sizes, d_out)),
                       ("loop", _loop(lhs, rhs, SIZES[case], d_out))):
        for what, a, b, limit in zip(("value", "d_lhs", "d_rhs"), got, want,
                                     (value, gradients, gradients)):
            assert _gap(a, b) < limit, (name, what)
    if case == "an-empty-group-in-the-middle":
        assert not np.asarray(got[2], np.float32)[1].any()
    if case == "rows-past-the-last-group":
        assert not np.asarray(got[0])[200:].any()
        assert not np.asarray(got[1], np.float32)[200:].any()


@pytest.mark.parametrize("m,k,n", [(640, 96, 256), (640, 128, 200),
                                   (600, 128, 256)])
def test_a_shape_off_the_alignment_takes_ragged_dot(m, k, n):
    """A side that 128 does not divide: no tile, no kernel in the program,
    and ``ragged_dot``'s numbers bit for bit."""
    lhs, rhs, d_out = _operands(jnp.float32, m, k, n)
    assert grouped._tiles(lhs, rhs, jnp.float32) is None
    sizes = jnp.asarray([m - 300, 0, 200, 100], jnp.int32)
    run = _value_and_gradients(grouped.grouped_matmul)
    assert "pallas_call" not in str(jax.make_jaxpr(run)(lhs, rhs, sizes,
                                                        d_out))
    for a, b in zip(run(lhs, rhs, sizes, d_out),
                    _value_and_gradients(_ragged_dot)(lhs, rhs, sizes,
                                                      d_out)):
        np.testing.assert_array_equal(a, b)


def test_matrices_over_the_budget_have_no_tile():
    assert grouped.row_tile(49152, 2048, 768, 2, 2, 4) == 256
    assert grouped.row_tile(49152, 8192, 2048, 2, 2, 4) is None
    # 384 rows: only 128 divides them
    assert grouped.row_tile(384, 2048, 768, 2, 2, 4) == 128


@pytest.mark.parametrize("m,k,n,g", [
    (49152, 2048, 768, 16), (24576, 2048, 768, 16), (24576, 768, 2048, 16),
    (24576, 2048, 512, 32), (24576, 512, 2048, 32)])
def test_the_accepted_cells_keep_their_tiles(m, k, n, g):
    """The transposed product's own budget moves no accepted cell: ``tm``
    256 for all three products at 16 groups of 2,048 x 768 and 32 of 2,048 x
    512, as before it."""
    lhs = jax.ShapeDtypeStruct((m, k), jnp.bfloat16)
    rhs = jax.ShapeDtypeStruct((g, k, n), jnp.bfloat16)
    assert grouped._tiles(lhs, rhs, jnp.float32) == (256,) * 3


def test_the_widest_experts_hold_the_transposed_product_whole():
    """8 groups of 2,048 x 1,792 over 24,576 rows: the value and the turned
    product are within ``VMEM_BUDGET`` (21.25 and 21.5 MiB), the transposed
    one (33.5 MiB; 33.75 for the down projection's 1,792 x 2,048) over it
    and within its own ``VMEM_BUDGET_T``, which stays under the limit the
    compiler is given."""
    mib = 2 ** 20
    assert grouped.vmem_bytes(256, 2048, 1792, 2, 2, 4) == 21.25 * mib
    assert grouped.vmem_bytes(256, 1792, 2048, 4, 2, 2) == 21.5 * mib
    assert grouped.vmem_bytes(256, 2048, 1792, 2, 4, 2, True) == 33.5 * mib
    assert grouped.vmem_bytes(256, 1792, 2048, 2, 4, 2, True) == 33.75 * mib
    assert grouped.vmem_bytes(128, 2048, 1792, 2, 4, 2, True) == 30.75 * mib
    assert grouped.VMEM_BUDGET < 30.75 * mib
    assert 33.75 * mib <= grouped.VMEM_BUDGET_T < 2 * grouped.VMEM_BUDGET
    assert grouped.row_tile(24576, 2048, 1792, 2, 4, 2, True) == 256
    # a product that is not transposed keeps VMEM_BUDGET: 32.6 MiB at 128
    assert grouped.row_tile(24576, 4096, 1792, 2, 2, 4) is None
    shape = jax.ShapeDtypeStruct
    for k, n in ((2048, 1792), (1792, 2048)):
        assert grouped._tiles(shape((24576, k), jnp.bfloat16),
                              shape((8, k, n), jnp.bfloat16),
                              jnp.float32) == (256,) * 3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["skewed", "an-empty-group-in-the-middle",
                                  "rows-past-the-last-group",
                                  "every-boundary-inside-one-tile"])
def test_a_matrix_over_the_budget_transposed_only_keeps_the_kernels(
        monkeypatch, case, dtype):
    """Budgets set for the test's shape so that the value and the turned
    product fit ``VMEM_BUDGET`` and the transposed one only its own: all
    three run as kernels and give what ``ragged_dot`` and a loop over the
    groups give; without the second budget the call takes ``ragged_dot``."""
    k, n = 256, 512
    lhs, rhs, d_out = _operands(dtype, M, k, n)
    a = lhs.dtype.itemsize
    whole = [grouped.vmem_bytes(TM, k, n, a, a, 4),
             grouped.vmem_bytes(TM, n, k, 4, a, a),
             grouped.vmem_bytes(TM, k, n, a, 4, a, True)]
    assert max(whole[:2]) < whole[2]
    monkeypatch.setattr(grouped, "VMEM_BUDGET", max(whole[:2]))
    monkeypatch.setattr(grouped, "VMEM_BUDGET_T", max(whole[:2]))
    assert grouped._tiles(lhs, rhs, jnp.float32) is None
    monkeypatch.setattr(grouped, "VMEM_BUDGET_T", whole[2])
    assert grouped._tiles(lhs, rhs, jnp.float32) == (TM, TM, TM)
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    run = _value_and_gradients(grouped.grouped_matmul)
    assert str(jax.make_jaxpr(run)(lhs, rhs, sizes, d_out)).count(
        "pallas_call") == 3
    got = run(lhs, rhs, sizes, d_out)
    value, gradients = (1e-5, 1e-5) if dtype == jnp.float32 else (1e-5, 1e-2)
    for name, want in (("ragged_dot", _XLA(lhs, rhs, sizes, d_out)),
                       ("loop", _loop(lhs, rhs, SIZES[case], d_out))):
        for what, a, b, limit in zip(("value", "d_lhs", "d_rhs"), got, want,
                                     (value, gradients, gradients)):
            assert _gap(a, b) < limit, (name, what)
    if case == "an-empty-group-in-the-middle":
        assert not np.asarray(got[2], np.float32)[1].any()
    if case == "rows-past-the-last-group":
        assert not np.asarray(got[0])[200:].any()
        assert not np.asarray(got[1], np.float32)[200:].any()


def test_the_visit_tables_by_hand():
    """512 rows in tiles of 128, four groups: seven visits."""
    tables = lambda sizes, **kw: [a.tolist() for a in grouped.visit_tables(  # noqa: E731
        jnp.asarray(sizes, jnp.int32), 512, 128, **kw)]
    # group 0 has rows 0..299 (tiles 0, 1, 2), group 1 none, group 2 rows
    # 300..399 (tiles 2, 3), group 3 rows 400..511 (tile 3); one visit is
    # left over: "no group" (4), which stays on the last tile
    offsets, group_ids, tile_ids = tables([300, 0, 100, 112])
    assert offsets == [0, 300, 300, 400, 512, 512]
    assert group_ids == [0, 0, 0, 2, 2, 3, 4]
    assert tile_ids == [0, 1, 2, 2, 3, 3, 3]
    # for the transposed product the empty group is visited, on the tile its
    # offset lies in, and every visit is spent
    _, group_ids, tile_ids = tables([300, 0, 100, 112], visit_empty=True)
    assert group_ids == [0, 0, 0, 1, 2, 2, 3]
    assert tile_ids == [0, 1, 2, 2, 2, 3, 3]
    # every boundary on a tile's edge: four visits do the work, three are
    # left over
    _, group_ids, tile_ids = tables([128, 128, 128, 128])
    assert group_ids == [0, 1, 2, 3, 4, 4, 4]
    assert tile_ids == [0, 1, 2, 3, 3, 3, 3]
    # the sizes fill 200 rows: the visits left over walk on over the tiles
    # after them (tile 1 holds rows 128..199 of group 3 and is started from
    # zeros by that visit; tiles 2 and 3 hold no group's rows)
    offsets, group_ids, tile_ids = tables([100, 50, 20, 30])
    assert offsets == [0, 100, 150, 170, 200, 200]
    assert group_ids == [0, 1, 1, 2, 3, 4, 4]
    assert tile_ids == [0, 0, 1, 1, 1, 2, 3]
    # an empty group at the buffer's end is visited on the last tile
    _, group_ids, tile_ids = tables([256, 256, 0, 0], visit_empty=True)
    assert group_ids == [0, 0, 1, 1, 2, 3, 4]
    assert tile_ids == [0, 1, 2, 3, 3, 3, 3]


def test_the_grid_is_a_constant_that_does_not_read_the_sizes():
    """``M / tm + G - 1`` visits in each of the three calls, in a program
    traced with the sizes abstract."""
    assert grouped.visits(M, TM, G) == 8
    assert grouped.visits(49152, 256, 16) == 207
    assert grouped.visits(24576, 256, 16) == 111
    lhs, rhs, d_out = _operands(jnp.bfloat16)
    sizes = jax.ShapeDtypeStruct((G,), jnp.int32)
    jaxpr = jax.make_jaxpr(_value_and_gradients(grouped.grouped_matmul))(
        lhs, rhs, sizes, d_out)
    calls = {}

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.setdefault(eqn.params["name"], []).append(
                    tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    assert calls == {"grouped_mm": [(8,), (8,)], "grouped_mm_t": [(8,)]}
