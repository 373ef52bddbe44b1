"""Pallas flash attention vs the full_attention oracle (fwd + bwd).

Interpret mode on the CPU mesh (exact values); TPU numerics are verified
by drives per CLAUDE.md.
"""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import flash_edge_cases as edge
from dt_tpu.ops.pallas import attention as attn
from dt_tpu.ops.pallas.attention import flash_attention
from dt_tpu.parallel.ring_attention import full_attention


def _qkv(rng, b=2, s=256, h=2, d=64):
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d).astype(np.float32) * 0.5)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_oracle(causal):
    rng = np.random.RandomState(0)
    q, k, v = _qkv(rng)
    got = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_flash_multiblock_kv_accumulation():
    # several kv blocks per q block exercises the online-softmax carry
    rng = np.random.RandomState(1)
    q, k, v = _qkv(rng, b=1, s=512, h=1, d=64)
    got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_oracle(causal):
    rng = np.random.RandomState(2)
    q, k, v = _qkv(rng, b=1, s=256, h=2, d=32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=128,
                            block_k=128)
        return (o * o).sum()

    def loss_ref(q, k, v):
        o = full_attention(q, k, v, causal=causal)
        return (o.astype(jnp.float32) ** 2).sum()

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name} causal={causal}")


def test_flash_under_jit_bf16():
    rng = np.random.RandomState(3)
    q, k, v = _qkv(rng, b=1, s=128, h=1, d=64)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))

    @jax.jit
    def f(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    v1, g = jax.value_and_grad(f)(q, k, v)
    assert np.isfinite(float(v1))
    assert np.isfinite(np.asarray(g, np.float32)).all()


def test_flash_rejects_nonmultiple_seq():
    q = jnp.zeros((1, 100, 1, 64))
    with pytest.raises(ValueError):
        flash_attention(q, q, q)


# ---------------------------------------------------------------------------
# PR 29: the forward's tiles come from the shape; operands go in as stored
# ---------------------------------------------------------------------------

TILES = [(128, 128), (256, 512), (512, 256), (512, 512), (None, None)]
LENGTHS = [512, 640, 1024]
# what the benchmark's two LM cells get (PERF.md section 6, PR 29)
CELL_TILES = {1024: (1024, 1024), 4096: (1024, 1024)}
# and the backward's (PERF.md section 6, PR 31)
CELL_BWD_TILES = {1024: (512, 512), 4096: (512, 512)}


def _tiles_for(s, tiles):
    """An explicit pair where it divides ``s``; the derived default
    (block None) where it does not, as for 640."""
    return tiles if all(t is None or s % t == 0 for t in tiles) \
        else (None, None)


def _squared(out):
    return (out.astype(jnp.float32) ** 2).sum()


def _grads(f, q, k, v):
    return edge.out_and_grads(f, (q, k, v), _squared)[1]


@functools.lru_cache(maxsize=None)
def _oracle_at(seed, s, h, causal, dtype=jnp.float32):
    """``_qkv(seed, 1, s, h, 64)`` as ``dtype``, and the oracle's output and
    gradients in float32 on those values: one oracle for every tile pair of
    a length."""
    qkv = tuple(t.astype(dtype) for t in _qkv(
        np.random.RandomState(seed), b=1, s=s, h=h, d=64))
    return (qkv,) + edge.out_and_grads(
        lambda q, k, v: full_attention(q, k, v, causal=causal),
        tuple(t.astype(jnp.float32) for t in qkv), _squared)


@functools.lru_cache(maxsize=None)
def _flash_at(seed, s, h, causal, bq, bk, dtype=jnp.float32):
    """The kernels' output and gradients at ``_oracle_at``'s operands under
    one pair of tiles (640's four pairs are one derived default)."""
    return edge.out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=causal, block_q=bq,
                                        block_k=bk),
        _oracle_at(seed, s, h, causal, dtype)[0], _squared)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", LENGTHS)
@pytest.mark.parametrize("tiles", TILES)
def test_flash_tiles_forward_and_gradients_match_oracle(tiles, s, causal):
    """float32 inputs, today's tolerances, every tile pair (640 is a
    multiple of 128 and of nothing larger: its pairs fall to the derived
    default, which must still divide it)."""
    bq, bk = _tiles_for(s, tiles)
    seed = s + 7 * causal
    got, grads = _flash_at(seed, s, 1, causal, bq, bk)
    _, want, wants = _oracle_at(seed, s, 1, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    for name, a, b in zip("qkv", grads, wants):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} {bq}x{bk} s={s}")


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("tiles", TILES)
def test_flash_bfloat16_operands_match_float32_oracle(tiles, causal):
    """bfloat16 inputs against the oracle run in float32 on the same
    bfloat16 values: the products q k^T are exact, the probabilities and
    the output round to bfloat16, so the gap is a bfloat16 step (2^-8 of
    the largest value) and not a float32 one."""
    at = (11 + causal, 512, 2, causal)
    _, want, wants = _oracle_at(*at, jnp.bfloat16)
    got, grads = _flash_at(*at, *tiles, jnp.bfloat16)
    step = 2.0 ** -8
    assert got.dtype == jnp.bfloat16
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=0, atol=2 * step * np.abs(want).max())
    for name, a, b in zip("qkv", grads, wants):
        assert a.dtype == jnp.bfloat16
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a, np.float32), b, rtol=0,
                                   atol=4 * step * np.abs(b).max(),
                                   err_msg=f"d{name} {tiles}")


def test_flash_derived_tiles_over_several_tiles():
    """2,048 positions at the derived 1,024 x 1,024: a tile above the
    diagonal that is skipped (its index map repeats the block before),
    one the diagonal crosses, one wholly below it."""
    rng = np.random.RandomState(5)
    q, k, v = _qkv(rng, b=1, s=2048, h=1, d=64)
    assert attn.forward_tiles(2048, 2048, 64, 4) == (1024, 1024)
    got = flash_attention(q, k, v, causal=True)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_forward_tiles_rule_over_every_length():
    """The rule alone: for every multiple of 128 up to 8,192 the tiles
    divide the lengths and the reckoned VMEM is under the limit; the two
    cells' shapes get the tiles PERF.md records (section 6, PR 29)."""
    for d in (64, 128):
        for itemsize in (2, 4):
            for s in range(128, 8192 + 1, 128):
                for sk in {s, 128, 8192}:
                    bq, bk = attn.forward_tiles(s, sk, d, itemsize)
                    assert s % bq == 0 and sk % bk == 0, (s, sk, bq, bk)
                    assert bq in attn.FORWARD_TILES
                    assert bk in attn.FORWARD_TILES
                    assert attn.tile_vmem_bytes(bq, bk, d, itemsize) \
                        <= attn.VMEM_BUDGET, (s, sk, d, itemsize)
    # gpt2m-seq1024 and granite4hm-b2-seq4096, bfloat16
    assert attn.forward_tiles(1024, 1024, 64, 2) == CELL_TILES[1024]
    assert attn.forward_tiles(4096, 4096, 64, 2) == CELL_TILES[4096]
    # a multiple of 128 and of nothing larger; a length below every tile
    assert attn.forward_tiles(640, 1152, 64, 2) == (128, 128)
    assert attn.forward_tiles(256, 256, 64, 4) == (256, 256)
    with pytest.raises(ValueError):
        attn.forward_tiles(100, 128, 64, 4)
    assert attn.DEFAULT_BLOCK == 128



def _primitives(jaxpr):
    """Every equation of a jaxpr, nested ones included, as (primitive
    name, the ``name`` a pallas_call was given)."""
    found = []
    for eqn in jaxpr.eqns:
        meta = eqn.params.get("metadata") or {}
        found.append((eqn.primitive.name, eqn.params.get("name")
                      or meta.get("name")))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _primitives(sub)
    return found


@pytest.mark.parametrize("tiles", [(None, None), (512, 256)])
def test_backward_is_one_pallas_call_whatever_the_forward_chose(tiles):
    """The forward's tiles stop at the backward's rule: the gradient's
    jaxpr holds the forward's call and one call named ``flash_bwd``, no
    scan and no while."""
    s = 1024
    q = jnp.zeros((1, s, 1, 64), jnp.bfloat16)
    f = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=tiles[0],
        block_k=tiles[1]).astype(jnp.float32).sum()
    prims = _primitives(
        jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, q, q).jaxpr)
    names = [p for p, _ in prims]
    assert "scan" not in names and "while" not in names
    calls = [n for p, n in prims if p == "pallas_call"]
    assert len(calls) == 2 and sum("flash_bwd" in (n or "")
                                   for n in calls) == 1, calls


@pytest.mark.parametrize("d,itemsize", [(64, 2), (64, 4), (128, 2),
                                        (128, 4)])
def test_backward_tiles_rule_over_every_length(d, itemsize):
    """The backward's rule alone, over the lengths the forward's rule test
    walks: the tiles divide the lengths and the backward's own reckoning
    of VMEM is under the limit."""
    for s in range(128, 8192 + 1, 128):
        for sk in {s, 128, 8192}:
            bq, bk = attn.backward_tiles(s, sk, d, itemsize)
            assert s % bq == 0 and sk % bk == 0, (s, sk, bq, bk)
            assert bq in attn.BACKWARD_TILES and bk in attn.BACKWARD_TILES
            assert attn.backward_vmem_bytes(bq, bk, s, d, itemsize) \
                <= attn.VMEM_BUDGET, (s, sk, d, itemsize)
    # a multiple of 128 and of nothing larger
    assert attn.backward_tiles(640, 1152, d, itemsize) == (128, 128)
    with pytest.raises(ValueError):
        attn.backward_tiles(100, 128, d, itemsize)


def test_backward_tiles_of_the_two_cells():
    """gpt2m-seq1024 and granite4hm-b2-seq4096, bfloat16 (PERF.md section
    6, PR 31); the forward's 1,024 x 1,024 is over the backward's VMEM."""
    assert attn.backward_tiles(1024, 1024, 64, 2) == CELL_BWD_TILES[1024]
    assert attn.backward_tiles(4096, 4096, 64, 2) == CELL_BWD_TILES[4096]
    assert attn.backward_vmem_bytes(1024, 1024, 1024, 64, 2) \
        > attn.VMEM_BUDGET


@pytest.mark.parametrize("s,sk,causal", [
    (256, 384, False),     # more keys than queries, no mask
    (384, 128, False),     # fewer
    (256, 512, True),      # keys no query sees: their dk, dv are zero
    (1536, 1536, True),    # 512 x 512: a tile skipped, crossed, below
])
def test_flash_backward_tiles_match_oracle(s, sk, causal):
    """Gradients at the backward's derived tiles against the oracle where
    the lengths differ and where the causal square is several tiles."""
    rng = np.random.RandomState(s + sk)
    mk = lambda n: jnp.asarray(rng.randn(1, n, 1, 64).astype(np.float32)
                               * 0.5)
    q, k, v = mk(s), mk(sk), mk(sk)
    if s == 1536:
        assert attn.backward_tiles(s, sk, 64, 4) == (512, 512)
    flash = lambda q, k, v: flash_attention(q, k, v, causal=causal)
    ref = lambda q, k, v: full_attention(q, k, v, causal=causal)
    for name, a, b in zip("qkv", _grads(flash, q, k, v),
                          _grads(ref, q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} s={s} sk={sk}")


@pytest.mark.parametrize("prefix", ["", "bwd_"], ids=["forward", "backward"])
def test_tiles_are_recorded_once_per_shape(caplog, prefix):
    """The tiles chosen at either pass's trace: one ``# flash_tiles`` (``#
    flash_bwd_tiles``) debug line and the gauge pair a distinct shape, so a
    fall back to 128 is seen."""
    import logging
    from dt_tpu.obs import metrics as obs_metrics
    obs_metrics.set_enabled(True)
    try:
        obs_metrics.registry().clear()
        attn._note_tiles.cache_clear()
        attn._flash_fwd_pallas.clear_cache()   # an earlier test's traces
        attn._flash_bwd_pallas.clear_cache()
        q = jnp.zeros((1, 640, 1, 64), jnp.float32)
        run = lambda q: flash_attention(q, q, q, causal=True).sum()
        if prefix:
            run = jax.grad(run)
        with caplog.at_level(logging.DEBUG, logger="dt_tpu"):
            run(q)
            run(q)
        lines = [r.getMessage() for r in caplog.records
                 if r.getMessage().startswith(f"# flash_{prefix}tiles")]
        # fifteen of the grid's tiles run under causal; at one sub-block a
        # tile none is walked
        assert lines == [f"# flash_{prefix}tiles s=640 sk=640 d=64 "
                         "dtype=float32 block_q=128 block_k=128 "
                         "mask=causal.run15of25"]
        labels = {"shape": "640x640x64.float32",
                  "mask": "causal.run15of25"}
        gauges = [g for g in obs_metrics.registry().gauges_export()
                  if g[0].startswith(f"flash.{prefix}block_")]
        assert gauges == [[f"flash.{prefix}block_k", labels, 128.0],
                          [f"flash.{prefix}block_q", labels, 128.0]]
    finally:
        obs_metrics.set_enabled(None)
        obs_metrics.registry().clear()


# -- crossed tiles walked in sub-blocks (PR 42) -------------------------------


def _causal_oracle(q, k, v, allowed):
    """``full_attention`` over (heads, S, d) operands (``allowed`` is its
    own causal mask)."""
    to4 = lambda x: x[:, :, None]  # noqa: E731 — heads as the batch
    return full_attention(to4(q), to4(k), to4(v), causal=True)[:, :, 0]


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("sub", [128, 256])
def test_the_walk_under_causal_matches_the_oracle(sub, d):
    """1,024 positions: the forward's one tile and the backward's two
    diagonal tiles of 512 are crossed, and walked; inputs whose large scores
    sit at the ends of every row's allowed keys."""
    s = 1024
    pos = np.arange(s)
    assert attn.crossed_kinds(None, True, s, s, 1024, 1024, sub)
    assert attn.crossed_kinds(None, True, s, s, 512, 512, sub)
    edge.kernels_match(pos[None, :] <= pos[:, None], d, sub, causal=True,
                       oracle=_causal_oracle)


def test_the_walk_leaves_the_whole_tiles_results_where_they_were():
    """The same call with crossed tiles computed whole (``sub`` 0): every
    allowed pair is computed as before and a skipped sub-block added exact
    zeros, so the two agree to rounding's last place."""
    q, k, v, do = edge.spiky_inputs(np.tril(np.ones((1024, 1024), bool)), 64)
    kw = dict(scale=0.125, causal=True, interpret=True)
    got = []
    for sub in (0, 128):
        out, lse = attn._flash_fwd_pallas(q, k, v, block_q=None,
                                          block_k=None, sub=sub, **kw)
        got.append((out, lse) + tuple(attn._flash_bwd_pallas(
            q, k, v, out, lse, do, sub=sub, **kw)))
    for a, b in zip(*got):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bq,bk", [(256, 128), (128, 256), (128, 128)])
def test_tiles_without_a_static_pattern_are_computed_whole(bq, bk):
    """A caller's rectangular tiles, and square ones of one sub-block:
    no walk, the gauge reads 100 and the result is the oracle's."""
    assert attn.crossed_kinds(None, True, 512, 512, bq, bk, 128) is None
    q, k, v = _qkv(np.random.RandomState(5), b=1, s=512, h=1)
    gauges = edge.pairs_computed_gauges(lambda: np.testing.assert_allclose(
        flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk),
        full_attention(q, k, v, causal=True), rtol=2e-4, atol=2e-5))
    assert gauges == {"flash.pairs_computed_pct": 100.0}


def test_the_gauges_say_what_share_of_the_run_tiles_pairs_is_computed():
    """The first LM cell's shape: the forward's whole square walked at
    36 of 64 sub-blocks, the backward's three tiles of 512 at 2.25."""
    q = jnp.zeros((1, 1024, 1, 64), jnp.bfloat16)
    gauges = edge.pairs_computed_gauges(lambda: jax.eval_shape(jax.grad(
        lambda q: flash_attention(q, q, q, causal=True).sum().astype(
            jnp.float32)), q))
    assert attn.SUB_BLOCK == 128
    assert gauges == {"flash.pairs_computed_pct": 56.25,
                      "flash.bwd_pairs_computed_pct": 75.0}


def test_without_causal_or_a_rule_the_programs_hold_no_walk():
    """No tile is crossed: the forward's and the backward's programs are
    what they are with the walk off, two and five products a tile."""
    x, lse = jnp.zeros((2, 1024, 64)), jnp.zeros((2, 1024))
    kw = dict(scale=0.125, causal=False, interpret=True)
    texts = {sub: (str(jax.make_jaxpr(lambda q: attn._flash_fwd_pallas(
        q, q, q, block_q=None, block_k=None, sub=sub, **kw))(x)),
        str(jax.make_jaxpr(lambda q: attn._flash_bwd_pallas(
            q, q, q, q, lse, q, sub=sub, **kw))(x))) for sub in (None, 0)}
    assert texts[None] == texts[0]
    assert [t.count("dot_general") for t in texts[None]] == [2, 5]
    assert attn.crossed_kinds(None, False, 1024, 1024, 1024, 1024, 128) \
        is None


# the five LM cells' flash calls: (cell, rule, causal, positions, head size,
# bytes an element)
CELL_CALLS = [
    ("gpt2m", None, True, 1024, 64, 2),
    ("granite4hm", None, True, 4096, 64, 2),
    ("sdar30b", attn.BlockDiffusionMask(4096, 4), False, 8192, 128, 2),
    ("laguna.win", attn.WindowMask(512), True, 8192, 128, 2),
    ("laguna.full", None, True, 8192, 128, 2),
    ("keye30b", attn.SelectedKeysMask(), True, 16384, 128, 2),
]
# what ISSUE 42 reckoned at sub-blocks of 128: tile-equivalents computed of
# the tiles run, forward and backward
RECKONED = {"gpt2m": ((0.5625, 1), (2.25, 3)),
            "sdar30b": ((17.0, 24), (68.0, 80)),
            "laguna.win": ((19.375, 31), (19.375, 31)),
            "laguna.full": ((32.5, 36), (130.0, 136)),
            "granite4hm": ((8.25, 10), (33.0, 36)),
            "keye30b": ((129.0, 136), (516.0, 528))}


@pytest.mark.parametrize("sub", [128, 256])
@pytest.mark.parametrize("cell,rule,causal,s,d,itemsize", CELL_CALLS,
                         ids=[c[0] for c in CELL_CALLS])
def test_the_walks_sums_against_counts_made_one_sub_block_at_a_time(
        cell, rule, causal, s, d, itemsize, sub):
    """At each cell's shape and derived tiles, both passes: the tiles that
    run, the crossed ones and the tile-equivalents computed are what a count
    over ``rule.allowed``, tile by tile and sub-block by sub-block, gives;
    the walk is engaged; and it computes no fewer pairs than the rule needs
    (what keeps every roofline under 100)."""
    selected = isinstance(rule, attn.SelectedKeysMask)
    allowed = (lambda q, k: k <= q) if rule is None or selected \
        else rule.allowed
    for which, tiles in enumerate((attn.forward_tiles, attn.backward_tiles)):
        b, bk = tiles(s, s, d, itemsize, None if selected else rule)
        assert b == bk
        assert attn.crossed_kinds(rule, causal, s, s, b, b, sub)
        run, crossed, computed = attn.computed_tiles(rule, causal, s, s, b,
                                                     b, sub)
        *by_hand, pairs = edge.walked_by_hand(allowed, s, b, sub)
        assert (run, crossed, computed) == tuple(by_hand)
        assert 0 < crossed <= run and computed < run
        assert computed * b * b >= pairs
        if sub == 128:
            assert (computed, run) == RECKONED[cell][which]


# -- fewer key-value heads than query heads (PR 44) ---------------------------

def _spread(t, heads):
    """Each key-value head repeated for the query heads it serves."""
    return jnp.repeat(t, heads // t.shape[2], axis=2)


def _dense_spread(q, k, v, allowed):
    """A dense masked softmax in float32 over spread heads; ``allowed``
    (S, S)."""
    k, v = _spread(k, q.shape[2]), _spread(v, q.shape[2])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(allowed, s, attn.NEG_INF)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _grouped_inputs(seed, rep, d, dtype, b=2, s=256, kv=2):
    """q of ``kv * rep`` heads, k and v of ``kv``, and a weight on the
    output, as ``dtype`` and as the same values in float32."""
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(  # noqa: E731
        rng.randn(b, s, h, d).astype(np.float32) * 0.5).astype(dtype)
    args = (mk(kv * rep), mk(kv), mk(kv))
    w = jnp.asarray(rng.randn(b, s, kv * rep, d).astype(np.float32))
    return args, tuple(t.astype(jnp.float32) for t in args), w


def _close(got, want, dtype, grad=False):
    """The file's tolerances: float32 as the tile tests hold it, bfloat16
    within steps of the largest value."""
    want = np.asarray(want, np.float32)
    if dtype == jnp.bfloat16:
        steps = (4 if grad else 2) * 2.0 ** -8 * np.abs(want).max()
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=0, atol=steps)
    else:
        np.testing.assert_allclose(got, want, rtol=5e-4 if grad else 2e-4,
                                   atol=5e-4 if grad else 2e-5)


GROUPED_RULES = {
    "causal": dict(causal=True),
    "block_diffusion": dict(mask=attn.BlockDiffusionMask(128, 4)),
    "window": dict(mask=attn.WindowMask(100)),
}
GROUPS = [(1, 128), (4, 64), (4, 128), (8, 128)]
# and the same heads of 128 handed over as the projections' (B, S, H * D)
# arrays (PR 45): the kernels' in-place layout
LAYOUTS = [g + (False,) for g in GROUPS] + [
    g + (True,) for g in GROUPS if g[1] % 128 == 0]


def _weighed(w):
    return lambda out: (out.astype(jnp.float32) * w).sum()


@functools.lru_cache(maxsize=None)
def _grouped_case(rule, rep, d, dtype):
    """``_grouped_inputs``' operands and weight for a case, with the
    oracle's output and gradients on the same values in float32 and on
    spread heads: one oracle for both layouts of the operands."""
    kw = GROUPED_RULES[rule]
    args, args32, w = _grouped_inputs(rep + d, rep, d, dtype)
    if rule == "causal":
        ref = lambda q, k, v: full_attention(  # noqa: E731
            q, _spread(k, q.shape[2]), _spread(v, q.shape[2]), causal=True)
    else:
        pos = np.arange(args[0].shape[1])
        allowed = kw["mask"].allowed(pos[:, None], pos[None, :])
        ref = lambda q, k, v: _dense_spread(q, k, v, allowed)  # noqa: E731
    return (args, w) + edge.out_and_grads(ref, args32, _weighed(w))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rep,d,in_place", LAYOUTS, ids=[
    f"{r}-{d}" + ("-placed" if p else "") for r, d, p in LAYOUTS])
@pytest.mark.parametrize("rule", sorted(GROUPED_RULES))
def test_grouped_heads_match_the_oracle_on_spread_heads(rule, rep, d,
                                                        in_place, dtype):
    """(B, S, KV, D) keys and values under each static rule: the output
    and the three gradients are the oracle's on heads spread with
    ``jnp.repeat``; dk and dv come back with ``KV`` heads, summed over
    each group.  ``in_place``: the same operands as rank-3 arrays."""
    kw = GROUPED_RULES[rule]
    args, w, want, wants = _grouped_case(rule, rep, d, dtype)
    flash = edge.placed(**kw) if in_place \
        else lambda q, k, v: flash_attention(q, k, v, **kw)  # noqa: E731
    got, grads = edge.out_and_grads(flash, args, _weighed(w))
    assert got.dtype == dtype
    _close(got, want, dtype)
    for name, a, x, b in zip("qkv", grads, args, wants):
        assert a.shape == x.shape and a.dtype == dtype, name
        _close(a, b, dtype, grad=True)


def test_key_value_heads_that_do_not_divide_the_query_heads_are_refused():
    q, k = jnp.zeros((1, 128, 6, 64)), jnp.zeros((1, 128, 4, 64))
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(q, k, k, causal=True)
    with pytest.raises(ValueError, match="differ"):
        flash_attention(q, k[:, :, :3], k[:, :, :2], causal=True)


@pytest.mark.parametrize("prefix", ["", "bwd_"], ids=["forward", "backward"])
def test_tile_notes_name_the_group(caplog, prefix):
    """With grouped heads the debug line ends in ``rep=`` and the gauges
    carry the label; without them both are what they were (the test
    above)."""
    import logging
    from dt_tpu.obs import metrics as obs_metrics
    obs_metrics.set_enabled(True)
    try:
        obs_metrics.registry().clear()
        attn._note_tiles.cache_clear()
        attn._flash_fwd_pallas.clear_cache()
        attn._flash_bwd_pallas.clear_cache()
        q, k = jnp.zeros((1, 256, 8, 64)), jnp.zeros((1, 256, 2, 64))
        run = lambda q: flash_attention(q, k, k, causal=True).sum()  # noqa: E731
        if prefix:
            run = jax.grad(run)
        with caplog.at_level(logging.DEBUG, logger="dt_tpu"):
            jax.eval_shape(run, q)
        lines = [r.getMessage() for r in caplog.records
                 if r.getMessage().startswith(f"# flash_{prefix}tiles")]
        assert len(lines) == 1 and lines[0].endswith(" rep=4"), lines
        labels = [lk for name, lk, _ in obs_metrics.registry().gauges_export()
                  if name.startswith(f"flash.{prefix}")]
        assert len(labels) == 3 and all(lk["rep"] == "4" for lk in labels)
    finally:
        obs_metrics.set_enabled(None)
        obs_metrics.registry().clear()
        attn._note_tiles.cache_clear()


# -- operands in the projections' layout at head size 128 (PR 45) -------------

def _grad_equations(d, rep, in_place, kv=2, b=2, s=256, **kw):
    """The equations of the gradient of a sum through ``flash_attention`` at
    head size ``d``, (B, S, KV * rep, D) queries over (B, S, KV, D) keys,
    or with ``in_place`` the same as (B, S, H * D) arrays."""
    q, k = jnp.zeros((b, s, kv * rep, d)), jnp.zeros((b, s, kv, d))
    flash = edge.placed(**kw) if in_place \
        else lambda q, k, v: flash_attention(q, k, v, **kw)  # noqa: E731
    return list(edge.equations(jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash(q, k, v).sum(), argnums=(0, 1, 2)))(
            q, k, k).jaxpr))


def _kernel_operands(eqns):
    """The operands' shapes of each ``pallas_call``, the forward's first."""
    return [[v.aval.shape for v in e.invars] for e in eqns
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("rep", [1, 4, 8])
@pytest.mark.parametrize("rule", sorted(GROUPED_RULES))
def test_rank_3_operands_are_not_turned(rule, rep, b=2, s=256, kv=2, d=128):
    """Handed (B, S, H * D) q and (B, S, KV * D) k, v, both kernels take
    them and out, do as they are and give dq, dk, dv back so: the
    gradient's jaxpr holds no ``transpose`` of a (..., H, D) array.  What it
    does hold: the by-head view's permutation on delta's product and on dk,
    dv where a group is summed (``by_head``: no byte moves under the (8,
    128) tiling), and one turn of delta's (B, S / 8, H, 8) sums."""
    eqns = _grad_equations(d, rep, True, **GROUPED_RULES[rule])
    h = kv * rep
    turned = [(e.invars[0].aval.shape, e.params["permutation"])
              for e in eqns if e.primitive.name == "transpose"]
    views = [shape for shape, perm in turned if perm == (0, 1, 3, 2, 4)]
    assert len(views) == (1 if rep == 1 else 5), turned
    assert [t for t in turned if t[1] != (0, 1, 3, 2, 4)] == [
        ((b, s // 8, h, 8), (0, 2, 1, 3))], turned
    wide, narrow, row = (b, s, h * d), (b, s, kv * d), (b * h, 1, s)
    fwd, bwd = _kernel_operands(eqns)
    assert fwd == [wide, narrow, narrow]
    assert bwd == [wide, wide, row, row, narrow, narrow]
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [v.aval.shape for v in calls[0].outvars][0] == wide
    assert [v.aval.shape for v in calls[1].outvars] == [wide] * 3


def test_rank_3_operands_need_their_heads_and_whole_tiles():
    q, k = jnp.zeros((1, 128, 4 * 128)), jnp.zeros((1, 128, 2 * 128))
    for bad in (dict(), dict(heads=3), dict(heads=8)):      # 8: heads of 64
        with pytest.raises(ValueError, match="whole tiles"):
            flash_attention(q, k, k, causal=True, **bad)
    with pytest.raises(ValueError, match="goes with"):
        flash_attention(q.reshape(1, 128, 4, 128), k.reshape(1, 128, 2, 128),
                        k.reshape(1, 128, 2, 128), causal=True, heads=4)
    assert attn.lane_tiled(128, 8192) and attn.lane_tiled(256, 8, 16)
    assert not attn.lane_tiled(64, 8192) and not attn.lane_tiled(128, 12)


def _stripped(fn, *args):
    """A gradient's jaxpr as text, without the addresses of the closures a
    ``custom_vjp`` call carries."""
    import re
    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(
        jax.grad(fn, argnums=(0, 1, 2)))(*args)))


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("rep", [1, 4])
@pytest.mark.parametrize("rule", sorted(GROUPED_RULES))
def test_rank_4_operands_take_the_old_path_to_the_letter(rule, rep, d, b=2,
                                                         s=256, kv=2):
    """(B, S, H, D) operands, at head size 64 (where a (1, block, 64) block
    of an (H * 64)-wide array is no legal Mosaic block) and at 128 (where
    the view of them as (B, S, H * D) would be a pass over the array): heads
    go after the batch as they always did, the kernels take (B * H, S, D)
    operands, and the gradient's jaxpr is the one of that formulation
    written out here."""
    kw = GROUPED_RULES[rule]
    h = kv * rep
    eqns = _grad_equations(d, rep, False, **kw)
    tall, short, row = (b * h, s, d), (b * kv, s, d), (b * h, 1, s)
    assert _kernel_operands(eqns) == [
        [tall, short, short], [tall, tall, row, row, short, short]]
    # q, k, v and the cotangent in, out and dq, dk, dv back
    assert sum(e.primitive.name == "transpose"
               and e.invars[0].aval.ndim == 4 for e in eqns) == 8

    def by_hand(q, k, v):
        to3 = lambda x: jnp.moveaxis(x, 2, 1).reshape(-1, x.shape[1], d)  # noqa: E731
        out3 = attn._flash(to3(q), to3(k), to3(v), d ** -0.5,
                           kw.get("causal", False), None, None, True,
                           kw.get("mask"), None)
        return jnp.moveaxis(out3.reshape(b, h, s, d), 1, 2).sum()

    q, k = jnp.zeros((b, s, h, d)), jnp.zeros((b, s, kv, d))
    assert _stripped(
        lambda q, k, v: flash_attention(q, k, v, interpret=True, **kw).sum(),
        q, k, k) == _stripped(by_hand, q, k, k)


@pytest.mark.parametrize("prefix", ["", "bwd_"], ids=["forward", "backward"])
@pytest.mark.parametrize("d,in_place", [(128, True), (128, False),
                                        (64, False)])
def test_tile_notes_name_the_layout(caplog, prefix, d, in_place):
    """Where the kernels read the projections' arrays the debug line ends
    in ``layout=bshd`` and the gauges carry the label ``layout``; with
    four-axis operands neither is there."""
    import logging
    from dt_tpu.obs import metrics as obs_metrics
    obs_metrics.set_enabled(True)
    try:
        obs_metrics.registry().clear()
        attn._note_tiles.cache_clear()
        attn._flash_fwd_pallas.clear_cache()
        attn._flash_bwd_pallas.clear_cache()
        q, k = jnp.zeros((1, 256, 8, d)), jnp.zeros((1, 256, 2, d))
        flash = edge.placed(causal=True) if in_place \
            else lambda q, k, v: flash_attention(q, k, v, causal=True)  # noqa: E731
        run = lambda q: flash(q, k, k).sum()  # noqa: E731
        if prefix:
            run = jax.grad(run)
        with caplog.at_level(logging.DEBUG, logger="dt_tpu"):
            jax.eval_shape(run, q)
        lines = [r.getMessage() for r in caplog.records
                 if r.getMessage().startswith(f"# flash_{prefix}tiles")]
        assert len(lines) == 1, lines
        assert lines[0].endswith(" rep=4 layout=bshd" if in_place
                                 else " rep=4"), lines
        labels = [lk for name, lk, _ in obs_metrics.registry().gauges_export()
                  if name.startswith(f"flash.{prefix}")]
        assert len(labels) == 3
        assert [lk.get("layout") for lk in labels] == \
            [("bshd" if in_place else None)] * 3
    finally:
        obs_metrics.set_enabled(None)
        obs_metrics.registry().clear()
        attn._note_tiles.cache_clear()
