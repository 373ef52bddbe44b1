"""The routed expert layer (``parallel/moe.py`` ``RoutedExperts``), the
block-diffusion mask rule of the flash kernels, ``models/routed_lm.py``, the
weighted masked cross-entropy with its metric, and the noising transform, at
a small size on the CPU: against a dense masked softmax, and against the
benchmark's plain reference
(``benchmark/configs/sdar-30b-a3b-chat_reference.py``) on seeded weights.
Widths in the tens; the real widths run on the chip."""

import importlib.util
import json
import os
import sys
from collections import Counter

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from dt_tpu import data as dt_data, models
from dt_tpu.models import routed_lm
from dt_tpu.ops import losses
from dt_tpu.ops.pallas import attention as attn
from dt_tpu.ops.pallas.attention import BlockDiffusionMask, flash_attention
from dt_tpu.parallel import moe
from dt_tpu.training import Module, metrics as metrics_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)
import sdar_drivers  # noqa: E402
import remat_held  # noqa: E402  (tests/)
import flash_edge_cases as edge  # noqa: E402  (tests/)


def _load_reference(config="sdar-30b-a3b-chat"):
    path = os.path.join(BENCH, "configs", config + "_reference.py")
    spec = importlib.util.spec_from_file_location(
        config.split("-")[0] + "_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()
with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat.json")) as f:
    PUBLISHED = json.load(f)
#: the published configuration at widths in the tens: 16 experts of which
#: this chip holds 4 from the fifth, 2 a token, four query heads over two
BATCH, SEQ, BLOCK = 2, 24, 4
SMALL = {**PUBLISHED, "hidden_size": 32, "head_dim": 8,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "moe_intermediate_size": 24, "num_experts_per_tok": 2,
         "num_experts": 4, "held_experts_first": 4,
         "published": {**PUBLISHED["published"], "num_experts": 16},
         "num_hidden_layers": 2, "vocab_size": 40, "mask_token_id": 39,
         "buffer_rows": None, "block_length": BLOCK, "attention": None,
         "dtype": "float32", "remat_blocks": False}
TRAFFIC = {"batch": BATCH, "seq_len": SEQ, "block_length": BLOCK}


def _dense_attention(q, k, v, rule):
    pos = jnp.arange(q.shape[1])
    seen = rule.allowed(pos[:, None], pos[None, :])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# -- the mask rule, tile by tile ---------------------------------------------

@pytest.mark.parametrize("half,block,bq,bk", [
    (256, 4, 128, 128), (512, 4, 128, 256), (512, 8, 256, 128),
    (384, 6, 128, 128), (256, 256, 128, 128), (512, 1, 256, 256)])
def test_a_tiles_fate_and_fetch_follow_the_rule(half, block, bq, bk):
    """Against the rule applied element by element: a tile runs where any
    pair of it is allowed and is unmasked where all are, its band is the
    rule inside it, and a skipped step fetches a tile that runs."""
    rule = BlockDiffusionMask(half, block)
    pos = np.arange(2 * half)
    dense = np.asarray(rule.allowed(pos[:, None], pos[None, :]))
    # the rule as the issue words it
    noisy, blk = pos < half, (pos % half) // block
    q, k = np.ix_(pos, pos)
    assert (dense == np.where(noisy[k], noisy[q] & (blk[q] == blk[k]),
                              np.where(noisy[q], blk[k] < blk[q],
                                       blk[k] <= blk[q]))).all()
    ran = 0
    for qi in range(2 * half // bq):
        for ki in range(2 * half // bk):
            t = dense[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            runs, unmasked, *band = rule.tile(qi, ki, bq, bk)
            assert bool(runs) == t.any() and bool(unmasked) == t.all()
            ran += bool(runs)
            kt = int(rule.key_tile(qi, ki, bq, bk))
            qt = int(rule.query_tile(ki, qi, bq, bk))
            if t.any():
                assert (kt, qt) == (ki, qi)
                assert (np.asarray(attn._band_mask(rule, band, 0,
                                                   (bq, bk))) == t).all()
                assert (np.asarray(attn._band_mask(rule, band, 1,
                                                   (bk, bq))) == t.T).all()
            assert dense[qi * bq:(qi + 1) * bq, kt * bk:(kt + 1) * bk].any()
            assert dense[qt * bq:(qt + 1) * bq, ki * bk:(ki + 1) * bk].any()
    assert ran == rule.tiles_run(bq, bk)


def test_the_rule_prunes_to_about_a_quarter_at_the_cells_shape():
    rule = BlockDiffusionMask(4096, 4)
    assert rule.tiles_run(512, 512) == 80        # of 256: 1.25 x the area
    assert rule.tiles_run(1024, 1024) == 24      # of 64: 1.5 x the area
    assert attn.forward_tiles(8192, 8192, 128, 2, rule) == (1024, 1024)
    assert attn.backward_tiles(8192, 8192, 128, 2, rule) == (512, 512)
    # without the rule the cells' tiles are what they were
    assert attn.forward_tiles(4096, 4096, 64, 2) == (1024, 1024)
    assert attn.backward_tiles(1024, 1024, 64, 2) == (512, 512)
    with pytest.raises(ValueError):
        BlockDiffusionMask(100, 8)
    q = jnp.zeros((1, 256, 1, 8))
    with pytest.raises(ValueError):
        flash_attention(q, q, q, causal=True, mask=BlockDiffusionMask(128, 4))
    with pytest.raises(ValueError):
        flash_attention(q, q, q, mask=BlockDiffusionMask(256, 4))


# -- the kernels under the rule, in interpret mode ---------------------------

@pytest.mark.parametrize("half,block", [(256, 4), (384, 16), (256, 1)])
def test_kernels_under_the_block_mask_match_a_dense_masked_softmax(half,
                                                                   block):
    rule = BlockDiffusionMask(half, block)
    keys = jax.random.split(jax.random.PRNGKey(half + block), 4)
    q, k, v, g = (jax.random.normal(kk, (1, 2 * half, 2, 32), jnp.float32)
                  for kk in keys)
    (got, grads), (want, wants) = (edge.out_and_grads(
        fn, (q, k, v), lambda out: jnp.sum(out * g)) for fn in (
            lambda q, k, v: flash_attention(q, k, v, mask=rule),
            lambda q, k, v: _dense_attention(q, k, v, rule)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for name, a, b in zip("qkv", grads, wants):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} half={half}")


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("sub", [128, 256])
@pytest.mark.parametrize("half,block,tile", [
    (1024, 4, None),    # the cell's blocks, the derived tiles (1,024, 512)
    (384, 6, 384),      # a block that is no power of two: it divides no
                        # derived tile, so tiles of 384 are forced
    (384, 96, 384),     # blocks nearly a sub-block long
])
def test_the_walk_under_the_block_mask_matches_a_dense_masked_softmax(
        half, block, tile, sub, d):
    """The three quadrants' diagonal tiles are crossed (by the block
    diagonal, the strict and the inclusive block-causal triangle) and
    walked in sub-blocks; inputs whose large scores sit at the ends of
    every row's allowed keys.  Tiles of 384 are not whole sub-blocks of
    256: computed whole."""
    rule = BlockDiffusionMask(half, block)
    fwd = tile or attn.forward_tiles(2 * half, 2 * half, d, 4, rule)[0]
    kinds = attn.crossed_kinds(rule, False, 2 * half, 2 * half, fwd, fwd, sub)
    assert (kinds is None) == bool(fwd % sub)
    pos = np.arange(2 * half)
    edge.kernels_match(np.asarray(rule.allowed(pos[:, None], pos[None, :])),
                       d, sub, mask=rule, block=tile)
    if kinds:
        assert len(kinds) == 3
        *by_hand, _ = edge.walked_by_hand(rule.allowed, 2 * half, fwd, sub)
        assert attn.computed_tiles(rule, False, 2 * half, 2 * half, fwd, fwd,
                                   sub) == tuple(by_hand)


@pytest.mark.parametrize("half,block,bq,bk", [
    (384, 6, None, None),   # a block that divides no tile: an edge crosses
                            # every crossed tile at another place
    (512, 4, 256, 128)])    # a caller's rectangular tiles
def test_block_mask_tiles_without_a_static_pattern_are_computed_whole(
        half, block, bq, bk):
    rule = BlockDiffusionMask(half, block)
    tq, tk = attn.forward_tiles(2 * half, 2 * half, 32, 4, rule)
    assert attn.crossed_kinds(rule, False, 2 * half, 2 * half, bq or tq,
                              bk or tk, 128) is None
    q, k, v = (jax.random.normal(kk, (1, 2 * half, 1, 32), jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(half), 3))
    gauges = edge.pairs_computed_gauges(lambda: np.testing.assert_allclose(
        flash_attention(q, k, v, mask=rule, block_q=bq, block_k=bk),
        _dense_attention(q, k, v, rule), rtol=2e-5, atol=2e-5))
    assert gauges == {"flash.pairs_computed_pct": 100.0}


def test_a_length_that_needs_padding_to_the_tile():
    """The attention module pads each half to the tile: 72 positions a
    half become 128, and a padded key lies in a block after every real
    query's, so nothing of it shows; forward and backward against the
    module's own dense path."""
    half, x = 72, jax.random.normal(jax.random.PRNGKey(0), (2, 144, 32))
    kw = dict(num_heads=4, num_kv_heads=2, head_dim=8,
              mask=BlockDiffusionMask(half, 4))
    flash = routed_lm.RotaryAttention(attention="flash", **kw)
    plain = routed_lm.RotaryAttention(attention=None, **kw)
    variables = plain.init(jax.random.PRNGKey(1), x)
    (got, grads), (want, wants) = (edge.out_and_grads(
        m.apply, (variables, x), lambda out: jnp.sum(jnp.sin(out)))
        for m in (flash, plain))
    np.testing.assert_allclose(got, want, atol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(wants)):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_grouped_heads_reach_the_kernels_unspread():
    """Eight query heads over two key-value heads under ``attention="flash"``
    (PR 44): both kernels' key and value operands are ``(B * KV, S, D)``,
    nothing computed from the key and value projections outside a kernel is
    as large as a ``(B, S, H, D)`` array, and the layer's output and
    gradients are the dense path's, which spreads the heads."""
    b, s, h, kv, d = 2, 256, 8, 2, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, 32))
    kw = dict(num_heads=h, num_kv_heads=kv, head_dim=d,
              mask=BlockDiffusionMask(s // 2, 4))
    flash = routed_lm.RotaryAttention(attention="flash", **kw)
    plain = routed_lm.RotaryAttention(attention=None, **kw)
    variables = plain.init(jax.random.PRNGKey(1), x)
    objective = lambda m: lambda v, x: jnp.sum(jnp.sin(m.apply(v, x)))  # noqa: E731
    remat_held.assert_keys_reach_the_kernels_unspread(
        jax.grad(objective(flash), argnums=(0, 1)), (variables, x), b, s, h, kv,
        d)
    (got, grads), (want, wants) = (edge.out_and_grads(
        m.apply, (variables, x), lambda out: jnp.sum(jnp.sin(out)))
        for m in (flash, plain))
    np.testing.assert_allclose(got, want, atol=2e-5)
    for a, c in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(wants)):
        np.testing.assert_allclose(a, c, atol=5e-5)


def test_tile_notes_name_the_mask_rule(caplog):
    import logging
    from dt_tpu.obs import metrics as obs_metrics
    obs_metrics.set_enabled(True)
    try:
        obs_metrics.registry().clear()
        attn._note_tiles.cache_clear()
        attn._flash_fwd_pallas.clear_cache()
        attn._flash_bwd_pallas.clear_cache()
        q = jnp.zeros((1, 512, 1, 64), jnp.float32)
        rule = BlockDiffusionMask(256, 4)
        with caplog.at_level(logging.DEBUG, logger="dt_tpu"):
            jax.grad(lambda q: flash_attention(q, q, q, mask=rule).sum())(q)
        lines = [r.getMessage() for r in caplog.records
                 if "tiles" in r.getMessage()]
        assert len(lines) == 2 and all(
            "mask=block_diffusion.half256.block4.run3of4.crossed3."
            "sub2.0of3" in ln for ln in lines)
        gauges = {(n, dict(lk).get("mask", "")) for n, lk, _ in
                  obs_metrics.registry().gauges_export()}
        assert any(n == "flash.block_q" and m.startswith("block_diffusion")
                   for n, m in gauges)
        assert any(n == "flash.bwd_block_k" and m.startswith(
            "block_diffusion") for n, m in gauges)
        shares = {n: v for n, _, v in obs_metrics.registry().gauges_export()
                  if n.endswith("pairs_computed_pct")}
        assert shares == {
            "flash.pairs_computed_pct": pytest.approx(200 / 3),
            "flash.bwd_pairs_computed_pct": pytest.approx(200 / 3)}
    finally:
        obs_metrics.set_enabled(None)
        attn._note_tiles.cache_clear()


# -- the routed layer --------------------------------------------------------

#: for a layer alone: its output matrix at the others' range, so that a
#: result is of the inputs' size
LAYER = {**SMALL, "residual_out_initializer_range": 0.02}


#: widths that are whole lane tiles, with a buffer that is whole row tiles:
#: what the grouped products' kernels take (``ops/pallas/grouped.py``; the
#: widths in the tens take ``jax.lax.ragged_dot``), so the path the cells
#: run is compared here too.  One row tile of 128 rows for the 192
#: assignments of 96 tokens, of which 4 experts of 16 are held
LANES = {"hidden_size": 128, "moe_intermediate_size": 128,
         "buffer_rows": 128}


def _layer_inputs(seed=0, cfg=LAYER):
    x = jax.random.normal(jax.random.PRNGKey(seed),
                          (BATCH, 2 * SEQ, cfg["hidden_size"]))
    blk = REF.init(jax.random.PRNGKey(seed + 1), cfg)["blocks"][0]
    return x, blk


def _routed(x, blk, held, buffer_rows=None, aux_weight=0.0, **switches):
    layer = moe.RoutedExperts(num_experts=16, top_k=2,
                              intermediate=blk["gate"].shape[-1],
                              held=held, buffer_rows=buffer_rows,
                              aux_weight=aux_weight, **switches)
    first, count = held
    params = {"router": blk["router"]}
    if switches.get("selection_bias"):      # state, read and not written
        state = {"batch_stats": {"selection_bias": blk["bias"]}}
        return layer.apply({"params": _share(params, blk, first, count),
                            **state}, x, mutable=["aux_loss", "counters"])
    if switches.get("shared_intermediate"):
        params.update({n: {"kernel": blk[n]} for n in (
            "shared_gate", "shared_up", "shared_down")})
    return layer.apply({"params": _share(params, blk, first, count)}, x,
                       mutable=["aux_loss", "counters"])


def _share(params, blk, first, count):
    for name in ("gate", "up", "down"):     # a whole layer's: this share's
        params[name] = blk[name] if len(blk[name]) == count else \
            blk[name][first:first + count]
    return params


#: what a configuration's layer adds to softmax scores over routed experts
#: alone (``RoutedExperts``' switches), and which of the reference's layers
#: is a routed one
LAYER_SWITCHES = {
    "laguna-xs.2": dict(scoring="sigmoid", routed_scale=2.5,
                        shared_intermediate=24),
    "lfm2-8b-a1b": dict(scoring="sigmoid", selection_bias=True,
                        norm_eps=1e-6)}
LAYER_EXTRA = {
    "laguna-xs.2": dict(
        shared_expert_intermediate_size=24, intermediate_size=48,
        layer_types=["full_attention", "sliding_attention"],
        mlp_layer_types=["dense", "sparse"],
        num_attention_heads_per_layer=[4, 4]),
    # a bias of the scores' own spread: it moves a share of the picks
    "lfm2-8b-a1b": dict(
        intermediate_size=48, layer_types=["conv", "full_attention"],
        num_dense_layers=1, expert_bias_initial_std=0.2)}
#: over how many chips a configuration shares a layer's 16 experts
CHIPS = {"lfm2-8b-a1b": 4}


@pytest.mark.parametrize("config", ["sdar-30b-a3b-chat",
                                    "keye-vl-2.0-30b-a3b", "laguna-xs.2",
                                    "lfm2-8b-a1b"])
def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer(config):
    """16 experts over 8 chips, 2 each (over 4 chips, 4 each, where the
    configuration is cut that way): each share computed by the routed
    layer, with the router (and the selection bias, where there is one)
    whole; together the uncut reference's layer, by the plain reference of
    each configuration that is cut this way.  What every chip computes
    alike (a shared expert) is counted once."""
    ref, base = REF, LAYER
    switches = LAYER_SWITCHES.get(config, {})
    chips = CHIPS.get(config, 8)
    if config != "sdar-30b-a3b-chat":
        ref = _load_reference(config)
        with open(os.path.join(BENCH, "configs", config + ".json")) as f:
            published = json.load(f)
        base = {**published, **{k: LAYER[k] for k in (
            "hidden_size", "head_dim", "num_attention_heads",
            "num_key_value_heads", "moe_intermediate_size",
            "num_experts_per_tok", "num_hidden_layers", "vocab_size",
            "residual_out_initializer_range")},
            **LAYER_EXTRA.get(config, {}),
            "published": {**published["published"], "num_experts": 16}}
    whole = {**base, "num_experts": 16, "held_experts_first": 0}
    x = jax.random.normal(jax.random.PRNGKey(0), (BATCH, 2 * SEQ, 32))
    blk = ref.init(jax.random.PRNGKey(1), whole)["blocks"][-1]
    if switches.get("selection_bias"):
        want, _ = ref.experts(x.reshape(-1, 32), blk, blk["bias"], whole,
                              lambda a: a)
        # the bias moved some of the picks: its shares are another layer's
        plain = sum(_routed(x, blk, (4 * i, 4), **{
            **switches, "selection_bias": False})[0] for i in range(4))
        assert float(jnp.max(jnp.abs(plain.reshape(-1, 32) - want))) > 1e-4
    else:
        want, _ = ref.experts(x.reshape(-1, 32), blk, whole, lambda a: a)
    each = 16 // chips
    shares = [_routed(x, blk, (each * i, each), **switches)
              for i in range(chips)]
    total = sum(out for out, _ in shares)
    if switches.get("shared_intermediate"):
        # seven of the eight copies of what every chip computes alike
        alone, _ = _routed(x, blk, (0, 2), **{**switches,
                                              "shared_intermediate": None})
        shared = shares[0][0] - alone
        assert float(jnp.max(jnp.abs(shared))) > 1e-4
        total = total - 7 * shared
    np.testing.assert_allclose(total.reshape(-1, 32), want, atol=2e-6)
    # no share is the whole, and every assignment is in exactly one share
    assert float(jnp.max(jnp.abs(shares[0][0] - total))) > 1e-4
    counted = sum(np.asarray(m["counters"]["moe"][0]) for _, m in shares)
    assert (counted[:, -3] == counted[:, -1] // chips).all()  # held: S x k
    assert (counted[:, -2] == 0).all()


@pytest.mark.parametrize("widths", [{}, LANES], ids=["tens", "lanes"])
def test_a_share_matches_the_references_share_and_its_auxiliary_term(widths):
    cfg = {**LAYER, **widths}
    x, blk = _layer_inputs(cfg=cfg)
    out, mutated = _routed(x, blk, (4, 4), aux_weight=0.5,
                           buffer_rows=cfg["buffer_rows"])
    d = cfg["hidden_size"]
    want, aux = REF.experts(x.reshape(-1, d), blk, cfg, lambda a: a)
    np.testing.assert_allclose(out.reshape(-1, d), want, atol=2e-6)
    np.testing.assert_allclose(mutated["aux_loss"]["load_balance"][0],
                               0.5 * aux, rtol=1e-6)
    # the term is E sum f P over all 16 outputs; 2 (= k) where even
    assert 2.0 <= float(aux) < 4.0
    counters = np.asarray(mutated["counters"]["moe"][0])
    assert counters.shape == (BATCH, 4 + len(moe.COUNTER_TAIL))
    assert (counters[:, :4].sum(1) == counters[:, -3]).all()
    assert (counters[:, -1] == 2 * SEQ * 2).all()


def test_an_overflow_is_counted_and_shows_in_the_result():
    x, blk = _layer_inputs()
    full, counted = _routed(x, blk, (4, 4))
    load = int(np.asarray(counted["counters"]["moe"][0])[:, -3].sum())
    assert load > 16
    # a buffer with room, padded: the same result, whatever the padding
    roomy, m = _routed(x, blk, (4, 4), buffer_rows=load + 7)
    np.testing.assert_allclose(roomy, full, atol=1e-6)
    assert np.asarray(m["counters"]["moe"][0])[:, -2].sum() == 0
    # eight rows too few: eight assignments dropped, counted, and missing
    tight, m = _routed(x, blk, (4, 4), buffer_rows=load - 8)
    assert np.asarray(m["counters"]["moe"][0])[:, -2].sum() == 8
    assert float(jnp.max(jnp.abs(tight - full))) > 1e-5


def test_sort_held_groups_and_clips_in_expert_order():
    experts = jnp.asarray([[0, 5], [5, 6], [6, 9], [5, 1], [7, 5]])
    load = moe.selection_load(experts, 10)
    assert load.tolist() == [1, 1, 0, 0, 0, 4, 2, 1, 0, 1]
    order, sizes, held = moe.sort_held(experts, load, first=5, count=3,
                                       rows=6)
    assert held.tolist() == [4, 2, 1] and sizes.tolist() == [4, 2, 0]
    flat = np.asarray(experts).reshape(-1)
    assert flat[np.asarray(order)].tolist() == [5, 5, 5, 5, 6, 6]
    order, sizes, _ = moe.sort_held(experts, load, first=5, count=3, rows=9)
    assert sizes.tolist() == [4, 2, 1]
    assert flat[np.asarray(order)][:7].tolist() == [5, 5, 5, 5, 6, 6, 7]


# -- the model against the reference -----------------------------------------

def _job(cfg):
    return sdar_drivers.BlockDiffusionMoEJob(cfg, TRAFFIC, 1, 0)


def _batch(seed=1):
    rng = np.random.default_rng(seed)
    x0 = rng.integers(0, SMALL["mask_token_id"], (BATCH, SEQ))
    return dt_data.block_diffusion_noise(x0, BLOCK, SMALL["mask_token_id"],
                                         rng)


def _gap(got, want):
    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)), got, want)))


@pytest.mark.parametrize("attention,remat,widths", [
    (None, False, {}), ("flash", True, {}), ("flash", True, LANES)],
    ids=["plain", "flash-remat", "flash-remat-lanes"])
def test_objective_and_gradient_match_the_reference(attention, remat,
                                                    widths):
    cfg = {**SMALL, **widths, "attention": attention, "remat_blocks": remat}
    params = REF.init(jax.random.PRNGKey(3), cfg)
    data, labels = _batch()
    with jax.default_matmul_precision("highest"):
        (want, loss), grads = jax.jit(jax.value_and_grad(
            lambda p: REF.loss_fn(p, data, labels, cfg), has_aux=True))(
                params)
    job = _job(cfg)

    def objective(tree):
        logits, mutated = job.mod.model.apply(
            {"params": tree}, data, mutable=["aux_loss", "counters"])
        assert logits.shape == (BATCH, SEQ, cfg["vocab_size"])
        return losses.weighted_masked_cross_entropy(logits, labels) + sum(
            jax.tree_util.tree_leaves(mutated["aux_loss"]))

    got, got_grads = jax.jit(jax.value_and_grad(objective))(
        job.program_tree(params))
    assert float(want) > float(loss) > 0       # the auxiliary term is in it
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert _gap(got_grads, job.program_tree(grads)) < 2e-3
    # the routers (through the weights and the auxiliary term) and both
    # norms on the heads have a gradient in every layer; an expert has one
    # where a position chose it
    for i in range(cfg["num_hidden_layers"]):
        blk = got_grads[f"block{i}"]
        for leaf in (blk["moe"]["router"], blk["attn"]["q_norm"]["scale"],
                     blk["attn"]["k_norm"]["scale"]):
            assert float(jnp.max(jnp.abs(leaf))) > 0
    assert float(jnp.max(jnp.abs(got_grads["block0"]["moe"]["down"]))) > 0


def test_the_model_is_made_by_name_and_takes_only_the_pair_of_halves():
    model = models.create("routed_lm", vocab_size=40, embed_dim=32,
                          num_layers=1, num_heads=4, num_kv_heads=2,
                          head_dim=8, num_experts=4, moe_intermediate=24,
                          attention=None)
    toks = jnp.arange(48).reshape(2, 24) % 40
    variables = model.init(jax.random.PRNGKey(0), toks)
    logits = model.apply(variables, toks, mutable=["counters"])[0]
    assert logits.shape == (2, 12, 40) and logits.dtype == jnp.float32
    # a noisy position sees no later block, noisy or clean
    moved = model.apply(variables, toks.at[:, 11].set(7).at[:, 23].set(9),
                        mutable=["counters"])[0]
    np.testing.assert_allclose(moved[:, :8], logits[:, :8], atol=1e-6)
    assert float(jnp.max(jnp.abs(moved[:, 8:] - logits[:, 8:]))) > 1e-6
    with pytest.raises(ValueError):
        model.init(jax.random.PRNGKey(0), toks[:, :23])


def test_rope_turns_pairs_by_position():
    x = jnp.ones((1, 3, 1, 4))
    out = routed_lm.rope(x, jnp.arange(3), theta=100.0)
    np.testing.assert_allclose(out[0, 0, 0], [1, 1, 1, 1], atol=1e-6)
    # position 1: the pairs (x0, x2) by 1 radian, (x1, x3) by 0.1
    want = [np.cos(1) - np.sin(1), np.cos(.1) - np.sin(.1),
            np.cos(1) + np.sin(1), np.cos(.1) + np.sin(.1)]
    np.testing.assert_allclose(out[0, 1, 0], want, atol=1e-6)
    # a rotation: norms are kept
    np.testing.assert_allclose(jnp.sum(out ** 2, -1), 4.0, atol=1e-5)


# -- the loss, its metric, the transform -------------------------------------

def test_weighted_masked_cross_entropy_and_its_metric_agree():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 6, 11)), jnp.float32)
    labels = np.stack([rng.integers(0, 11, (2, 6)).astype(np.float32),
                       rng.choice([0.0, 1.5, 40.0], (2, 6))], -1)
    logp = np.asarray(jax.nn.log_softmax(logits))
    want = -sum(labels[b, i, 1] * logp[b, i, int(labels[b, i, 0])]
                for b in range(2) for i in range(6)) / 12
    np.testing.assert_allclose(
        losses.weighted_masked_cross_entropy(logits, jnp.asarray(labels)),
        want, rtol=1e-6)
    host, device = (metrics_lib.create("weighted-ce") for _ in range(2))
    host.update(labels, np.asarray(jax.nn.softmax(logits)))
    stats = metrics_lib.device_form(device)
    assert metrics_lib.stats_key(stats) == ("weighted_label_logp",)
    reduced = metrics_lib.device_reduce(stats, logits, jnp.asarray(labels))
    assert reduced["weighted_label_logp"].shape == (2, 6)
    device.update_reduced(labels, {k: np.asarray(v)
                                   for k, v in reduced.items()})
    np.testing.assert_allclose(host.get()[1], want, rtol=1e-6)
    np.testing.assert_allclose(device.get()[1], want, rtol=1e-6)
    # one number a row: reshaped as ever
    ce = metrics_lib.device_reduce(
        metrics_lib.device_form(metrics_lib.create("ce")), logits,
        jnp.asarray(labels[..., 0].reshape(-1)))
    assert ce["label_logp"].shape == (2, 6)


def test_the_noising_transform_masks_at_its_rate_with_its_weights():
    rng = np.random.default_rng(7)
    x0 = rng.integers(0, 99, (8, 4096))
    data, labels = dt_data.block_diffusion_noise(x0, 4, 99, rng)
    assert data.shape == (8, 8192) and data.dtype == np.int32
    assert labels.shape == (8, 4096, 2) and labels.dtype == np.float32
    xt, clean = data[:, :4096], data[:, 4096:]
    masked = xt == 99
    assert (clean == x0).all() and (xt[~masked] == x0[~masked]).all()
    assert (labels[..., 0] == x0).all()
    weight = labels[..., 1]
    assert ((weight > 0) == masked).all()
    # t uniform in [0.001, 1]: half the positions masked, weights 1/t >= 1
    assert abs(masked.mean() - 0.5005) < 0.01
    assert weight[masked].min() >= 1.0 and weight.max() <= 1000.0
    # one level a block: the masked positions of a block share a weight
    by_block = weight.reshape(8, 1024, 4)
    top = by_block.max(-1, keepdims=True)
    assert ((by_block == 0) | (by_block == top)).all()
    # E[1/t . 1(masked)] = 1 a position: the weights sum to about L
    assert abs(weight.mean() - 1.0) < 0.02
    with pytest.raises(ValueError):
        dt_data.block_diffusion_noise(x0[:, :4095], 4, 99, rng)


def test_the_iterator_noises_each_batch_anew_from_its_seed():
    x0 = np.arange(4 * 16).reshape(4, 16) % 30
    make = lambda seed: dt_data.BlockDiffusionIter(  # noqa: E731
        dt_data.NDArrayIter(x0, np.zeros(4), batch_size=2), 4, 30, seed=seed)
    a, b = list(make(5)), list(make(5))
    assert len(a) == 2 and a[0].data.shape == (2, 32)
    assert a[0].label.shape == (2, 16, 2)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.data, y.data)
        np.testing.assert_array_equal(x.label, y.label)
    assert any((x.data != y.data).any() for x, y in zip(a, list(make(6))))
    np.testing.assert_array_equal(a[1].data[:, 16:], x0[2:])


# -- what a rematerialised block keeps ----------------------------------------

def _block(policy_names, attention="flash", d=32, i=24):
    """One ``RoutedBlock`` as ``RoutedLM`` wraps it, with its variables and
    an input: 2 x 256 positions of width ``d``, four heads of 8 over two, 16
    experts of width ``i`` of which 4 are held, 2 a token, the worst-case
    buffer (1,024 rows).  At 128 and 128 the grouped products are the
    Pallas kernels."""
    import flax.linen as linen
    attn_kw = dict(num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=1e6,
                   mask=BlockDiffusionMask(128, 4), attention=attention)
    moe_kw = dict(num_experts=16, top_k=2, intermediate=i, held=(4, 4),
                  buffer_rows=None, aux_weight=0.001)
    cls = routed_lm.RoutedBlock if policy_names is None else linen.remat(
        routed_lm.RoutedBlock,
        policy=jax.checkpoint_policies.save_only_these_names(*policy_names))
    blk = cls(tuple(sorted(attn_kw.items())), tuple(sorted(moe_kw.items())),
              1e-6, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 256, d))
    params = jax.jit(routed_lm.RoutedBlock(
        tuple(sorted(attn_kw.items())), tuple(sorted(moe_kw.items())),
        1e-6, jnp.float32).init)(jax.random.PRNGKey(1), x)["params"]
    return blk, {"params": params}, x


def _remat_changes_no_number():
    cfg = {**SMALL, "attention": "flash"}
    data, labels = _batch()
    params = REF.init(jax.random.PRNGKey(3), cfg)
    got = []
    for remat in (False, True):
        job = _job({**cfg, "remat_blocks": remat})

        def objective(tree, model=job.mod.model):
            logits, mutated = model.apply(
                {"params": tree}, data, mutable=["aux_loss", "counters"])
            return losses.weighted_masked_cross_entropy(logits, labels) + sum(
                jax.tree_util.tree_leaves(mutated["aux_loss"]))
        got.append(jax.jit(jax.value_and_grad(objective))(
            job.program_tree(params)))
    (loss, grads), (loss_r, grads_r) = got
    np.testing.assert_allclose(loss_r, loss, rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(grads_r)):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-8,
                                   err_msg=jax.tree_util.keystr(path))


def _a_block_keeps_its_list(d=32, i=24):
    """Beyond its input and its parameters the block keeps the values on
    ``SAVED``, at the sizes the list's comment gives, and the integer
    indices jax derives from ``order`` for the two gathers."""
    b, s, h, hd, k, count = 2, 256, 4, 8, 2, 4
    t, r, f32 = b * s, b * s * k, "float32"
    # each name's values, (shape, dtype) -> count; the formula in the
    # list's comment at c = 4 bytes
    by_name = {
        "flash_out": {((b * h, s, hd), f32): 1},
        "flash_lse": {((b * h, s), f32): 1},
        "attn_out": {((b, s, d), f32): 1},
        "attn_qkv": {((b, s, h * hd), f32): 1, ((b, s, 2 * hd), f32): 2},
        # weights, sizes; order and each row's token, and beside those two
        # the indices jax derives from them for the two gathers
        "moe_route": {((t, k), f32): 1, ((count,), "int32"): 1,
                      ((r,), "int32"): 4},
        "moe_gate": {((r, i), f32): 1}, "moe_up": {((r, i), f32): 1},
        # the names of a layer with an index: under a mask rule the block
        # has none and keeps nothing for them (tests/test_sparse_attention)
        "dsa_selection": {}, "indexer_kl_grads": {},
        # and of a layer under a band (tests/test_mixed_attention.py)
        "flash_win_out": {}, "flash_win_lse": {},
        # and of a layer whose mixer is a gated short convolution
        # (tests/test_short_conv_lm.py)
        "conv_in_proj": {}}
    formula = {"dsa_selection": 0, "indexer_kl_grads": 0, "flash_win_out": 0,
               "flash_win_lse": 0, "conv_in_proj": 0,
               "flash_out": t * h * hd * 4, "flash_lse": t * h * 4,
               "attn_out": t * d * 4, "attn_qkv": t * (h + 2 * 2) * hd * 4,
               "moe_route": t * k * 4 + 2 * r * 4 + count * 4 + 2 * r * 4,
               "moe_gate": r * i * 4, "moe_up": r * i * 4}
    assert set(routed_lm.SAVED) <= set(by_name)
    for names in (routed_lm.SAVED, tuple(by_name)):   # the list; every name
        blk, variables, x = _block(names, d=d, i=i)
        kept, args = remat_held.held(blk, variables, x,
                                     mutable=["aux_loss", "counters"])
        assert sum(args.values()) == 1 + len(
            jax.tree_util.tree_leaves(variables))
        assert kept == sum((Counter(by_name[n]) for n in names), Counter())
        assert remat_held.held_bytes(kept) == sum(formula[n] for n in names)
    # and a policy that names nothing keeps nothing
    blk, variables, x = _block((), d=d, i=i)
    assert not remat_held.held(blk, variables, x,
                               mutable=["aux_loss", "counters"])[0]


def _the_gradient_calls_each_kernel_once(d=32, i=24, grouped=None):
    """``flash_out`` and ``flash_lse`` are on the list, so the backward pass
    does not run the forward kernel again; a block that keeps nothing runs
    it twice.  At widths that take the grouped products' kernels
    (``grouped`` = their calls with the list, with nothing kept) the list
    spares the recomputation of gate and up."""
    for n, (names, forward) in enumerate(((routed_lm.SAVED, 1), ((), 2))):
        blk, variables, x = _block(names, d=d, i=i)
        calls, total = remat_held.kernel_calls(
            blk, variables, x, mutable=["aux_loss", "counters"])
        want = {"flash_fwd_bd": forward, "flash_bwd_bd": 1}
        if grouped:
            want.update(grouped_mm=grouped[n] - 3, grouped_mm_t=3)
        assert calls == want
        assert total == sum(want.values())


def _the_gauge_counts_the_list(remat):
    from dt_tpu.obs import metrics as obs_metrics
    obs_metrics.set_enabled(True)
    try:
        obs_metrics.registry().clear()
        job = _job({**SMALL, "remat_blocks": remat})
        job.mod._metric_stats = metrics_lib.device_form(
            metrics_lib.create("weighted-ce"))
        job.mod._build_steps()
        gauges = {name: value for name, _, value in
                  obs_metrics.registry().gauges_export()}
    finally:
        obs_metrics.set_enabled(None)
    assert gauges["model.remat_blocks"] == int(remat)
    assert gauges["model.remat_saved_names"] == (
        len(routed_lm.SAVED) if remat else 0)
    assert "model.layers_ssm" not in gauges


@pytest.mark.parametrize("check", [
    _remat_changes_no_number, _a_block_keeps_its_list,
    _the_gradient_calls_each_kernel_once,
    lambda: _the_gauge_counts_the_list(True),
    lambda: _the_gauge_counts_the_list(False),
    lambda: _a_block_keeps_its_list(128, 128),
    # a layer's products: 3 forward, those the list does not spare again,
    # 6 backward (3 of them transposed)
    lambda: _the_gradient_calls_each_kernel_once(128, 128, (11, 12))],
    ids=["numbers", "held", "kernels", "gauge-on", "gauge-off",
         "held-lanes", "kernels-lanes"])
def test_rematerialised_blocks_keep_the_named_values(check):
    check()


# -- through fit --------------------------------------------------------------

def test_fit_reports_the_loss_and_the_layers_counters():
    """``Module.fit`` with the loss and the metric's device form: the
    metric is the reference's loss, the steps' counters reach the host with
    its statistics, and the gauges name each layer."""
    from dt_tpu.obs import metrics as obs_metrics
    cfg = {**SMALL, "buffer_rows": 2 * SEQ * BATCH}     # T x k / 2
    params = REF.init(jax.random.PRNGKey(3), cfg)
    data, labels = _batch()
    job = _job(cfg)
    job.make_state(REF.init, jax.random.PRNGKey(3))

    class Feed:
        batch_size = BATCH

        def reset(self):
            self.left = 2

        def next(self):
            if not self.left:
                raise StopIteration
            self.left -= 1
            return dt_data.DataBatch(data, labels, 0)

    seen = []
    obs_metrics.set_enabled(True)
    try:
        obs_metrics.registry().clear()
        job.fit(Feed(), [lambda p: seen.append(
            dict(p.eval_metric.get_name_value())["weighted-ce"])])
        gauges = {(n, dict(lk).get("layer")): v for n, lk, v in
                  obs_metrics.registry().gauges_export()}
    finally:
        obs_metrics.set_enabled(None)
    with jax.default_matmul_precision("highest"):
        _, want = REF.loss_fn(params, data, labels, cfg)
    np.testing.assert_allclose(seen[0], want, rtol=1e-5)
    assert job.mod.metric_flushes == {"device": 2, "host": 0}
    counted = job.mod.step_counters
    assert sorted(counted) == ["block0/moe/moe", "block1/moe/moe"]
    for name, c in counted.items():
        assert c["steps"] == 2 and c["sum"].shape == (4 + 3,)
        assert c["sum"][-1] == 2 * BATCH * 2 * SEQ * 2       # steps x T x k
        assert c["sum"][:4].sum() == c["sum"][-3] and c["sum"][-2] == 0
        assert (c["max"] <= c["sum"]).all() and c["max"][-3] > 0
        assert gauges[("moe.overflow_assignments", name)] == 0
        assert 0 < gauges[("moe.held_load_share_pct", name)] < 100
        assert gauges[("moe.fullest_over_mean_load", name)] >= 1
        assert 0 < gauges[("moe.buffer_fill_pct", name)] <= 100
