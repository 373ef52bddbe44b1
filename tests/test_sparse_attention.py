"""Attention over the keys a learned index picks, at a small size on the CPU:
the flash kernels under the selection rule (``SelectedKeysMask``, the
selection carried as packed bitmaps) against a dense masked softmax; the
exact top-k selection of ``ops/sparse_index.py`` against ``jax.lax.top_k``;
the KL term and the gradients its forward pass computes against autodiff of a
dense form; three-part rotary positions; and ``models.RoutedLM`` under the
causal objective against the benchmark's plain reference
(``benchmark/configs/keye-vl-2.0-30b-a3b_reference.py``) on seeded weights:
loss, gradients and three steps, and which parameters each term's gradient
reaches.  Widths in the tens; the real widths run on the chip."""

import functools
import importlib.util
import json
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from dt_tpu.models import routed_lm
from dt_tpu.ops import losses, sparse_index
from dt_tpu.ops.pallas import attention as attn
from dt_tpu.ops.pallas.attention import (NEG_INF, SelectedKeysMask,
                                         flash_attention, pack_selection)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)
import keye_drivers  # noqa: E402
import flash_edge_cases as edge  # noqa: E402  (tests/)


def _load_reference():
    path = os.path.join(BENCH, "configs", "keye-vl-2.0-30b-a3b_reference.py")
    spec = importlib.util.spec_from_file_location("keye_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()
with open(os.path.join(BENCH, "configs", "keye-vl-2.0-30b-a3b.json")) as f:
    PUBLISHED = json.load(f)
#: the published configuration at widths in the tens: 16 experts of which
#: this chip holds 4 from the fifth, 2 a token, four query heads over two,
#: four index heads of 8 that pick 48 keys of up to 256
BATCH, SEQ, TOPK = 2, 256, 48
SMALL = {**PUBLISHED, "hidden_size": 32, "head_dim": 16,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "moe_intermediate_size": 24, "num_experts_per_tok": 2,
         "num_experts": 4, "held_experts_first": 4,
         "published": {**PUBLISHED["published"], "num_experts": 16},
         "num_hidden_layers": 2, "vocab_size": 40, "buffer_rows": None,
         "rope_scaling": {**PUBLISHED["rope_scaling"],
                          "mrope_section": [2, 3, 3]},
         "sa_config": {**PUBLISHED["sa_config"], "indexer_head_dim": 8,
                       "indexer_num_heads": 4, "topk": TOPK,
                       "q_chunk_size": 128, "kv_chunk_size": 128},
         # the output matrices at the others' range: a position's state
         # then depends on what it attended to, and so on the selection
         "residual_out_initializer_range": 0.02,
         "attention": None, "dtype": "float32", "remat_blocks": False}
TRAFFIC = {"batch": BATCH, "seq_len": SEQ}


# -- the kernels under the selection rule -----------------------------------

def _dense(q, k, v, allowed, with_lse=False):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(allowed[:, None], s, NEG_INF)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return (out, jax.nn.logsumexp(s, -1)) if with_lse else out


def _top_keys(t, k, causal, seed=0):
    """A selection as an index makes it: each query's ``k`` largest of
    random scores (at or before it under ``causal``)."""
    scores = jax.random.normal(jax.random.PRNGKey(seed), (BATCH, t, t))
    valid = jnp.tril(jnp.ones((t, t), bool)) if causal else \
        jnp.ones((t, t), bool)
    scores = jnp.where(valid, scores, -jnp.inf)
    kth = jax.lax.top_k(scores, min(k, t))[0][..., -1:]
    return valid & (scores >= kth)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t,k,tiles", [
    (256, 48, (128, 128)),      # fewer keys than positions
    (256, 1000, (None, None)),  # more: every (causal) key
    (512, 64, (256, 128)),      # tiles that are not square
])
def test_both_kernels_under_a_selection_match_a_dense_masked_softmax(
        causal, t, k, tiles):
    allowed = _top_keys(t, k, causal)
    # under causal the bitmap also holds keys after the query: the kernels
    # hold a tile the diagonal crosses to both, and skip the tiles above
    handed = allowed if not causal else allowed | (
        _top_keys(t, k, False, 1) & ~jnp.tril(jnp.ones((t, t), bool)))
    sel = pack_selection(handed)
    q, kk, v = (jax.random.normal(key, (BATCH, t, 2, 32)) for key in
                jax.random.split(jax.random.PRNGKey(2), 3))
    w = jax.random.normal(jax.random.PRNGKey(3), q.shape)
    flash = lambda *a: flash_attention(  # noqa: E731
        *a, causal=causal, mask=SelectedKeysMask(), selection=sel,
        block_q=tiles[0], block_k=tiles[1], interpret=True, return_lse=True)
    weighed = lambda out: jnp.sum(out[0] * w)  # noqa: E731
    (out, lse), got = edge.out_and_grads(flash, (q, kk, v), weighed)
    (want, want_lse), ref = edge.out_and_grads(
        lambda *a: _dense(*a, allowed, with_lse=True), (q, kk, v), weighed)
    np.testing.assert_allclose(out, want, atol=2e-6)
    np.testing.assert_allclose(lse, want_lse, atol=2e-6)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b, atol=5e-6)


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("sub", [128, 256])
def test_the_walk_under_a_selection_beside_causal_matches_a_dense_softmax(
        sub, d):
    """1,024 positions, each query's 96 keys of random scores: the tiles
    the diagonal crosses (the forward's one, the backward's two of 512) are
    walked in sub-blocks with the bitmap on every sub-block computed and the
    causal compare on the diagonal ones alone; the bitmap also holds keys
    after the query, which the walk must not see.  Inputs whose large scores
    sit at each row's first and last selected key."""
    t = 1024
    allowed = _top_keys(t, 96, True)[:1]
    handed = allowed | (_top_keys(t, 96, False, 1)[:1]
                        & ~jnp.tril(jnp.ones((t, t), bool)))
    assert attn.crossed_kinds(SelectedKeysMask(), True, t, t, 512, 512, sub)
    edge.kernels_match(np.asarray(allowed[0]), d, sub,
                       mask=SelectedKeysMask(), causal=True,
                       selection=pack_selection(handed))
    # without causal no tile is crossed by a static edge: computed whole
    assert attn.crossed_kinds(SelectedKeysMask(), False, t, t, 512, 512,
                              sub) is None


def _weighed(w):
    return lambda out: jnp.sum(out[0].astype(jnp.float32) * w)


@functools.lru_cache(maxsize=None)
def _grouped_case(rep, d, dtype, t=256, kv=2):
    """A grouped case's selection, operands and weight, with the dense
    softmax's output, log-sum-exp and gradients on the same values in
    float32 and on spread heads: one oracle for both layouts."""
    allowed = _top_keys(t, 48, True)
    sel = pack_selection(allowed | (
        _top_keys(t, 48, False, 1) & ~jnp.tril(jnp.ones((t, t), bool))))
    keys = jax.random.split(jax.random.PRNGKey(rep + d), 4)
    args = tuple((0.5 * jax.random.normal(key, (BATCH, t, h, d))).astype(
        dtype) for key, h in zip(keys, (kv * rep, kv, kv)))
    w = jax.random.normal(keys[3], args[0].shape)
    dense = lambda q, k, v: _dense(  # noqa: E731
        q, *(jnp.repeat(x, rep, axis=2) for x in (k, v)), allowed,
        with_lse=True)
    return (sel, args, w) + edge.out_and_grads(
        dense, tuple(a.astype(jnp.float32) for a in args), _weighed(w))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rep,d,in_place", [
    (1, 128, False), (4, 64, False), (4, 128, False), (8, 128, False),
    (1, 128, True), (4, 128, True), (8, 128, True)])
def test_grouped_heads_under_a_selection_match_spread_heads(rep, d, in_place,
                                                            dtype):
    """(B, S, KV, D) keys and values under a selection beside ``causal``
    (PR 44: the index maps read key-value head ``h // rep``, and the
    bitmaps' own maps still divide by the query heads): output, log-sum-exp
    and the three gradients are the dense softmax's on heads spread with
    ``jnp.repeat``; float32 tight, bfloat16 within steps of the largest
    value as ``tests/test_flash_attention.py`` holds it."""
    t, kv = 256, 2
    sel, args, w, (want, want_lse), ref = _grouped_case(rep, d, dtype)
    kw = dict(causal=True, mask=SelectedKeysMask(), selection=sel,
              interpret=True, return_lse=True)
    # ``in_place``: the same heads as the projections' (B, S, H * D) arrays
    flash = edge.placed(**kw) if in_place \
        else lambda *a: flash_attention(*a, **kw)  # noqa: E731

    def close(got, want, steps):
        want = np.asarray(want, np.float32)
        atol = steps * 2.0 ** -8 * np.abs(want).max() \
            if dtype == jnp.bfloat16 else 5e-6
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=0, atol=atol)
    (out, lse), got = edge.out_and_grads(flash, args, _weighed(w))
    assert out.dtype == dtype and lse.shape == (BATCH, kv * rep, t)
    close(out, want, 2)
    close(lse, want_lse, 2)
    for a, x, b in zip(got, args, ref):
        assert a.shape == x.shape and a.dtype == dtype
        close(a, b, 4)


def test_a_tile_without_a_selected_pair_computes_nothing():
    """The fate table is what the kernel goes by: with an entry struck out
    the tile's pairs are missing from the result, so the tile did not run;
    with the bitmap's own table the result is the dense one."""
    t = 256
    allowed = _top_keys(t, 40, True).at[:, 128:, :128].set(False)
    allowed = allowed | jnp.eye(t, dtype=bool)
    sel = pack_selection(allowed)
    assert np.asarray(sel.blocks).tolist() == [
        [[True, False], [False, True]]] * BATCH
    run, of = SelectedKeysMask.tiles(sel.blocks, 128, 128, causal=True)
    assert run.tolist() == [2, 2] and of.tolist() == [3, 3]
    q, k, v = (jax.random.normal(key, (BATCH, t, 2, 32)) for key in
               jax.random.split(jax.random.PRNGKey(4), 3))
    flash = lambda s: flash_attention(  # noqa: E731
        q, k, v, causal=True, mask=SelectedKeysMask(), selection=s,
        block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(flash(sel), _dense(q, k, v, allowed),
                               atol=2e-6)
    # the same bitmaps, the first tile struck from the table: the first 128
    # queries' result is gone (nothing ran for them), the others' stays
    struck = sel._replace(blocks=sel.blocks.at[:, 0, 0].set(False))
    out = flash(struck)
    np.testing.assert_allclose(out[:, 128:], flash(sel)[:, 128:], atol=2e-6)
    assert float(jnp.max(jnp.abs(out[:, :128]))) == 0.0


def test_the_bitmaps_layout_and_its_packing_by_chunks():
    """Bit ``b`` of lane ``l`` of group ``g`` is key ``g * 4096 + b * 128 +
    l``; chunk by chunk (``pack_rows``, ``pack_columns``) gives what the
    whole array gives; unpacking gives the array back."""
    t = 384
    allowed = jax.random.bernoulli(jax.random.PRNGKey(0), 0.2, (1, t, t))
    sel = pack_selection(allowed)
    assert sel.by_query.shape == sel.by_key.shape == (1, 1, t, 128)
    words = np.asarray(sel.by_query[0, 0]).view(np.uint32)
    for q, key in ((5, 0), (5, 129), (300, 383), (17, 256 + 31)):
        assert (words[q, key % 128] >> (key // 128)) & 1 == \
            int(allowed[0, q, key])
    turned = np.asarray(sel.by_key[0, 0]).view(np.uint32)
    assert (turned[129, 5 % 128] >> (5 // 128)) & 1 == int(allowed[0, 5, 129])
    np.testing.assert_array_equal(attn.unpack_selection(sel.by_query, t),
                                  allowed)
    by_key = jnp.zeros((1, t, 128), jnp.int32)
    for first in range(0, t, 128):
        by_key = by_key | attn.pack_columns(
            jnp.swapaxes(allowed[0, first:first + 128], 0, 1), first)[None]
    np.testing.assert_array_equal(by_key, sel.by_key[0])
    np.testing.assert_array_equal(
        attn.unpack_tile(sel.by_query[0, 0, :128], 256, 128) != 0,
        allowed[0, :128, 256:])
    with pytest.raises(ValueError, match="go together"):
        flash_attention(*(jnp.zeros((1, t, 1, 8)),) * 3,
                        mask=SelectedKeysMask())
    with pytest.raises(ValueError, match="two bitmaps"):
        flash_attention(*(jnp.zeros((1, 256, 1, 8)),) * 3,
                        mask=SelectedKeysMask(), selection=sel)


def test_without_the_rule_the_kernels_programs_are_what_they_were():
    """The rule is static in kind: a causal call's program holds no operand
    and no name of the selection's."""
    x = jnp.zeros((1, 256, 2, 32))
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
        q, q, q, causal=True, interpret=True))))(x))
    assert "name=flash_bwd" in text and "_sel" not in text
    sel = pack_selection(_top_keys(256, 48, True)[:1])
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
        q, q, q, causal=True, mask=SelectedKeysMask(), selection=sel,
        interpret=True))))(x))
    assert "name=flash_fwd_sel" in text and "name=flash_bwd_sel" in text


# -- the index: scores, selection, the KL term -------------------------------

def _index_inputs(t=SEQ, hi=4, di=8, seed=0, batch=BATCH):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (batch, t, hi, di)),
            jax.random.normal(keys[1], (batch, t, di)),
            jax.random.normal(keys[2], (batch, t, hi)))


def _dense_scores(q_i, k_i, w):
    z = jnp.einsum("btjd,bsd->btjs", q_i, k_i)
    return jnp.einsum("btjs,btj->bts", jax.nn.relu(z), w) \
        * q_i.shape[-1] ** -0.5


def test_the_key_order_and_the_kth_largest_are_exact():
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, jnp.inf])
    keys = np.asarray(sparse_index.sortable_key(x))
    assert (np.diff(keys.astype(np.int64)) >= 0).all()
    assert keys[3] == keys[4]                       # -0.0 as 0.0
    rows = jax.random.normal(jax.random.PRNGKey(0), (8, 384)) * 5
    rows = rows.at[1, :200].set(-jnp.inf).at[2].set(rows[2].round())
    for k in (1, 7, 128, 384):
        got = sparse_index.kth_largest_key(sparse_index.sortable_key(rows), k)
        want = sparse_index.sortable_key(jax.lax.top_k(rows, k)[0][:, -1])
        np.testing.assert_array_equal(got, want)
    # fewer than k: 0, below every key
    assert sparse_index.kth_largest_key(
        sparse_index.sortable_key(rows), 385).tolist() == [0] * 8


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("k,q_chunk,kv_chunk", [(48, 256, 128),
                                                (1000, 128, 128)])
def test_the_selection_is_jax_lax_top_ks(causal, k, q_chunk, kv_chunk):
    q_i, k_i, w = _index_inputs()
    sel, index_lse, picked = sparse_index.select_keys(
        q_i, k_i, w, k, causal=causal, q_chunk=q_chunk, kv_chunk=kv_chunk)
    # the scores as the program makes them, a whole row at a time
    scores = jax.vmap(sparse_index.index_scores)(q_i, k_i, w)
    np.testing.assert_allclose(scores, _dense_scores(q_i, k_i, w), atol=1e-5)
    valid = jnp.tril(jnp.ones((SEQ, SEQ), bool)) if causal else \
        jnp.ones((SEQ, SEQ), bool)
    scores = jnp.where(valid, scores, -jnp.inf)
    # jax.lax.top_k's k-th value is the threshold; keys tied with it are all
    # kept (with four index heads a sixteenth of the scores are exactly 0)
    top, where = jax.lax.top_k(scores, min(k, SEQ))
    want = valid & (scores >= top[..., -1:])
    got = attn.unpack_selection(sel.by_query, SEQ)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        attn.unpack_selection(sel.by_key, SEQ), jnp.swapaxes(want, 1, 2))
    # top_k's own choice is in it, and is all of it where nothing ties
    assert bool(jnp.all(jnp.take_along_axis(got, where, axis=-1)
                        | ~jnp.take_along_axis(valid[None], where, axis=-1)))
    each = np.minimum(np.arange(SEQ) + 1, k) if causal else \
        np.full(SEQ, min(k, SEQ))
    count = np.asarray(jnp.sum(got, axis=-1))
    assert (count >= each).all() and (count == each).mean() > 0.7
    assert picked.tolist() == count.sum(axis=1).tolist()
    np.testing.assert_allclose(index_lse, jax.nn.logsumexp(
        jnp.where(want, scores, -jnp.inf), axis=-1), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(sel.blocks, jnp.any(want.reshape(
        BATCH, SEQ // 128, 128, SEQ // 128, 128), axis=(2, 4)))


def _dense_kl(q_i, k_i, w, q, k, chosen):
    """The KL term in its plainest form: autodiff gives its gradients."""
    heads, kv = q.shape[2], k.shape[2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, heads // kv, 2)) \
        * q.shape[-1] ** -0.5
    pbar = jnp.mean(jax.nn.softmax(
        jnp.where(chosen[:, None], s, -jnp.inf), -1), axis=1)
    log_pi = jax.nn.log_softmax(
        jnp.where(chosen, _dense_scores(q_i, k_i, w), -jnp.inf), -1)
    seen = chosen & (pbar > 0)
    kl = jnp.sum(jnp.where(seen, pbar * (jnp.log(jnp.where(seen, pbar, 1.0))
                                         - jnp.where(seen, log_pi, 0.0)),
                           0.0), axis=-1)
    return jnp.mean(kl, axis=-1)


@pytest.mark.parametrize("chunk", [128, 256])
def test_the_kl_term_and_the_gradients_its_forward_pass_computes(chunk):
    q_i, k_i, w = _index_inputs()
    q = jax.random.normal(jax.random.PRNGKey(5), (BATCH, SEQ, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(6), (BATCH, SEQ, 2, 16))
    sel, index_lse, _ = sparse_index.select_keys(q_i, k_i, w, TOPK,
                                                 q_chunk=128, kv_chunk=128)
    chosen = attn.unpack_selection(sel.by_query, SEQ)
    lse = _dense(q, jnp.repeat(k, 2, 2), jnp.repeat(k, 2, 2), chosen,
                 with_lse=True)[1]
    weights = jnp.asarray([1.0, 0.25])      # a cotangent for each sequence

    def mine(q_i, k_i, w, q, k, lse):
        return jnp.sum(weights * sparse_index.indexer_kl(
            q_i, k_i, w, q, k, lse, index_lse, sel, scale=16 ** -0.5,
            chunk=chunk))

    def plain(q_i, k_i, w, q, k):
        return jnp.sum(weights * _dense_kl(q_i, k_i, w, q, k, chosen))

    value, got = jax.jit(jax.value_and_grad(mine, (0, 1, 2, 3, 4, 5)))(
        q_i, k_i, w, q, k, lse)
    plain_value, want = jax.jit(jax.value_and_grad(plain, (0, 1, 2)))(
        q_i, k_i, w, q, k)
    np.testing.assert_allclose(value, plain_value, rtol=2e-5)
    assert float(plain_value) > 0.01
    for a, b in zip(got[:3], want):
        assert float(jnp.max(jnp.abs(b))) > 1e-5
        np.testing.assert_allclose(a, b, atol=2e-6 + 1e-4 * float(
            jnp.max(jnp.abs(b))))
    # the main attention is a constant to the term
    for a in got[3:]:
        assert float(jnp.max(jnp.abs(a))) == 0.0


# -- three-part rotary positions ---------------------------------------------

def test_mrope_is_rope_where_the_rows_are_equal_and_the_references_where_not():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3, 16))
    pos = jnp.arange(24) * 3 + 1
    np.testing.assert_allclose(
        routed_lm.mrope(x, jnp.broadcast_to(pos, (3, 24)), 1e7, (2, 3, 3)),
        routed_lm.rope(x, pos, 1e7), atol=1e-6)
    rows = jnp.stack([pos, jnp.arange(24) // 4, jnp.arange(24) % 4 + 7])
    got = routed_lm.mrope(x, rows, 1e7, (2, 3, 3))
    assert float(jnp.max(jnp.abs(got - routed_lm.rope(x, pos, 1e7)))) > 0.1
    for b in range(2):
        np.testing.assert_allclose(
            got[b], REF.mrope(x[b], rows, 1e7, [2, 3, 3]), atol=1e-6)
    # pair i of section r turns by rows[r] * theta^(-i/8), by hand
    i, t = 4, 9                                     # the second section
    angle = float(rows[1, t]) * 1e7 ** (-i / 8)
    a, b = x[0, t, 0, i], x[0, t, 0, i + 8]
    np.testing.assert_allclose(got[0, t, 0, i],
                               a * np.cos(angle) - b * np.sin(angle),
                               atol=1e-5)
    with pytest.raises(ValueError, match="cut"):
        routed_lm.mrope(x, rows, 1e7, (2, 3, 4))


# -- the model against the reference -----------------------------------------

def _job(cfg):
    return keye_drivers.SparseIndexMoEJob(cfg, TRAFFIC, 1, 0)


def _batch(seed=1):
    toks = np.random.default_rng(seed).integers(
        0, SMALL["vocab_size"], (BATCH, SEQ), dtype=np.int32)
    return toks, np.roll(toks, -1, axis=1)


def _gap(got, want):
    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)), got, want)))


def _terms(job, tree, data, labels):
    logits, mutated = job.mod.model.apply(
        {"params": tree}, data, mutable=["aux_loss", "counters"])
    aux = mutated["aux_loss"]
    kl = sum(jax.tree_util.tree_leaves(
        {k: v["attn"]["indexer_kl"] for k, v in aux.items()}))
    balance = sum(jax.tree_util.tree_leaves(
        {k: v["moe"] for k, v in aux.items()}))
    return losses.softmax_cross_entropy(logits, labels), kl, balance, \
        logits, mutated["counters"]


INDEX = ("index_q", "index_k", "index_w", "index_k_norm")


@functools.lru_cache(maxsize=None)
def _reference():
    """The seeded weights with the reference's objective, its two terms and
    its gradient on them and ``_batch()``: it reads neither ``attention`` nor
    ``remat_blocks``, so one run serves every case of ``SMALL``."""
    params = REF.init(jax.random.PRNGKey(3), SMALL)
    data, labels = _batch()
    with jax.default_matmul_precision("highest"):
        (want, (loss, want_kl)), grads = jax.jit(jax.value_and_grad(
            lambda p: REF.loss_fn(p, data, labels, SMALL), has_aux=True))(
                params)
    return params, want, loss, want_kl, grads


@pytest.mark.parametrize("attention,remat", [(None, False), ("flash", True)])
def test_objective_and_gradient_match_the_reference(attention, remat):
    cfg = {**SMALL, "attention": attention, "remat_blocks": remat}
    params, want, loss, want_kl, grads = _reference()
    data, labels = _batch()
    job = _job(cfg)

    def objective(tree):
        ce, kl, balance, logits, _ = _terms(job, tree, data, labels)
        assert logits.shape == (BATCH, SEQ, cfg["vocab_size"])
        return ce + kl + balance, (ce, kl)

    (got, (ce, kl)), got_grads = jax.jit(jax.value_and_grad(
        objective, has_aux=True))(job.program_tree(params))
    np.testing.assert_allclose(ce, loss, rtol=1e-5)
    np.testing.assert_allclose(kl, want_kl, rtol=1e-4)
    assert float(kl) > 0.01                 # the term is in the objective
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert _gap(got_grads, job.program_tree(grads)) < 2e-3
    for i in range(cfg["num_hidden_layers"]):
        for name in INDEX:
            for leaf in jax.tree_util.tree_leaves(
                    got_grads[f"block{i}"]["attn"][name]):
                assert float(jnp.max(jnp.abs(leaf))) > 0


def test_each_terms_gradient_reaches_its_own_parameters_only():
    """The index learns from the KL term alone, the rest of the model from
    the cross-entropy (and the load-balancing term) alone."""
    job = _job(SMALL)
    tree = job.program_tree(REF.init(jax.random.PRNGKey(3), SMALL))
    data, labels = _batch()
    by_kl, by_ce = jax.jit(lambda tree: (
        jax.grad(lambda t: _terms(job, t, data, labels)[1])(tree),
        jax.grad(lambda t: sum(_terms(job, t, data, labels)[0:3:2]))(tree)))(
            tree)
    def flat(g):
        return {jax.tree_util.keystr(p): float(jnp.max(jnp.abs(v)))
                for p, v in jax.tree_util.tree_flatten_with_path(g)[0]}
    kl, ce = flat(by_kl), flat(by_ce)
    for path in kl:
        inside = any(f"'{name}'" in path for name in INDEX)
        if inside:
            assert kl[path] > 0 and ce[path] == 0.0, path
        else:
            assert kl[path] == 0.0, path
    assert sum(v > 0 for v in ce.values()) >= len(ce) - 4 * 5 - 2


def test_three_steps_through_fit_follow_the_reference(topk=TOPK, rate=1e-5,
                                                      rtol=2e-5):
    """``Module.fit`` with the cross-entropy's device form: each step's
    loss and the parameters' change are the reference's, and the layers'
    counts reach the host."""
    from dt_tpu import data as dt_data
    cfg = {**SMALL, "attention": "flash", "remat_blocks": True,
           "sa_config": {**SMALL["sa_config"], "topk": topk},
           "optimizer": {**SMALL["optimizer"], "learning_rate": rate}}
    key = jax.random.PRNGKey(3)
    batches = [_batch(seed) for seed in (1, 2, 3)]
    with jax.default_matmul_precision("highest"):
        want = REF.train(key, [(d[None], lb[None]) for d, lb in batches],
                         cfg, 3)
    job = _job(cfg)
    job.make_state(REF.init, key)

    class Feed:
        batch_size = BATCH

        def reset(self):
            self.left = list(batches)

        def next(self):
            if not self.left:
                raise StopIteration
            return dt_data.DataBatch(*self.left.pop(0), 0)

    seen = []
    job.fit(Feed(), [lambda p: seen.append(
        dict(p.eval_metric.get_name_value())["cross-entropy"])])
    # the metric is the epoch's running mean: each step's own loss from it
    seen = np.diff(np.asarray(seen) * np.arange(1, 4), prepend=0.0)
    np.testing.assert_allclose(seen[0], want["losses"][0], rtol=2e-6)
    np.testing.assert_allclose(seen, want["losses"], rtol=rtol)
    assert seen[0] != seen[1] != seen[2]
    # the norm of the difference over the tree, as the benchmark compares it
    change = job.param_change_host(key, job.mod.state)
    leaves = jax.tree_util.tree_leaves
    diff = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(
        leaves(change), leaves(job.program_tree(want["param_change"]))))
    size = sum(float(jnp.sum(b ** 2)) for b in leaves(want["param_change"]))
    assert size > 0 and (diff / size) ** 0.5 < 0.05
    counted = job.mod.step_counters
    assert sorted(n for n in counted if n.endswith("/dsa")) == [
        "block0/attn/dsa", "block1/attn/dsa"]
    each = int(np.minimum(np.arange(SEQ) + 1, topk).sum())
    for name in ("block0/attn/dsa", "block1/attn/dsa"):
        c = counted[name]
        assert c["steps"] == 3 and c["sum"].shape == (
            len(routed_lm.DSA_COUNTERS),)
        assert c["sum"][0] >= 3 * BATCH * each      # ties add to it
        assert c["sum"][0] < 3 * BATCH * each * 1.05
        assert c["sum"][1] == 3 * BATCH * SEQ * (SEQ + 1) // 2
        assert 0 < c["sum"][2] <= c["sum"][3]
        assert 0 < c["sum"][4] <= c["sum"][5]
        assert c["sum"][6] > 0


@pytest.mark.parametrize("what", ["the KL term", "the selection", "the norm"])
def test_leaving_a_part_out_is_seen(what, monkeypatch):
    """The comparison holds the program to the whole layer: a program
    without the KL term, with every causal key in place of the selected ones
    (the selection left out for ``t >= k``) or without the index key's norm
    is not the reference's, in the objective or in a gradient."""
    cfg = dict(SMALL)
    params, want, _, _, grads = _reference()
    data, labels = _batch()
    broken = dict(cfg)
    if what == "the KL term":
        broken["indexer_kl_weight"] = 0.0
    elif what == "the selection":
        broken["sa_config"] = {**cfg["sa_config"], "topk": SEQ}
    else:
        import flax.linen as linen

        class NoNorm(linen.Module):
            epsilon: float = 0.0
            dtype: object = None

            def __call__(self, x):
                return x

        monkeypatch.setattr(routed_lm.linen, "LayerNorm", NoNorm)
    job = _job(broken)
    got, got_grads = jax.jit(jax.value_and_grad(
        lambda t: sum(_terms(job, t, data, labels)[:3])))(
            job.program_tree(params))
    off = abs(float(got) - float(want)) / float(want)
    assert off > 1e-3 or _gap(got_grads, job.program_tree(grads)) > 0.5, off


def test_a_rematerialised_block_with_an_index_keeps_its_list():
    """Beyond what a block under a mask rule keeps (tests/test_routed_lm.py),
    the selection and the gradients the KL term's forward pass computed, at
    the sizes the list's comment gives; the gradient then calls each flash
    kernel once, and the KL term's loops run once."""
    import flax.linen as linen
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import remat_held
    b, s, d, h, hd, hi, di = 2, 256, 32, 4, 8, 4, 8
    attn_kw = dict(num_heads=h, num_kv_heads=2, head_dim=hd, rope_theta=1e7,
                   mask=None, attention="flash", mrope_section=(1, 1, 2),
                   indexer=tuple(sorted(dict(
                       heads=hi, head_dim=di, top_k=48, q_chunk=128,
                       kv_chunk=128).items())))
    moe_kw = dict(num_experts=16, top_k=2, intermediate=24, held=(4, 4),
                  buffer_rows=None, aux_weight=0.001)
    make = lambda names: linen.remat(  # noqa: E731
        routed_lm.RoutedBlock,
        policy=jax.checkpoint_policies.save_only_these_names(*names))(
            tuple(sorted(attn_kw.items())), tuple(sorted(moe_kw.items())),
            1e-6, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, d))
    variables = {"params": jax.jit(make(()).init)(
        jax.random.PRNGKey(1), x)["params"]}
    mutable = ["aux_loss", "counters"]

    def held(names):
        """As ``remat_held.held``, of an objective with the block's terms in
        it: without them the KL term's gradients have nothing to do."""
        from collections import Counter
        from jax._src.ad_checkpoint import saved_residuals

        def objective(variables, x):
            out, mutated = make(names).apply(variables, x, mutable=mutable)
            return jnp.sum(out) + sum(jax.tree_util.tree_leaves(
                mutated["aux_loss"]))
        return Counter((tuple(aval.shape), jnp.dtype(aval.dtype).name)
                       for aval, why in saved_residuals(objective, variables,
                                                        x)
                       if "from the argument" not in why)

    kept = held(routed_lm.SAVED)
    mine = {((b, 1, s, 128), "int32"): 2,           # the two bitmaps
            ((b, s // 128, s // 128), "bool"): 1,   # the block table
            ((b, s, hi, di), "float32"): 1,         # the KL term's three
            ((b, s, di), "float32"): 1, ((b, s, hi), "float32"): 1}
    for key, n in mine.items():
        assert kept[key] == n, (key, kept)
    without = held(tuple(n for n in routed_lm.SAVED if n not in (
        "dsa_selection", "indexer_kl_grads")))
    # the comment's formula (the index log-sum-exp and the count of pairs
    # are kept with the selection; nothing reads them in the backward pass)
    assert remat_held.held_bytes(kept) - remat_held.held_bytes(without) <= \
        2 * b * s * s // 8 * (4096 // s) + b * (s // 128) ** 2 \
        + b * s * 4 + b * 4 + b * s * (hi * di + di + hi) * 4
    calls, _ = remat_held.kernel_calls(make(routed_lm.SAVED), variables, x,
                                       mutable)
    assert calls == {"flash_fwd_sel": 1, "flash_bwd_sel": 1}
    calls, _ = remat_held.kernel_calls(make(()), variables, x, mutable)
    assert calls == {"flash_fwd_sel": 2, "flash_bwd_sel": 1}


def test_the_block_diffusion_objective_takes_no_index():
    from dt_tpu import models
    model = models.create("routed_lm", vocab_size=40, embed_dim=32,
                             num_layers=1, num_heads=4, num_kv_heads=2,
                             head_dim=8, indexer={"heads": 2}, attention=None)
    with pytest.raises(ValueError, match="no index"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    with pytest.raises(ValueError, match="no objective"):
        models.create("routed_lm", objective="prefix").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    # plain causal attention, with and without the kernel, at a length that
    # needs padding to the tile, and at positions handed in
    model = lambda a: models.create(  # noqa: E731
        "routed_lm", vocab_size=40, embed_dim=32, num_layers=1, num_heads=4,
        num_kv_heads=2, head_dim=8, num_experts=4, objective="causal",
        attention=a)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 100), 0, 40)
    params = model(None).init(jax.random.PRNGKey(0), tokens)
    plain = model(None).apply(params, tokens)
    assert plain.shape == (2, 100, 40)
    np.testing.assert_allclose(model("flash").apply(params, tokens), plain,
                               atol=2e-5)
    moved = model(None).apply(params, tokens, positions=jnp.arange(100) + 5)
    np.testing.assert_allclose(moved, plain, atol=2e-5)   # rotary: relative
    # and causal: a later token changes no earlier position's logits
    other = tokens.at[:, 60].set((tokens[:, 60] + 1) % 40)
    np.testing.assert_allclose(model(None).apply(params, other)[:, :60],
                               plain[:, :60], atol=1e-6)
