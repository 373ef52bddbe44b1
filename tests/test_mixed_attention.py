"""A decoder whose layers alternate windowed and full attention (PR 41): the
band rule in both flash kernels against ``allowed``'s dense oracle, the rule's
sums against counts made one by one, YaRN and partial rotary against the
plain reference, the model against the reference at widths in the tens (and
what fails when a part is left out), the shares of a sigmoid-scored layer
with a shared expert, and the defaults against the parent's program."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dt_tpu import models
from dt_tpu.models import routed_lm
from dt_tpu.ops import losses
from dt_tpu.ops.pallas import attention as attn
from dt_tpu.ops.pallas.attention import WindowMask, flash_attention
from dt_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)
import laguna_drivers  # noqa: E402
import flash_edge_cases as edge  # noqa: E402

CONFIG = "laguna-xs.2"


def _load_reference():
    path = os.path.join(BENCH, "configs", CONFIG + "_reference.py")
    spec = importlib.util.spec_from_file_location("laguna_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()
with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
    PUBLISHED = json.load(f)
#: the published configuration at widths in the tens: five layers of both
#: kinds with 4 and 6 query heads over 2, a window of 40, the YaRN schedule
#: scaled from 32 positions, 8 experts of which this chip holds 4 from the
#: third, 2 a token, a dense first layer
BATCH, SEQ = 2, 128
FULL_ROPE = {**PUBLISHED["rope_parameters"]["full_attention"],
             "original_max_position_embeddings": 32, "factor": 8.0,
             "beta_fast": 4}
SMALL = {**PUBLISHED, "hidden_size": 32, "head_dim": 16,
         "num_key_value_heads": 2, "num_attention_heads": 4,
         "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
         "intermediate_size": 48, "moe_intermediate_size": 24,
         "shared_expert_intermediate_size": 24, "num_experts_per_tok": 2,
         "num_experts": 4, "held_experts_first": 2, "sliding_window": 40,
         "published": {**PUBLISHED["published"], "num_experts": 8},
         "rope_parameters": {**PUBLISHED["rope_parameters"],
                             "full_attention": FULL_ROPE},
         "vocab_size": 64, "buffer_rows": 512, "attention": None,
         "dtype": "float32", "remat_blocks": False,
         # ranges at which every part of a layer shows in the loss
         "initializer_range": 0.25, "residual_out_initializer_range": 0.25}
TRAFFIC = {"batch": BATCH, "seq_len": SEQ}


# -- the band rule in both kernels --------------------------------------------

def _dense_attention(q, k, v, rule):
    pos = jnp.arange(q.shape[1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where(rule.allowed(pos[:, None], pos[None, :]), scores,
                       attn.NEG_INF)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


@pytest.mark.parametrize("s,window,bq,bk", [
    (512, 100, 128, 128),       # smaller than a tile
    (512, 128, 128, 128),       # a tile
    (512, 200, 128, 128),       # larger than a tile
    (1024, 300, 256, 128),      # tiles that differ, either way round
    (1024, 300, 128, 256),
    (512, 1, 128, 128),         # the query's own key alone
    (512, 1000, 128, 128),      # larger than the sequence: causal
    (1024, 512, None, None),    # the derived tiles
], ids=lambda v: str(v))
def test_kernels_under_the_band_match_a_dense_masked_softmax(s, window, bq,
                                                             bk):
    rule = WindowMask(window)
    q, k, v = (jax.random.normal(key, (1, s, 2, 32))
               for key in jax.random.split(jax.random.PRNGKey(s + window), 3))
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, mask=rule, block_q=bq, block_k=bk, interpret=True)
    # the weight is the cosine of the oracle's output, a constant
    want, ref = edge.out_and_grads(
        lambda *a: _dense_attention(*a, rule), (q, k, v),
        lambda out: (out * jnp.cos(jax.lax.stop_gradient(out))).sum())
    weigh = jnp.cos(want)
    out, got = edge.out_and_grads(flash, (q, k, v),
                                  lambda out: (out * weigh).sum())
    np.testing.assert_allclose(out, want, atol=2e-6)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b, atol=2e-5)
    if window >= s:     # then the band is every causal key
        np.testing.assert_allclose(
            out, flash_attention(q, k, v, causal=True,
                                            interpret=True), atol=2e-6)
    else:               # and one key at the band's edge is seen in the result
        wider = _dense_attention(q, k, v, WindowMask(window + 1))
        assert float(jnp.max(jnp.abs(wider - want))) > 1e-3


@pytest.mark.parametrize("s,window,bq,bk", [
    (1024, 300, 128, 128), (1024, 300, 256, 128), (1024, 300, 128, 256),
    (1024, 129, 128, 128), (512, 2000, 128, 128), (1024, 512, 512, 512)])
def test_the_rules_sums_against_counts_made_one_by_one(s, window, bq, bk):
    rule = WindowMask(window)
    pos = np.arange(s)
    allowed = np.asarray(rule.allowed(pos[:, None], pos[None, :]))
    assert rule.pairs(s) == allowed.sum()
    tiles = allowed.reshape(s // bq, bq, s // bk, bk).any(axis=(1, 3))
    causal = (pos[None, :] <= pos[:, None]).reshape(
        s // bq, bq, s // bk, bk).any(axis=(1, 3))
    assert rule.tiles_run(s, bq, bk) == tiles.sum()
    assert rule.causal_tiles(s, bq, bk) == causal.sum()
    # the shortened axes hold every tile a row or a column of tiles needs,
    # and are no longer than the whole axis
    assert rule.key_steps(s, bq, bk) == tiles.sum(axis=1).max()
    assert rule.query_steps(s, bq, bk) == tiles.sum(axis=0).max()
    for qi in range(s // bq):
        lo, hi = rule.key_span(qi, bq, bk)
        assert list(np.flatnonzero(tiles[qi])) == list(range(lo, hi + 1))
    for ki in range(s // bk):
        lo, hi = rule.query_span(ki, bq, bk, s // bq)
        assert list(np.flatnonzero(tiles[:, ki])) == list(range(lo, hi + 1))


def test_the_band_at_the_cells_shape():
    """8,192 positions, a window of 512: an eighth of the causal pairs, two
    key tiles of sixteen a query tile at 512 x 512, and the share of the
    computed pairs that is needed by tile (ISSUE 41's reckoning)."""
    rule, s = WindowMask(512), 8192
    assert rule.pairs(s) == 4063488                     # 4.06M
    assert s * (s + 1) // 2 == 33558528                 # 33.56M
    assert rule.key_steps(s, 512, 512) == rule.query_steps(s, 512, 512) == 2
    assert rule.tiles_run(s, 512, 512) == 31
    assert rule.causal_tiles(s, 512, 512) == 136
    share = {b: rule.pairs(s) / (rule.tiles_run(s, b, b) * b * b)
             for b in (512, 256, 128)}
    assert 0.49 < share[512] < 0.51 and 0.66 < share[256] < 0.68 \
        and 0.79 < share[128] < 0.81
    assert rule.key_steps(s, 256, 256) == 3 and rule.key_steps(
        s, 128, 128) == 5
    for tiles in (attn.forward_tiles, attn.backward_tiles):
        assert tiles(s, s, 128, 2, rule) == (512, 512)
        assert tiles(s, s, 128, 2, WindowMask(100)) == (128, 128)
    # without the rule the tiles are what they were
    assert attn.forward_tiles(s, s, 128, 2) == (1024, 1024)
    assert attn.backward_tiles(s, s, 128, 2) == (512, 512)


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("sub", [128, 256])
@pytest.mark.parametrize("s,window", [(1536, 512), (1024, 256)])
def test_the_walk_under_the_band_matches_a_dense_masked_softmax(s, window,
                                                                sub, d):
    """A window of one tile: the diagonal tile meets the causal edge alone,
    the tile before it the band's edge alone, and both are walked in
    sub-blocks (tiles of 256 are one sub-block of 256: computed whole);
    inputs whose large scores sit at both ends of every row's band."""
    rule = WindowMask(window)
    assert attn.forward_tiles(s, s, d, 4, rule) == (window, window)
    kinds = attn.crossed_kinds(rule, True, s, s, window, window, sub)
    assert (kinds is None) == (sub == window)
    pos = np.arange(s)
    edge.kernels_match(np.asarray(rule.allowed(pos[:, None], pos[None, :])),
                       d, sub, mask=rule, causal=True)
    run, crossed, computed = attn.computed_tiles(rule, True, s, s, window,
                                                 window, sub)
    *by_hand, _ = edge.walked_by_hand(rule.allowed, s, window, sub)
    assert (run, crossed, computed) == tuple(by_hand) or kinds is None
    if kinds is None:
        assert (crossed, computed) == (0, run)


@pytest.mark.parametrize("s,window,tile", [(1024, 300, 256),
                                           (1024, 384, 256)])
def test_a_window_that_is_not_whole_tiles_is_computed_whole(s, window, tile):
    """The band's edge then crosses a tile at another place in every
    second tile: no static pattern, the whole-tile path, the gauges read
    100 and the result is the dense one."""
    rule = WindowMask(window)
    assert attn.forward_tiles(s, s, 32, 4, rule) == (tile, tile)
    assert attn.crossed_kinds(rule, True, s, s, tile, tile, 128) is None
    q, k, v = (jax.random.normal(key, (1, s, 1, 32))
               for key in jax.random.split(jax.random.PRNGKey(window), 3))

    def trace():
        flash = lambda *a: flash_attention(  # noqa: E731
            *a, mask=rule, interpret=True)
        np.testing.assert_allclose(flash(q, k, v),
                                   _dense_attention(q, k, v, rule), atol=2e-6)
        jax.eval_shape(jax.grad(lambda *a: flash(*a).sum()), q, k, v)

    assert edge.pairs_computed_gauges(trace) == {
        "flash.pairs_computed_pct": 100.0,
        "flash.bwd_pairs_computed_pct": 100.0}


def test_tile_notes_name_the_band(caplog):
    attn._note_tiles.cache_clear()
    q = jnp.zeros((1, 512, 1, 32))
    with caplog.at_level("DEBUG", logger="dt_tpu"):
        jax.eval_shape(lambda q: jax.grad(lambda q: flash_attention(
            q, q, q, mask=WindowMask(100), interpret=True).sum())(q), q)
    lines = [r.getMessage() for r in caplog.records
             if "flash_" in r.getMessage()]
    assert any("# flash_tiles" in ln and "mask=window100.run7of16" in ln
               for ln in lines), lines
    assert any("# flash_bwd_tiles" in ln and "window100" in ln
               for ln in lines), lines
    # the cell's shape: 31 tiles run, all crossed, 19.375 tile-equivalents
    # at sub-blocks of 128 (ten of a tile's sixteen), and the gauges
    q = jnp.zeros((1, 8192, 1, 128), jnp.bfloat16)
    with caplog.at_level("DEBUG", logger="dt_tpu"):
        gauges = edge.pairs_computed_gauges(lambda: jax.eval_shape(
            jax.grad(lambda q: flash_attention(
                q, q, q, mask=WindowMask(512), interpret=True).sum().astype(
                    jnp.float32)), q))
    assert gauges == {"flash.pairs_computed_pct": 62.5,
                      "flash.bwd_pairs_computed_pct": 62.5}
    assert sum("mask=window512.run31of256.crossed31.sub19.375of31" in
               r.getMessage() for r in caplog.records) == 2


# -- rotary over a part of a head, and the YaRN schedule ---------------------

def test_yarn_and_partial_rotary_match_the_reference():
    rope_cfg = PUBLISHED["rope_parameters"]["full_attention"]
    freq = routed_lm.yarn_frequencies(
        64, rope_cfg["rope_theta"], rope_cfg["factor"],
        rope_cfg["original_max_position_embeddings"], rope_cfg["beta_fast"],
        rope_cfg["beta_slow"])
    want, factor = REF.yarn_frequencies({**rope_cfg, "head_dim": 128})
    np.testing.assert_allclose(freq, want, rtol=1e-6)
    assert factor == pytest.approx(0.1 * np.log(64) + 1)
    plain = 500000.0 ** (-np.arange(32) / 32)
    # the fast pairs are kept, the slow ones divided by the factor, and a
    # ramp lies between
    assert freq[0] == pytest.approx(plain[0])
    assert freq[-1] == pytest.approx(plain[-1] / 64, rel=1e-5)
    assert ((freq < plain * (1 - 1e-6)) & (freq > plain / 64 * (1 + 1e-6))
            ).sum() >= 3
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3, 128))
    pos = jnp.arange(24) * 5
    got = routed_lm.rope_part(x, pos, freq, factor)
    for b in range(2):
        np.testing.assert_allclose(got[b], REF.rotary(x[b], pos, rope_cfg),
                                   rtol=1e-4, atol=1e-4)
    # the second half of the head passes through; the first is scaled too
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    assert float(jnp.max(jnp.abs(got[:, 0, :, :64]))) > 0


def test_a_factor_of_one_over_the_whole_head_is_rope():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 3, 16))
    pos = jnp.arange(24) * 7
    freq = routed_lm.yarn_frequencies(16, 1e4, 1.0, 32, 4, 1)
    np.testing.assert_allclose(freq, 1e4 ** (-np.arange(8) / 8), rtol=1e-6)
    np.testing.assert_allclose(routed_lm.rope_part(x, pos, freq),
                               routed_lm.rope(x, pos, 1e4), atol=1e-6)
    sliding = PUBLISHED["rope_parameters"]["sliding_attention"]
    np.testing.assert_allclose(REF.rotary(x[0], pos, sliding),
                               routed_lm.rope(x, pos, 1e4)[0], atol=1e-5)


# -- the model against the reference -----------------------------------------

def _batch(seed=1):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, SMALL["vocab_size"], (BATCH, SEQ), dtype=np.int32)
    return toks, np.roll(toks, -1, axis=1)


def _gap(got, want):
    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)), got, want)))


def _objective(model, tree, data, labels):
    logits, mutated = model.apply({"params": tree}, data,
                                  mutable=["aux_loss", "counters"])
    return losses.softmax_cross_entropy(logits, labels) + sum(
        jax.tree_util.tree_leaves(mutated["aux_loss"])), mutated


@pytest.fixture(scope="module")
def reference():
    """The reference's objective and gradient on the toy, once."""
    params = REF.init(jax.random.PRNGKey(3), SMALL)
    data, labels = _batch()
    with jax.default_matmul_precision("highest"):
        (want, loss), grads = jax.jit(jax.value_and_grad(
            lambda p: REF.loss_fn(p, data, labels, SMALL), has_aux=True))(
                params)
    return params, data, labels, want, loss, grads


@pytest.mark.parametrize("attention,remat", [(None, False), ("flash", True)],
                         ids=["plain", "flash-remat"])
def test_objective_and_gradient_match_the_reference(reference, attention,
                                                    remat):
    params, data, labels, want, loss, grads = reference
    cfg = {**SMALL, "attention": attention, "remat_blocks": remat}
    job = laguna_drivers.MixedAttentionMoEJob(cfg, TRAFFIC, 1, 0)
    model = job.mod.model
    assert model.saved_names == routed_lm.SAVED
    (got, mutated), got_grads = jax.jit(jax.value_and_grad(
        lambda t: _objective(model, t, data, labels), has_aux=True))(
            job.program_tree(params))
    assert float(want) > float(loss) > 0       # the auxiliary term is in it
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert _gap(got_grads, job.program_tree(grads)) < 2e-3
    # the gate, the shared expert and the router learn in every layer that
    # has them; the dense layer has no experts and sows no counters
    for i in range(5):
        blk = got_grads[f"block{i}"]
        assert float(jnp.max(jnp.abs(blk["attn"]["gate_proj"]["kernel"]))) > 0
        assert ("mlp" in blk) == (i == 0) and ("moe" in blk) == (i > 0)
        if i:
            for leaf in (blk["moe"]["router"],
                         blk["moe"]["shared_down"]["kernel"]):
                assert float(jnp.max(jnp.abs(leaf))) > 0
    counters = mutated["counters"]
    assert "block0" not in counters
    assert all("moe" in counters[f"block{i}"] for i in range(1, 5))
    # the windowed layers' static counts (under flash: tiles exist)
    windowed = [i for i in range(5) if "attn" in counters.get(f"block{i}",
                                                              {})]
    assert windowed == ([1, 2, 3] if attention else [])
    if attention:
        win = np.asarray(counters["block1"]["attn"]["win"][0])
        rule = WindowMask(40)
        assert win.shape == (BATCH, len(routed_lm.WIN_COUNTERS))
        assert (win[:, 0] == rule.pairs(SEQ)).all()
        assert (win[:, 3] == rule.tiles_run(SEQ, 128, 128)).all()


#: a model that leaves one part out: ``RoutedLM``'s switch where it has one
#: (the others change the full layers' rotary rule), and the reference's
#: weights such a model has no place for
SWITCHES = {
    "gate": dict(attn_gate=False),
    "shared_expert": dict(shared_intermediate=None),
    "scale_2.5": dict(routed_scale=1.0),
    "sigmoid_scores": dict(scoring="softmax"),
    "window": dict(window=SEQ),
    "window_edge": dict(window=41),
}
NO_PLACE_FOR = {"gate": "gate_proj", "shared_expert": "shared_"}


def _leaving_out(model, part):
    if part in SWITCHES:
        return model.clone(**SWITCHES[part])
    layers = []
    for rec in model.layers:
        rope = dict(rec["rope"])
        if part == "yarn_factor" and "yarn" in rope:
            rope["yarn"] = {**rope["yarn"], "attention_factor": 1.0}
        if part == "untouched_half":
            rope.pop("rotary_dim", None)
        layers.append({**rec, "rope": rope})
    return model.clone(layers=tuple(layers))


def _without(tree, name):
    return {k: _without(v, name) if isinstance(v, dict) else v
            for k, v in tree.items() if name not in k}


@pytest.mark.parametrize("part", sorted(SWITCHES) + ["yarn_factor",
                                                     "untouched_half"])
def test_leaving_a_part_out_fails_the_comparison(reference, part):
    """The gate, the shared expert, the scale, the sigmoid, the YaRN factor,
    the half of a full layer's head that does not turn, the window, one key
    at its edge: without any one the objective is not the reference's."""
    params, data, labels, want, _, _ = reference
    job = laguna_drivers.MixedAttentionMoEJob(SMALL, TRAFFIC, 1, 0)
    tree = job.program_tree(params)
    if part in NO_PLACE_FOR:
        tree = _without(tree, NO_PLACE_FOR[part])
    got, _ = _objective(_leaving_out(job.mod.model, part), tree, data,
                        labels)       # not jitted: eight models, one pass
    # ten times what the comparison allows the program
    assert abs(float(got) - float(want)) > 1e-4 * float(want), part


def test_the_model_says_what_it_cannot_build():
    base = dict(vocab_size=16, embed_dim=16, num_layers=1, head_dim=8,
                attention=None, objective="causal")
    tokens = jnp.zeros((1, 8), jnp.int32)
    for layers, message in (
            (({"attention": "linear"},), "no attention"),
            (({"mlp": "conv"},), "no feed-forward"),
            (({"heads": 4},), "has no"),
            (({}, {}), "2 records for 1 layers")):
        with pytest.raises(ValueError, match=message):
            models.RoutedLM(layers=layers, **base).init(
                jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="no layers that differ"):
        models.RoutedLM(layers=({},), **{**base,
                                          "objective": "block_diffusion"}
                        ).init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="no scoring"):
        moe.route_top_k(jnp.zeros((2, 4)), 2, "tanh")


# -- the shares of a layer with sigmoid scores and a shared expert ------------

def test_sigmoid_routing_by_hand():
    logits = jnp.log(jnp.asarray([[1.0, 3.0, 1 / 3.0, 9.0]]))   # s = l/(1+l)
    experts, weights, probs = moe.route_top_k(logits, 2, "sigmoid")
    assert experts.tolist() == [[3, 1]]
    scores = np.array([0.5, 0.75, 0.25, 0.9])
    np.testing.assert_allclose(weights[0], [0.9 / 1.65, 0.75 / 1.65],
                               rtol=1e-6)
    np.testing.assert_allclose(probs[0], scores / scores.sum(), rtol=1e-6)


def test_the_defaults_leave_the_routed_layer_as_it_was():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    kw = dict(num_experts=8, top_k=2, intermediate=24, held=(2, 4),
              aux_weight=0.01)
    old = moe.RoutedExperts(**kw)
    new = moe.RoutedExperts(scoring="softmax", routed_scale=1.0,
                            shared_intermediate=None, **kw)
    params = old.init(jax.random.PRNGKey(1), x)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(new.init(jax.random.PRNGKey(1), x))
    assert sorted(params["params"]) == ["down", "gate", "router", "up"]
    a, _ = old.apply(params, x, mutable=["aux_loss", "counters"])
    b, _ = new.apply(params, x, mutable=["aux_loss", "counters"])
    np.testing.assert_array_equal(a, b)


# -- the defaults against the parent's program --------------------------------

#: the two routed configurations' mechanisms at toy size, as
#: ``tests/fixtures/routed_lm_parent.npz`` was made from the parent commit's
#: ``RoutedLM`` (PR 40's tree): the same arguments, keys and tokens
PARENT_CASES = {
    "block_diffusion": (dict(
        vocab_size=40, embed_dim=32, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=8, num_experts=16, num_experts_per_tok=2,
        moe_intermediate=24, held_experts=(4, 4), block_length=4,
        attention=None), (2, 48)),
    "causal_index": (dict(
        vocab_size=40, embed_dim=32, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=2,
        moe_intermediate=24, held_experts=(2, 4), buffer_rows=128,
        objective="causal", attention=None, indexer=dict(
            heads=4, head_dim=8, top_k=32, q_chunk=128, kv_chunk=128,
            kl_weight=1.0), mrope_section=(2, 3, 3)), (1, 128)),
}


@pytest.mark.parametrize("case", sorted(PARENT_CASES))
def test_defaults_leave_the_routed_configurations_as_the_parent_had_them(
        case):
    """The same tree, leaf for leaf, and the same output: on the machine
    that made the fixture to the last bit (checked when it was made); here
    to float32's rounding, since another CPU's kernels may round
    otherwise."""
    kw, shape = PARENT_CASES[case]
    parent = np.load(os.path.join(REPO, "tests", "fixtures",
                                  "routed_lm_parent.npz"))
    model = models.RoutedLM(**kw)
    tokens = jax.random.randint(jax.random.PRNGKey(7), shape, 0, 40)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    logits, mutated = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, mutable=["aux_loss", "counters"]))(params, tokens)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    assert [jax.tree_util.keystr(p) + " " + str(tuple(a.shape))
            for p, a in leaves] == list(parent[case + ".paths"])
    np.testing.assert_allclose(
        [float(np.abs(np.asarray(a, np.float64)).sum()) for _, a in leaves],
        parent[case + ".sizes"], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(logits), parent[case + ".logits"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(sum(jax.tree_util.tree_leaves(mutated["aux_loss"]))),
        parent[case + ".aux"], rtol=1e-6)
