"""A decoder of gated short convolutions among attention layers whose experts
are chosen under a selection bias (PR 43): the model against the plain
reference at widths in the tens (objective, gradients, the bias a step moves,
three steps through ``Module.fit`` with the bias after each, and what fails
when a part is left out), the reference's mixer and attention layer against
the family's public implementation, the bias by hand (selection only, no
gradient, no moments, the update's sign and size, once a step under remat),
and the defaults against the parent's program."""

import functools
import importlib.util
import json
import os
import sys

import flax.linen as linen
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dt_tpu import data as dt_data, models
from dt_tpu.models import routed_lm
from dt_tpu.ops import losses, ssm
from dt_tpu.parallel import moe

import remat_held
from parent_cases import PARENT_CASES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)
import lfm2_drivers  # noqa: E402
import traffic as traffic_lib  # noqa: E402

CONFIG = "lfm2-8b-a1b"


def _load_reference():
    path = os.path.join(BENCH, "configs", CONFIG + "_reference.py")
    spec = importlib.util.spec_from_file_location("lfm2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()
with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
    PUBLISHED = json.load(f)
#: the published configuration at widths in the tens: five layers (conv +
#: dense, attention + routed, three of conv + routed), 4 query heads of 8
#: over 2, 8 experts of which this chip holds 4 from the third, 2 a token,
#: a tied head, a bias that is not zero and moves by a tenth of its spread
BATCH, SEQ = 2, 128
SMALL = {**PUBLISHED, "hidden_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 48,
         "moe_intermediate_size": 24, "num_experts_per_tok": 2,
         "num_experts": 4, "held_experts_first": 2,
         "published": {**PUBLISHED["published"], "num_experts": 8},
         "vocab_size": 64, "buffer_rows": 512, "attention": None,
         "dtype": "float32", "remat_blocks": False,
         "expert_bias_initial_std": 0.1, "expert_bias_update_speed": 0.02,
         # ranges at which every part of a layer shows in the loss
         "initializer_range": 0.25, "residual_out_initializer_range": 0.25,
         "embedding_initializer_range": 0.25}
#: two layers of it (a conv mixer and the attention layer, both routed) on
#: one short sequence: what the parts left out are tried on
PAIR = {**SMALL, "num_hidden_layers": 2, "num_dense_layers": 0,
        "layer_types": ["conv", "full_attention"], "buffer_rows": 64}
PAIR_TRAFFIC = {"batch": 1, "seq_len": 32}
TRAFFIC = {"generator": "traffic:uniform_tokens", "batch": BATCH,
           "seq_len": SEQ, "distinct_batches": 3, "steps_per_reading": 1,
           "warm_steps": 0}
STATE = lfm2_drivers.STATE
ROUTED_BLOCKS = ["block1", "block2", "block3", "block4"]


def _batch(seed=1):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, SMALL["vocab_size"], (BATCH, SEQ), dtype=np.int32)
    return toks, np.roll(toks, -1, axis=1)


def _gap(got, want):
    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)), got, want)))


def _objective(model, tree, data, labels):
    """The program's loss on ``tree`` (the job's: parameters with the
    biases beside them) -> (loss, what the step mutated)."""
    tree = dict(tree)
    variables = {"params": tree, STATE: tree.pop(STATE)}
    if not variables[STATE]:
        del variables[STATE]
    logits, mutated = model.apply(
        variables, data, mutable=["aux_loss", "counters", STATE])
    assert not jax.tree_util.tree_leaves(mutated.get("aux_loss", {}))
    return losses.softmax_cross_entropy(logits, labels), mutated


def _biases(stats):
    return [stats[name]["moe"]["selection_bias"] for name in ROUTED_BLOCKS]


def _with_biases(job, tree):
    """A reference tree under the program's names, the biases beside the
    parameters under ``batch_stats``."""
    return {**job.program_tree(tree), STATE: job.state_tree(tree)}


@pytest.fixture(scope="module")
def reference():
    """The reference's loss, gradient and moved biases on the toy, once."""
    tree = REF.init(jax.random.PRNGKey(3), SMALL)
    data, labels = _batch()
    params, biases = REF.split_bias(tree)
    with jax.default_matmul_precision("highest"):
        (loss, moved), grads = jax.jit(jax.value_and_grad(
            lambda p: REF.loss_fn(p, biases, data, labels, SMALL),
            has_aux=True))(params)
    none = [b if b is None else jnp.zeros_like(b) for b in biases]
    return tree, data, labels, loss, REF.join_bias(grads, none), \
        [b for b in moved if b is not None]


def test_objective_gradient_and_moved_bias_match_the_reference(
        reference, attention="flash", remat=True):
    """Under the flash kernels with every block rematerialised, as the cell
    runs (the plain path is the three steps through ``fit`` below)."""
    tree, data, labels, want, grads, moved = reference
    cfg = {**SMALL, "attention": attention, "remat_blocks": remat}
    job = lfm2_drivers.ShortConvMoEJob(cfg, TRAFFIC, 1, 0)
    model = job.mod.model
    assert model.saved_names == routed_lm.SAVED
    assert "conv_in_proj" in routed_lm.SAVED
    start = _with_biases(job, tree)
    (got, mutated), got_grads = jax.jit(jax.value_and_grad(
        lambda t: _objective(model, t, data, labels), has_aux=True))(start)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # no gradient reaches a bias: the selection passes none
    want_grads = _with_biases(job, grads)
    got_stats, _ = got_grads.pop(STATE), want_grads.pop(STATE)
    assert sorted(got_stats) == ROUTED_BLOCKS
    assert not any(np.asarray(b).any() for b in _biases(got_stats))
    assert _gap(got_grads, want_grads) < 2e-3
    # the tied table's gradient sums both uses; the taps, both gates'
    # projections and the router learn in every layer that has them
    assert "lm_head" not in got_grads
    for i, kind in enumerate(SMALL["layer_types"]):
        blk = got_grads[f"block{i}"]
        assert ("conv" in blk) == (kind == "conv") == ("attn" not in blk)
        assert ("mlp" in blk) == (i == 0) and ("moe" in blk) == (i > 0)
        leaves = [blk["conv"]["conv_kernel"],
                  blk["conv"]["in_proj"]["kernel"]] if kind == "conv" \
            else [blk["attn"]["q_norm"]["scale"]]
        for leaf in leaves + ([blk["moe"]["router"]] if i else []):
            assert float(jnp.max(jnp.abs(leaf))) > 0
    # the step moved each bias once (a rematerialised block's second
    # forward does not move it again), by u and as the reference moved it
    before = _biases(start[STATE])
    after = _biases(mutated[STATE])
    for b0, b1, ref_b in zip(before, after, moved):
        np.testing.assert_allclose(b1, ref_b, atol=1e-7)
        step = np.abs(np.asarray(b1 - b0))
        assert np.allclose(step[step > 0], 0.02, atol=1e-7) and step.any()
    # the counters: the routed layers', and what the bias moved
    counters = mutated["counters"]
    assert "block0" not in counters
    for name in ROUTED_BLOCKS:
        made = np.asarray(counters[name]["moe"]["moe_bias"][0])
        assert made.shape == (BATCH, len(moe.BIAS_COUNTERS))
        assert (made[:, 1] == SEQ * 2).all() and (made[:, 0] > 0).all()
        assert (made[:, 0] < SEQ).all()


def test_evaluation_and_init_leave_the_bias_alone():
    model = lfm2_drivers.ShortConvMoEJob(SMALL, TRAFFIC, 1, 0).mod.model
    data, _ = _batch()
    made = jax.eval_shape(model.init, jax.random.PRNGKey(0), data)
    assert sorted(made[STATE]) == ROUTED_BLOCKS
    layer = moe.RoutedExperts(num_experts=4, top_k=2, intermediate=8,
                              scoring="sigmoid", selection_bias=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 8))
    made = layer.init(jax.random.PRNGKey(1), x)
    assert not np.asarray(made[STATE]["selection_bias"]).any()   # zeros
    stats = {"selection_bias": jnp.asarray([0.2, 0.1, 0.0, -0.1])}
    # no collection mutable (the model's evaluation), or another one
    layer.apply({"params": made["params"], STATE: stats}, x)
    _, mutated = layer.apply({"params": made["params"], STATE: stats}, x,
                             mutable=["counters"])
    assert STATE not in mutated and "moe_bias" in mutated["counters"]


# -- three steps through fit ---------------------------------------------------

def test_three_steps_through_fit_match_the_reference_and_its_biases():
    """``Module.fit`` carries the biases in ``TrainState.batch_stats``: after
    each of three steps they are the reference's, the selection they make
    has changed, Adam holds no moments for them, and losses, first gradient
    and the change of parameters and biases are the reference's."""
    cfg = dict(SMALL)
    job = lfm2_drivers.ShortConvMoEJob(cfg, TRAFFIC, 1, 5)
    batches = traffic_lib.generate(TRAFFIC, cfg, 5)
    feed = traffic_lib.Feed(batches, dt_data.DataBatch, cast=job.cast)
    key = jax.random.PRNGKey(5)
    job.make_state(REF.init, key)
    start = _biases(jax.device_get(job.mod.state.batch_stats))
    assert all(np.asarray(b).any() for b in start)     # the seeded draw
    got = {"losses": [], "biases": [], "moved": []}
    for i in range(3):
        seen = []
        job.fit(feed.arm(1), [lambda p: seen.append(
            dict(p.eval_metric.get_name_value())["cross-entropy"])])
        got["losses"].append(float(seen[-1]))
        got["biases"].append(_biases(jax.device_get(
            job.mod.state.batch_stats)))
        got["moved"].append(sum(
            int(c["sum"][0]) for name, c in job.mod.step_counters.items()
            if name.endswith("/moe_bias")))
        if i == 0:
            first = job.first_gradient_host(key, job.mod.state)
    change = job.param_change_host(key, job.mod.state)
    shards = [(d[None], lb[None]) for d, lb in batches]
    with jax.default_matmul_precision("highest"):
        want = REF.train(key, shards, cfg, 3)
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    for step_got, step_want in zip(got["biases"], want["biases"]):
        for a, b in zip(step_got, step_want):
            np.testing.assert_allclose(a, b, atol=1e-7)
    # the bias moved the selection, and differently as it moved itself
    assert all(got["moved"]) and len(set(got["moved"])) > 1
    # the trees the benchmark compares are the parameters'; the biases'
    # change over the three steps is the reference's too
    assert STATE not in first and STATE not in change
    assert _gap(first, job.program_tree(want["first_gradient"])) < 2e-3
    assert _gap(change, job.program_tree(want["param_change"])) < 2e-2
    for b0, b3, ref_b in zip(start, got["biases"][-1], _biases(
            job.state_tree(want["param_change"]))):
        np.testing.assert_allclose(b3 - b0, ref_b, atol=1e-7)
        assert np.abs(np.asarray(b3 - b0)).max() <= 3 * 0.02 + 1e-7
    # Adam's moments follow the parameters' tree: nothing for a bias
    state = job.mod.state
    inner = getattr(state.opt_state, "inner", state.opt_state)
    for moments in (inner.a, inner.b):
        assert jax.tree_util.tree_structure(moments) == \
            jax.tree_util.tree_structure(state.params)
    assert "selection_bias" not in str(jax.tree_util.tree_structure(
        state.params))
    assert "selection_bias" in str(jax.tree_util.tree_structure(
        state.batch_stats))


# -- leaving a part out --------------------------------------------------------

class _BrokenConv(routed_lm.ShortConv):
    """``ShortConv`` without one of its parts."""
    without: str = ""

    @linen.compact
    def __call__(self, x, positions=None):
        d = x.shape[-1]
        dense = lambda n, name: linen.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        gate_in, gate_out, u = jnp.split(dense(3 * d, "in_proj")(x), 3,
                                         axis=-1)
        kernel = self.param("conv_kernel", linen.initializers.ones,
                            (self.taps, d), jnp.float32)
        if self.without != "gate_in":
            u = gate_in * u
        if self.without == "tap":
            kernel = kernel.at[0].set(0.0)
        u = ssm.causal_conv1d(u, kernel)
        if self.without != "gate_out":
            u = gate_out * u
        return dense(d, "out_proj")(u)


_ROUTE_TOP_K = moe.route_top_k


def _biased_weights(logits, k, scoring="softmax", bias=None, norm_eps=0.0):
    """``route_top_k`` with the weights gathered from ``s + bias``."""
    experts, weights, probs = _ROUTE_TOP_K(logits, k, scoring, bias, norm_eps)
    if bias is not None:
        scores = jax.nn.sigmoid(logits) + bias
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + norm_eps)
    return experts, weights, probs


PARTS = ["gate_in", "gate_out", "tap", "qk_norm", "bias_in_selection",
         "unbiased_weights", "bias_update", "tie", "norm_eps"]


@pytest.fixture(scope="module")
def pair_reference():
    """The reference's loss and moved biases on two layers, once."""
    tree = REF.init(jax.random.PRNGKey(4), PAIR)
    toks = np.random.default_rng(2).integers(0, 64, (1, 32), dtype=np.int32)
    params, biases = REF.split_bias(tree)
    with jax.default_matmul_precision("highest"):
        loss, moved = jax.jit(lambda p: REF.loss_fn(
            p, biases, toks, np.roll(toks, -1, axis=1), PAIR))(params)
    return tree, toks, np.roll(toks, -1, axis=1), loss, moved


@pytest.mark.parametrize("part", [None] + PARTS)
def test_leaving_a_part_out_fails_the_comparison(pair_reference, monkeypatch,
                                                 part):
    """``B *``, ``C *``, one tap, the norm on the heads' queries and keys,
    the bias in the selection, the unbiased weights, the bias's update, the
    tie, the renormalisation's epsilon: without any one the loss (or the
    moved bias) is not the reference's; with all of them (``None``) it
    is."""
    tree, data, labels, want, moved = pair_reference
    job = lfm2_drivers.ShortConvMoEJob(PAIR, PAIR_TRAFFIC, 1, 0)
    model, start = job.mod.model, _with_biases(job, tree)
    if part in ("gate_in", "gate_out", "tap"):
        monkeypatch.setattr(routed_lm, "ShortConv", functools.partial(
            _BrokenConv, without=part))
    elif part == "qk_norm":
        model = model.clone(qk_norm=False)
        start["block1"]["attn"] = {k: v for k, v in start["block1"][
            "attn"].items() if not k.endswith("_norm")}
        assert len(start["block1"]["attn"]) == 4
    elif part == "bias_in_selection":
        model, start[STATE] = model.clone(selection_bias=False), {}
    elif part == "unbiased_weights":
        monkeypatch.setattr(moe, "route_top_k", _biased_weights)
    elif part == "bias_update":
        monkeypatch.setattr(moe, "moved_bias", lambda bias, *_: bias)
    elif part == "tie":
        model = model.clone(tie_word_embeddings=False)
        start["lm_head"] = jnp.flip(start["embedding"], axis=0)
    elif part == "norm_eps":
        model = model.clone(router_norm_eps=0.05)
    got, mutated = _objective(model, start, data, labels)
    off = abs(float(got) - float(want)) / float(want)
    bias_off = max(float(jnp.max(jnp.abs(
        mutated[STATE][f"block{i}"]["moe"]["selection_bias"] - b)))
        for i, b in enumerate(moved)) if STATE in mutated else 1.0
    if part is None:
        assert off < 1e-5 and bias_off < 1e-7
    elif part == "bias_update":
        assert off < 1e-5 and bias_off > 0.01     # the next step's fault
    else:
        # ten times what the comparison allows the program
        assert off > 1e-4, (part, off)


# -- the bias by hand ----------------------------------------------------------

def test_the_bias_selects_and_the_unbiased_scores_weigh():
    logits = jnp.log(jnp.asarray([[1.0, 3.0, 1 / 3.0, 9.0]]))   # s = l/(1+l)
    scores = np.array([0.5, 0.75, 0.25, 0.9])
    bias = jnp.asarray([0.5, 0.0, 0.0, -0.5])     # s + bias: 1, .75, .25, .4
    experts, weights, probs = moe.route_top_k(logits, 2, "sigmoid", bias,
                                              norm_eps=1e-6)
    assert experts.tolist() == [[0, 1]]
    np.testing.assert_allclose(weights[0], np.array([0.5, 0.75])
                               / (1.25 + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(probs[0], scores / scores.sum(), rtol=1e-6)
    # exchanging either is another router: the selection from s alone ...
    plain, plain_w, _ = moe.route_top_k(logits, 2, "sigmoid")
    assert plain.tolist() == [[3, 1]]
    np.testing.assert_allclose(plain_w[0], [0.9 / 1.65, 0.75 / 1.65],
                               rtol=1e-6)
    # ... and the weights from s + bias
    assert not np.allclose(weights[0], np.array([1.0, 0.75]) / 1.75,
                           rtol=1e-3)
    # a bias of zeros selects as no bias does, and weighs as it
    same, same_w, _ = moe.route_top_k(logits, 2, "sigmoid", jnp.zeros(4))
    assert same.tolist() == plain.tolist()
    np.testing.assert_allclose(same_w, plain_w, rtol=1e-6)
    # the weights' gradient reaches the logits through s alone: none through
    # the bias, whatever it selects
    grad = jax.grad(lambda b: moe.route_top_k(logits, 2, "sigmoid", b)[
        1][0, 0])(bias)
    assert not np.asarray(grad).any()


def test_the_update_moves_each_bias_against_its_load():
    """A skewed router: experts 0 and 1 take everything.  Each step moves
    them down by ``u`` and the others up, until the selection changes."""
    experts = jnp.asarray([[0, 1]] * 6 + [[0, 2]] * 2)     # 8 tokens, k = 2
    # loads 8, 6, 2, 0 of mean 4
    bias = jnp.asarray([0.1, 0.0, -0.1, 0.0])
    load = moe.selection_load(experts, 4)
    assert load.tolist() == [8, 6, 2, 0]
    np.testing.assert_allclose(moe.moved_bias(bias, load, 0.25),
                               [-0.15, -0.25, 0.15, 0.25], atol=1e-7)
    # at the mean: unmoved
    even = moe.selection_load(jnp.asarray([[0, 1], [2, 3]]), 4)
    np.testing.assert_array_equal(moe.moved_bias(bias, even, 0.25), bias)
    np.testing.assert_allclose(
        moe.moved_bias(bias, load, 0.25),
        REF.moved_bias(bias, experts, {"expert_bias_update_speed": 0.25}),
        atol=1e-7)
    # through the layer: a router whose first two outputs always win, a
    # large u; three training steps, each selecting with the bias the one
    # before left, end with another selection
    layer = moe.RoutedExperts(num_experts=4, top_k=2, intermediate=8,
                              scoring="sigmoid", selection_bias=True,
                              bias_update_speed=0.3, norm_eps=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 8))
    made = layer.init(jax.random.PRNGKey(1), x)
    router = jnp.zeros((8, 4)).at[:, :2].set(
        jnp.abs(x[0]).mean(axis=0)[:, None] * 0.0)
    params = {**made["params"], "router": router}
    stats = {"selection_bias": jnp.asarray([0.2, 0.1, 0.0, -0.1])}
    seen = []
    step = jax.jit(lambda stats: layer.apply(
        {"params": params, "batch_stats": stats}, x,
        mutable=["batch_stats", "counters"]))
    for _ in range(3):
        _, mutated = step(stats)
        seen.append(np.asarray(mutated["counters"]["moe"][0])[0, :4].tolist())
        stats = mutated["batch_stats"]
    # scores all 0.5: the bias alone selects.  Step 1: experts 0, 1 (loads
    # 16, 16, 0, 0) -> bias (-.1, -.2, .3, .2); step 2: experts 2, 3 ->
    # (.2, .1, 0, -.1); step 3: experts 0, 1 again
    assert seen == [[16, 16, 0, 0], [0, 0, 16, 16], [16, 16, 0, 0]]
    np.testing.assert_allclose(stats["selection_bias"],
                               [-0.1, -0.2, 0.3, 0.2], atol=1e-6)


def test_a_rematerialised_block_moves_the_bias_once():
    block = dict(attn=None, moe=routed_lm._items(dict(
        num_experts=4, top_k=2, intermediate=8, scoring="sigmoid",
        selection_bias=True, bias_update_speed=0.25)),
        conv=routed_lm._items({"taps": 3}))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 8))
    plain = routed_lm.RoutedBlock(**block)
    remat = linen.remat(
        routed_lm.RoutedBlock,
        policy=jax.checkpoint_policies.save_only_these_names(
            *routed_lm.SAVED))(**block)
    made = plain.init(jax.random.PRNGKey(1), x)

    def moved(module):
        def loss(p):
            y, mutated = module.apply(
                {"params": p, "batch_stats": made["batch_stats"]}, x,
                mutable=["batch_stats", "counters"])
            return jnp.sum(y ** 2), mutated["batch_stats"]
        (_, stats), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
            made["params"])
        return stats["moe"]["selection_bias"], grads
    once, grads = moved(plain)
    again, remat_grads = moved(remat)
    # by u, or not at all where an expert's load is the mean
    steps = set(np.abs(np.asarray(once)).tolist())
    assert steps <= {0.0, 0.25} and 0.25 in steps
    np.testing.assert_array_equal(once, again)
    assert _gap(remat_grads, grads) < 1e-5
    # and keeps, by name, the mixer's in_proj output (T x 3 d) and what the
    # route hands on, not the gates' products nor the experts' hidden rows
    kept, _ = remat_held.held(remat, dict(made), x,
                              mutable=["batch_stats", "counters"])
    assert kept[((1, 16, 24), "float32")] == 1           # conv_in_proj
    assert kept[((32, 8), "float32")] == 1               # moe_up alone
    assert ((1, 16, 8), "float32") not in kept


def test_the_model_says_what_a_conv_layer_cannot_take():
    base = dict(vocab_size=16, embed_dim=16, num_layers=1, head_dim=8,
                attention=None, objective="causal")
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="a conv layer's record has no"):
        models.RoutedLM(layers=({"attention": "conv", "num_heads": 4},),
                        **base).init(jax.random.PRNGKey(0), tokens)
    made = models.RoutedLM(layers=({"attention": "conv", "mlp": "dense"},),
                           dense_intermediate=24, tie_word_embeddings=True,
                           **base).init(jax.random.PRNGKey(0), tokens)
    assert sorted(made["params"]) == ["block0", "embedding", "final_norm"]
    assert sorted(made["params"]["block0"]["conv"]) == [
        "conv_kernel", "in_proj", "out_proj"]
    assert made["params"]["block0"]["conv"]["conv_kernel"].shape == (3, 16)
    assert "batch_stats" not in made


# -- the reference against the family's public implementation -----------------

@pytest.mark.skipif(
    not os.environ.get("LFM2_AGAINST_TRANSFORMERS"),
    reason="importing torch and transformers costs the suite 25 s here: "
           "set LFM2_AGAINST_TRANSFORMERS=1 (passed when it was written)")
def test_reference_mixer_and_attention_match_transformers():
    """``short_conv`` and ``attention`` of the reference against
    ``Lfm2ShortConv.slow_forward`` and ``Lfm2Attention`` (eager) on copied
    seeded weights."""
    os.environ.setdefault("USE_TF", "0")        # the import alone: 30 s
    os.environ.setdefault("USE_FLAX", "0")
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    try:
        from transformers.models.lfm2 import configuration_lfm2, modeling_lfm2
    except ImportError as e:                     # an older transformers
        pytest.skip(f"no lfm2 in this transformers: {e}")
    cfg = {**SMALL, "initializer_range": 0.3,
           "residual_out_initializer_range": 0.3}
    hf = configuration_lfm2.Lfm2Config(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=SEQ, norm_eps=cfg["norm_eps"],
        rope_theta=float(cfg["rope_theta"]), conv_bias=False,
        conv_L_cache=cfg["conv_L_cache"],
        layer_types=["conv", "full_attention"])
    hf._attn_implementation = "eager"
    blocks = REF.init(jax.random.PRNGKey(11), cfg)["blocks"]
    conv_blk, attn_blk = blocks[0], blocks[1]
    attn_blk = {**attn_blk, "q_norm": attn_blk["q_norm"] * 1.5,
                "k_norm": attn_blk["k_norm"] * 0.5}
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(12), (2, SEQ, 32)))
    t = lambda a: torch.tensor(np.asarray(a))  # noqa: E731
    ident = lambda a: a  # noqa: E731
    with torch.no_grad(), jax.default_matmul_precision("highest"):
        mixer = modeling_lfm2.Lfm2ShortConv(hf, 0)
        mixer.in_proj.weight.copy_(t(conv_blk["win"].T))
        mixer.out_proj.weight.copy_(t(conv_blk["wout"].T))
        mixer.conv.weight.copy_(t(conv_blk["taps"].T[:, None, :]))
        want = mixer.slow_forward(t(x)).numpy()
        got = np.stack([REF.short_conv(jnp.asarray(s), conv_blk, cfg, ident)
                        for s in x])
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert np.abs(want).max() > 0.1

        layer = modeling_lfm2.Lfm2Attention(hf, 1)
        for name in ("q", "k", "v"):
            getattr(layer, name + "_proj").weight.copy_(
                t(attn_blk["w" + name].T))
        layer.out_proj.weight.copy_(t(attn_blk["wo"].T))
        layer.q_layernorm.weight.copy_(t(attn_blk["q_norm"]))
        layer.k_layernorm.weight.copy_(t(attn_blk["k_norm"]))
        pos = torch.arange(SEQ)[None].expand(2, -1)
        rotary = modeling_lfm2.Lfm2RotaryEmbedding(hf)(t(x), pos)
        mask = torch.full((SEQ, SEQ), float("-inf")).triu(1)[None, None]
        want, _ = layer(t(x), rotary, mask)
        got = np.stack([REF.attention(jnp.asarray(s), attn_blk, cfg, ident)
                        for s in x])
        np.testing.assert_allclose(got, want.numpy(), atol=2e-5)
        assert np.abs(want.numpy()).max() > 0.1


# -- the defaults against the parent's program --------------------------------

@pytest.mark.parametrize("case", sorted(PARENT_CASES))
def test_defaults_leave_the_three_routed_configurations_as_the_parent_had(
        case):
    """The same tree, leaf for leaf, the same output and the same counters
    as PR 42's ``RoutedLM`` gave (``tests/fixtures/routed_lm_parent43.npz``,
    made from a ``git archive`` of that commit by
    ``tests/parent_cases.py``'s arguments): on the machine that made the
    fixture to the last bit (checked when it was made); here to float32's
    rounding, since another CPU's kernels may round otherwise."""
    kw, shape = PARENT_CASES[case]
    parent = np.load(os.path.join(REPO, "tests", "fixtures",
                                  "routed_lm_parent43.npz"))
    model = models.RoutedLM(**kw)
    tokens = jax.random.randint(jax.random.PRNGKey(7), shape, 0, 40)
    made = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    assert "batch_stats" not in made
    params = made["params"]
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
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(x).reshape(-1) for x in
                        jax.tree_util.tree_leaves(mutated["counters"])]),
        parent[case + ".counters"])
    assert not any("moe_bias" in jax.tree_util.keystr(p) for p, _ in
                   jax.tree_util.tree_flatten_with_path(
                       mutated["counters"])[0])
