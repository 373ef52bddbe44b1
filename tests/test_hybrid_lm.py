"""``ops/ssm.py`` and ``models/hybrid_lm.py`` at a small size on the CPU,
against the one-position recurrence and the benchmark's plain reference
(``benchmark/configs/granite-4.0-h-micro_reference.py``) on seeded weights.
Widths in the tens, chunks of 4 or 8; the real widths run on the chip.
"""

from collections import Counter

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from dt_tpu import models
from dt_tpu.models import hybrid_lm

from hybrid_small import BATCH, REF, SEQ, SMALL, hybrid_drivers
import remat_held
import traffic  # benchmark/traffic.py, on the path since hybrid_small


def _job(cfg):
    return hybrid_drivers.HybridLMJob(
        cfg, {"batch": BATCH, "seq_len": SEQ}, 1, 0)


def _tokens(cfg, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (BATCH, SEQ), 0,
                              cfg["vocab_size"])
    return toks, jnp.roll(toks, -1, axis=1)


def _program_loss_and_grad(job, tree, tokens, labels):
    from dt_tpu.ops import losses
    return jax.jit(jax.value_and_grad(
        lambda t: losses.softmax_cross_entropy(
            job.mod.model.apply({"params": t}, tokens), labels)))(tree)


def _gap(got, want):
    """The largest leaf's largest difference, against that leaf's largest
    value."""
    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)), got, want)))


@pytest.fixture(scope="module")
def seeded():
    """The reference's weights from a seed, a batch, and the reference's
    loss and gradient on it."""
    params = REF.init(jax.random.PRNGKey(3), SMALL)
    tokens, labels = _tokens(SMALL)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(lambda p: REF.loss_and_grad(
            p, tokens, labels, SMALL))(params)
    return params, tokens, labels, float(loss), grads


def test_mixer_matches_the_reference(seeded):
    blk = seeded[0]["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (BATCH, SEQ, 32))
    mixer = hybrid_lm.Mamba2Mixer(n_heads=4, d_head=16, d_state=8, chunk=4)
    tree = _job(SMALL).program_tree(
        {"embed": None, "norm_f": None, "blocks": [blk]})["block0"]["mamba"]
    got = jax.jit(mixer.apply)({"params": tree}, x)
    want = jax.jit(jax.vmap(
        lambda seq: REF.mamba_mixer(seq, blk, SMALL, lambda a: a)))(x)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def test_model_matches_the_reference_loss_gradient_and_three_steps(seeded):
    """The whole model on the reference's seeded weights: logits, loss, the
    gradient of every leaf, then three Adam steps through ``Module.fit``
    against the reference's ``train``."""
    from dt_tpu import data as dt_data
    ref_params, tokens, labels, ref_loss, ref_grads = seeded
    job = _job(SMALL)
    key = jax.random.PRNGKey(3)
    tree = job.program_tree(ref_params)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(job.mod.model.apply)({"params": tree}, tokens)
        want = jax.jit(jax.vmap(lambda t: REF.forward(ref_params, t, SMALL)))(
            tokens)
        assert logits.dtype == jnp.float32
        np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-6)
        loss, grads = _program_loss_and_grad(job, tree, tokens, labels)
        assert abs(float(loss) - ref_loss) < 1e-5
        assert _gap(grads, job.program_tree(ref_grads)) < 1e-4
        # three steps through fit, as the benchmark's first steps
        batches = [tuple(np.asarray(a) for a in _tokens(SMALL, seed))
                   for seed in (1, 2, 3)]
        job.make_state(REF.init, key)
        seen = []
        feed = traffic.Feed(batches, dt_data.DataBatch)
        for _ in batches:
            job.fit(feed.arm(1), [lambda p: seen.append(
                dict(p.eval_metric.get_name_value())["cross-entropy"])])
        ref = REF.train(key, [(d[None], lb[None]) for d, lb in batches],
                        SMALL, 3)
    np.testing.assert_allclose(seen, ref["losses"], rtol=2e-5)
    assert job.mod.metric_flushes == {"device": 1, "host": 0}
    change = jax.tree_util.tree_map(jnp.subtract, job.mod.state.params,
                                    job.program_tree(ref_params))
    assert _gap(change, job.program_tree(ref["param_change"])) < 2e-3
    assert _gap(grads, job.program_tree(ref["first_gradient"])) < 1e-4


@pytest.mark.parametrize("broken", [
    {}, {"embedding_multiplier": 1.0}, {"residual_multiplier": 1.0},
    {"logits_scaling": 1.0}, {"attention_multiplier": 1.0},
    {"tie_word_embeddings": False}], ids=lambda b: "-".join(b) or "published")
def test_each_multiplier_and_the_tied_head_matter(seeded, broken):
    """The model as published passes the comparison with the reference; with
    one of the four scalars set to 1, or with a head of its own, it fails."""
    ref_params, tokens, labels, ref_loss, ref_grads = seeded
    job = _job({**SMALL, **broken})
    tree = job.program_tree(ref_params)
    if broken.get("tie_word_embeddings") is False:   # a head drawn apart
        tree["lm_head"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(6), ref_params["embed"].shape)
    loss, grads = _program_loss_and_grad(job, tree, tokens, labels)
    grads.pop("lm_head", None)
    loss_gap = abs(float(loss) - ref_loss) / ref_loss
    grad_gap = _gap(grads, job.program_tree(ref_grads))
    if broken:
        assert loss_gap > 1e-4 or grad_gap > 1e-2, (loss_gap, grad_gap)
    else:
        assert loss_gap < 1e-5 and grad_gap < 1e-4, (loss_gap, grad_gap)


def test_reference_gradient_a_sequence_at_a_time_is_the_whole_batchs(seeded):
    params, tokens, labels, loss, grads = seeded
    whole_loss, whole = jax.jit(jax.value_and_grad(
        lambda p: REF.loss_fn(p, tokens, labels, SMALL)))(params)
    assert abs(loss - float(whole_loss)) < 1e-6
    assert _gap(grads, whole) < 1e-5


def test_grouped_heads_reach_the_kernels_unspread():
    """Eight query heads over two key-value heads under ``attention="flash"``
    (PR 44): both kernels' key and value operands are ``(B * KV, S, D)``,
    nothing computed from the key and value projections outside a kernel is
    as large as a ``(B, S, H, D)`` array, and the layer's output and
    gradients are the plain path's, which spreads the heads for the
    oracle."""
    b, s, h, kv, d = 2, 128, 8, 2, 8      # h * d over the layer's width
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, 32))
    layer = lambda kind: hybrid_lm.GroupedQueryAttention(  # noqa: E731
        num_heads=h, num_kv_heads=kv, head_dim=d, scale=d ** -0.5,
        attention=kind)
    variables = layer(None).init(jax.random.PRNGKey(1), x)
    objective = lambda kind: lambda v, x: jnp.sum(  # noqa: E731
        layer(kind).apply(v, x) ** 2)
    remat_held.assert_keys_reach_the_kernels_unspread(
        jax.grad(objective("flash"), argnums=(0, 1)), (variables, x), b, s, h, kv,
        d)
    got, want = (layer(kind).apply(variables, x) for kind in ("flash", None))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    grads = [jax.grad(objective(kind), argnums=(0, 1))(variables, x)
             for kind in ("flash", None)]
    for a, c in zip(*map(jax.tree_util.tree_leaves, grads)):
        np.testing.assert_allclose(a, c, rtol=5e-4, atol=5e-5)


def test_rematerialised_blocks_change_no_number_and_no_name():
    plain, remat = (models.create(
        "hybrid_lm", vocab_size=40, embed_dim=32, intermediate=48,
        layer_types=("mamba", "attention"), num_heads=4, num_kv_heads=2,
        ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_chunk=4,
        attention=None, remat=flag) for flag in (False, True))
    tokens, labels = _tokens(SMALL)
    params = jax.jit(plain.init)(jax.random.PRNGKey(0), tokens)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(jax.eval_shape(
            remat.init, jax.random.PRNGKey(0), tokens))
    from dt_tpu.ops import losses
    grads = [jax.jit(jax.grad(lambda p, m=m: losses.softmax_cross_entropy(
        m.apply(p, tokens), labels)))(params) for m in (plain, remat)]
    assert _gap(*grads) < 1e-5



# -- what a rematerialised block keeps ----------------------------------------

#: a block of each kind as ``HybridLM`` wraps it: width 32, feed-forward 48;
#: four state-space heads of 16 over a state of 8 (in_proj 2 x 64 + 2 x 8 + 4
#: wide); four query heads of 8 over two
MIXERS = {"mamba": dict(n_heads=4, d_head=16, d_state=8, n_groups=1,
                        d_conv=4, chunk=4, conv_bias=True),
          "attention": dict(num_heads=4, num_kv_heads=2, head_dim=8,
                            attention="flash", scale=8 ** -0.5)}


def _block(kind, policy_names, length):
    import flax.linen as linen
    make = lambda cls: cls(kind, 48, 0.22,  # noqa: E731
                           tuple(sorted(MIXERS[kind].items())), 1e-5,
                           jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (BATCH, length, 32))
    variables = jax.jit(make(hybrid_lm.HybridBlock).init)(
        jax.random.PRNGKey(1), x)
    return make(linen.remat(
        hybrid_lm.HybridBlock,
        policy=jax.checkpoint_policies.save_only_these_names(
            *policy_names))), variables, x


def _remat_changes_no_number():
    cfg = {**SMALL, "attention": "flash"}
    tokens, labels = _tokens(cfg)
    tree = None
    got = []
    for remat in (False, True):
        job = _job({**cfg, "remat_blocks": remat})
        tree = tree or job.program_tree(REF.init(jax.random.PRNGKey(3), cfg))
        got.append(_program_loss_and_grad(job, tree, tokens, labels))
    (loss, grads), (loss_r, grads_r) = got
    np.testing.assert_allclose(loss_r, loss, rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(grads_r)):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-8,
                                   err_msg=jax.tree_util.keystr(path))


def _a_block_keeps_its_list(kind):
    """Beyond its input and its parameters a block keeps the values on
    ``SAVED``, at the sizes the list's comment gives: the state-space one
    its in_proj, both kinds the mixer's last product and the feed-forward's
    two."""
    length, d, inter, f32 = 16, 32, 48, "float32"
    wide = 2 * 64 + 2 * 8 + 4
    # each name's width and how many of it a block of this kind makes
    by_name = {"mixer_out": (d, 1), "mlp_gate": (inter, 1),
               "mlp_up": (inter, 1),
               "ssm_in_proj": (wide, int(kind == "mamba"))}
    assert set(hybrid_lm.SAVED) <= set(by_name)
    for names in (hybrid_lm.SAVED, tuple(by_name)):   # the list; every name
        blk, variables, x = _block(kind, names, length)
        kept, args = remat_held.held(blk, variables, x)
        assert sum(args.values()) == 1 + len(
            jax.tree_util.tree_leaves(variables))
        want = Counter()
        for name in names:
            width, count = by_name[name]
            want[((BATCH, length, width), f32)] += count
        assert kept == +want
        # the formula in the list's comment at c = 4 bytes
        assert remat_held.held_bytes(kept) == BATCH * length * 4 * sum(
            by_name[n][0] * by_name[n][1] for n in names)
    blk, variables, x = _block(kind, (), length)
    assert not remat_held.held(blk, variables, x)[0]


def _the_gradient_calls_the_forward_kernel_twice():
    """``flash_out`` is not on this model's list (the benchmark counts the
    forward kernel's calls from a file): the backward pass runs the forward
    kernel again.  With it on the list, once."""
    for names, forward in ((hybrid_lm.SAVED, 2),
                           (hybrid_lm.SAVED + ("flash_out", "flash_lse"), 1)):
        blk, variables, x = _block("attention", names, 128)
        calls, total = remat_held.kernel_calls(blk, variables, x)
        # the causal forward has no name of its own
        assert calls == {"flash_bwd": 1} and total == forward + 1, names


def _the_gauge_counts_the_list(remat):
    from dt_tpu.obs import metrics as obs_metrics
    from dt_tpu.training import metrics as metrics_lib
    obs_metrics.set_enabled(True)
    try:
        obs_metrics.registry().clear()
        job = _job({**SMALL, "remat_blocks": remat})
        job.mod._metric_stats = metrics_lib.device_form(
            metrics_lib.create("ce"))
        job.mod._build_steps()
        gauges = {name: value for name, _, value in
                  obs_metrics.registry().gauges_export()}
    finally:
        obs_metrics.set_enabled(None)
    assert gauges["model.remat_blocks"] == int(remat)
    assert gauges["model.remat_saved_names"] == (
        len(hybrid_lm.SAVED) if remat else 0)


@pytest.mark.parametrize("check", [
    _remat_changes_no_number, lambda: _a_block_keeps_its_list("mamba"),
    lambda: _a_block_keeps_its_list("attention"),
    _the_gradient_calls_the_forward_kernel_twice,
    lambda: _the_gauge_counts_the_list(True),
    lambda: _the_gauge_counts_the_list(False)],
    ids=["numbers", "held-mamba", "held-attention", "kernels", "gauge-on",
         "gauge-off"])
def test_rematerialised_blocks_keep_the_named_values(check):
    check()


def test_module_sets_the_models_gauges_when_it_builds_its_steps():
    from dt_tpu.obs import metrics as obs_metrics
    from dt_tpu.training import metrics as metrics_lib
    obs_metrics.set_enabled(True)
    try:
        job = _job({**SMALL, "remat_blocks": True})
        job.mod._metric_stats = metrics_lib.device_form(
            metrics_lib.create("ce"))
        job.mod._build_steps()
        gauges = {name: value for name, _, value in
                  obs_metrics.registry().gauges_export()}
    finally:
        obs_metrics.set_enabled(None)
    assert gauges["model.layers_ssm"] == 1
    assert gauges["model.layers_attention"] == 1
    assert gauges["model.ssm_chunk"] == 4
    assert gauges["model.remat_blocks"] == 1
