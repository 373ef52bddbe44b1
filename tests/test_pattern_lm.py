"""A decoder of single-part blocks whose parts hold a share of their heads or
experts (PR 47): the gated norm by groups, the routed layer's squared-ReLU
and latent forms against a dense loop over experts, the share tests (the
parts all head shares and all expert shares give add up to the uncut
reference's whole block), and three steps through ``Module.fit`` against the
plain reference with its biases, at widths in the tens."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dt_tpu import data as dt_data, models
from dt_tpu.models import hybrid_lm, pattern_lm
from dt_tpu.ops import ssm
from dt_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)
import nemotron_drivers  # noqa: E402
import traffic as traffic_lib  # noqa: E402

CONFIG = "nemotron-3-super-120b-a12b"


def _load_reference():
    path = os.path.join(BENCH, "configs", CONFIG + "_reference.py")
    spec = importlib.util.spec_from_file_location("nemotron_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()
with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
    PUBLISHED = json.load(f)
#: the whole toy model: 8 state-space heads of 8 in 2 groups, 4 query heads
#: of 8 over 2, 16 experts of 24 in a latent of 16, 3 a token, a shared
#: expert of 40; ranges at which every part of a block shows in its result
WHOLE = {**{k: v for k, v in PUBLISHED.items() if k != "published"},
         "hidden_size": 32, "mamba_head_dim": 8, "ssm_state_size": 8,
         "chunk_size": 16, "head_dim": 8, "moe_intermediate_size": 24,
         "moe_latent_size": 16, "moe_shared_expert_intermediate_size": 40,
         "num_experts_per_tok": 3, "mamba_num_heads": 8, "n_groups": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "n_routed_experts": 16, "vocab_size": 64,
         "hybrid_override_pattern": "ME*E", "num_hidden_layers": 4,
         "held_experts_first": 0, "buffer_rows": 256, "attention": None,
         "dtype": "float32", "expert_bias_initial_std": 0.1,
         "expert_bias_update_speed": 0.02, "initializer_range": 0.25,
         "residual_out_initializer_range": 0.25}
#: what the second chip of two (heads) and of four (experts) holds of it
SHARE = {**WHOLE, "mamba_num_heads": 4, "n_groups": 1,
         "num_attention_heads": 2, "num_key_value_heads": 1,
         "n_routed_experts": 4, "held_experts_first": 4,
         "held_mamba_heads_first": 4, "held_attention_heads_first": 2,
         "published": {**PUBLISHED["published"], "mamba_num_heads": 8,
                       "n_groups": 2, "num_attention_heads": 4,
                       "num_key_value_heads": 2, "n_routed_experts": 16}}
TRAFFIC = {"generator": "traffic:uniform_tokens", "batch": 2, "seq_len": 64,
           "distinct_batches": 3, "steps_per_reading": 1, "warm_steps": 0}
F32 = jnp.float32
IDENTITY = lambda a: a  # noqa: E731


def _gap(got, want):
    """The norm of the difference over the whole tree against the
    reference's norm."""
    leaves = jax.tree_util.tree_leaves
    diff = sum(float(jnp.sum(jnp.square(a - b)))
               for a, b in zip(leaves(got), leaves(want)))
    return (diff / sum(float(jnp.sum(jnp.square(b)))
                       for b in leaves(want))) ** 0.5


# -- the gated norm by groups --------------------------------------------------

def test_gated_norm_with_one_group_is_todays_to_the_bit():
    rng = np.random.default_rng(0)
    y, z = (jnp.asarray(rng.normal(size=(2, 16, 64)), jnp.bfloat16)
            for _ in range(2))
    scale = jnp.asarray(rng.normal(size=(64,)), F32)
    v = y.astype(F32) * jax.nn.silu(z.astype(F32))
    v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True)
                          + 1e-5)
    today = (v * scale.astype(F32)).astype(y.dtype)
    for got in (ssm.gated_rms_norm(y, z, scale, 1e-5),
                ssm.gated_rms_norm(y, z, scale, 1e-5, groups=1)):
        assert got.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(today, np.float32))


@pytest.mark.parametrize("groups", [2, 4, 8])
def test_gated_norm_by_groups_is_a_plain_norm_of_each_group(groups):
    rng = np.random.default_rng(groups)
    y, z = (rng.normal(size=(2, 16, 64)).astype(np.float32)
            for _ in range(2))
    scale = rng.normal(size=(64,)).astype(np.float32)
    got = ssm.gated_rms_norm(jnp.asarray(y), jnp.asarray(z),
                             jnp.asarray(scale), 1e-5, groups=groups)
    u = y * (z / (1 + np.exp(-z)))
    want = np.concatenate([
        part / np.sqrt(np.mean(part ** 2, axis=-1, keepdims=True) + 1e-5)
        for part in np.split(u, groups, axis=-1)], axis=-1) * scale
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # and it is not the norm over all the channels at once
    whole = ssm.gated_rms_norm(jnp.asarray(y), jnp.asarray(z),
                               jnp.asarray(scale), 1e-5)
    assert np.abs(np.asarray(whole) - want).max() > 1e-2


# -- the routed layer's forms against a dense loop over experts --------------

def _dense_loop(params, x, layer, bias=None):
    """``RoutedExperts``' result by a loop over the experts held, each over
    every token with its weight (zero where it was not chosen), nothing
    sorted, nothing dropped."""
    b, s, d = x.shape
    rows = x.reshape(b * s, d)
    first, count = layer.held or (0, layer.num_experts)
    scores = jax.nn.sigmoid(jnp.dot(rows, params["router"],
                                    precision=jax.lax.Precision.HIGHEST))
    pick = scores if bias is None else scores + bias
    _, chosen = jax.lax.top_k(pick, layer.top_k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    top = layer.routed_scale * top / (jnp.sum(top, -1, keepdims=True)
                                      + layer.norm_eps)
    work = rows @ params["latent_in"]["kernel"] if layer.latent else rows
    y = jnp.zeros_like(work)
    for e in range(count):
        w = jnp.sum(jnp.where(chosen == first + e, top, 0.0), axis=-1)
        up = work @ params["up"][e]
        hidden = jax.nn.silu(work @ params["gate"][e]) * up \
            if layer.expert_form == "gated_silu" \
            else jnp.square(jax.nn.relu(up))
        y = y + w[:, None] * (hidden @ params["down"][e])
    if layer.latent:
        y = y @ params["latent_out"]["kernel"]
    if layer.shared_intermediate:
        up = rows @ params["shared_up"]["kernel"]
        hidden = jax.nn.silu(rows @ params["shared_gate"]["kernel"]) * up \
            if layer.expert_form == "gated_silu" \
            else jnp.square(jax.nn.relu(up))
        y = y + hidden @ params["shared_down"]["kernel"]
    return y.reshape(b, s, d), chosen


@pytest.mark.parametrize("form", ["relu2", "gated_silu"])
@pytest.mark.parametrize("latent", [16, None])
def test_routed_forms_match_a_dense_loop_at_top_22_of_64(form, latent):
    layer = moe.RoutedExperts(
        num_experts=64, top_k=22, intermediate=24, held=(8, 16),
        scoring="sigmoid", routed_scale=5.0, norm_eps=1e-20,
        shared_intermediate=40, expert_form=form, latent=latent)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32), F32)
    made = layer.init(jax.random.PRNGKey(0), x)
    params = made["params"]
    assert ("gate" in params) == ("shared_gate" in params) \
        == (form == "gated_silu")
    assert ("latent_in" in params) == ("latent_out" in params) \
        == bool(latent)
    assert params["up"].shape == (16, latent or 32, 24)
    assert params["down"].shape == (16, 24, latent or 32)
    assert params["router"].shape == (32, 64)
    with jax.default_matmul_precision("highest"):
        got, mutated = layer.apply({"params": params}, x,
                                   mutable=["counters"])
        want, chosen = _dense_loop(params, x, layer)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(want).max()) > 0.05
    # the counters: every assignment to a held expert placed, none dropped
    counted = np.asarray(mutated["counters"]["moe"][0]).sum(axis=0)
    here = np.asarray((chosen >= 8) & (chosen < 24)).sum()
    assert list(counted[-3:]) == [here, 0, 2 * 32 * 22]
    # and the gradients: through the latent and both forms
    def objective(fn):
        return jax.grad(lambda p: jnp.sum(jnp.square(fn(p))))(params)
    with jax.default_matmul_precision("highest"):
        g_got = objective(lambda p: layer.apply(
            {"params": p}, x, mutable=["counters"])[0])
        g_want = objective(lambda p: _dense_loop(p, x, layer)[0])
    assert _gap(g_got, g_want) < 2e-4


def test_an_assignment_that_finds_no_room_is_dropped_and_counted():
    """A buffer smaller than the held load: the layer counts what it drops,
    and its result is the dense loop's less exactly the dropped ones' (the
    buffer fills in expert order, so the last held experts' lose them)."""
    kw = dict(num_experts=64, top_k=22, intermediate=24, held=(8, 16),
              scoring="sigmoid", routed_scale=5.0, norm_eps=1e-20,
              expert_form="relu2", latent=16)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32), F32)
    roomy, tight = (moe.RoutedExperts(buffer_rows=rows, **kw)
                    for rows in (None, 256))
    params = roomy.init(jax.random.PRNGKey(0), x)["params"]
    outs = []
    for layer in (roomy, tight):
        out, mutated = layer.apply({"params": params}, x,
                                   mutable=["counters"])
        outs.append((out, np.asarray(mutated["counters"]["moe"][0]).sum(0)))
    held = outs[0][1][-3]
    assert held > 256 and outs[0][1][-2] == 0
    assert outs[1][1][-3] == held and outs[1][1][-2] == held - 256
    # the experts whose rows all found room give the same part either way
    assert float(jnp.abs(outs[0][0] - outs[1][0]).max()) > 1e-3


def test_the_defaults_build_the_layer_the_accepted_configurations_have():
    """No ``latent``, the gated form: the parameters the four routed
    configurations' references draw by path, and nothing new."""
    layer = moe.RoutedExperts(num_experts=8, top_k=2, intermediate=24,
                              held=(2, 4), shared_intermediate=16)
    assert (layer.expert_form, layer.latent) == ("gated_silu", None)
    x = jnp.zeros((1, 16, 32), F32)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0),
                                               x))["params"]
    assert sorted(params) == ["down", "gate", "router", "shared_down",
                              "shared_gate", "shared_up", "up"]
    assert params["gate"].shape == params["up"].shape == (4, 32, 24)
    with pytest.raises(ValueError, match="no expert form"):
        moe.RoutedExperts(num_experts=8, top_k=2, intermediate=24,
                          expert_form="gelu").init(jax.random.PRNGKey(0), x)


# -- the share tests -----------------------------------------------------------

def _mamba_share(blk, first, count, cfg):
    """The columns and rows of the whole mixer's weights that heads ``first
    .. first + count`` and their groups hold."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    per = h // g
    groups = np.arange(first // per, (first + count) // per)
    x_cols = np.arange(first * p, (first + count) * p)
    b_cols = h * p + np.concatenate([np.arange(j * n, (j + 1) * n)
                                     for j in groups])
    c_cols = b_cols + g * n
    conv = np.concatenate([x_cols, b_cols, c_cols])
    heads = np.arange(first, first + count)
    cols = np.concatenate([x_cols, h * p + conv,
                           2 * h * p + 2 * g * n + heads])
    return {"in_proj": {"kernel": blk["in_proj"][:, cols]},
            "conv_kernel": blk["conv_w"][:, conv],
            "conv_bias": blk["conv_b"][conv], "dt_bias": blk["dt_bias"][heads],
            "A_log": blk["A_log"][heads], "D": blk["D"][heads],
            "norm_scale": blk["norm_g"][x_cols],
            "out_proj": {"kernel": blk["out_proj"][x_cols]}}


def _randomised(blk, key):
    """The block's ones (norm scales, ``D``) drawn instead, so that a share
    that took the wrong channels' shows."""
    out = dict(blk)
    for i, name in enumerate(("norm_g", "D")):
        out[name] = 1.0 + 0.5 * jax.random.normal(
            jax.random.fold_in(key, i), blk[name].shape, F32)
    return out


@pytest.mark.parametrize("shares", [2, 1])
def test_the_mamba_shares_parts_add_up_to_the_whole_block(shares):
    cfg = WHOLE
    blk = _randomised(REF.init(jax.random.PRNGKey(2), cfg)["blocks"][0],
                      jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 48, 32), F32)
    with jax.default_matmul_precision("highest"):
        whole = jnp.stack([REF.mamba_mixer(t, blk, cfg, IDENTITY) for t in x])
        count = cfg["mamba_num_heads"] // shares
        parts = []
        for i in range(shares):
            mixer = hybrid_lm.Mamba2Mixer(
                n_heads=8, d_head=8, d_state=8, n_groups=2, chunk=16,
                held=None if shares == 1 else (i * count, count))
            parts.append(mixer.apply(
                {"params": _mamba_share(blk, i * count, count, cfg)}, x))
    assert float(jnp.abs(whole).max()) > 0.1
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-4, atol=2e-5)
    if shares > 1:      # and no share alone is the block
        assert float(jnp.abs(parts[0] - whole).max()) > 0.05
        # the reference given the share's configuration computes that part
        held = {**SHARE, "mamba_num_heads": count, "n_groups": 2 // shares}
        with jax.default_matmul_precision("highest"):
            part = jnp.stack([REF.mamba_mixer(
                t, _reference_names(_mamba_share(blk, count, count, cfg)),
                held, IDENTITY) for t in x])
        np.testing.assert_allclose(parts[1], part, rtol=2e-4, atol=2e-5)


def _reference_names(share):
    """``_mamba_share``'s tree under the reference's names."""
    return {"in_proj": share["in_proj"]["kernel"],
            "conv_w": share["conv_kernel"], "conv_b": share["conv_bias"],
            "dt_bias": share["dt_bias"], "A_log": share["A_log"],
            "D": share["D"], "norm_g": share["norm_scale"],
            "out_proj": share["out_proj"]["kernel"]}


def test_a_mamba_share_is_whole_groups_normed_alone():
    x = jnp.zeros((1, 16, 32), F32)
    kw = dict(n_heads=8, d_head=8, d_state=8, n_groups=2, chunk=16)
    for held in ((0, 2), (2, 4)):
        with pytest.raises(ValueError):
            hybrid_lm.Mamba2Mixer(held=held, **kw).init(
                jax.random.PRNGKey(0), x)
    made = hybrid_lm.Mamba2Mixer(**kw).init(jax.random.PRNGKey(0), x)
    assert made["params"]["in_proj"]["kernel"].shape == (32, 2 * 64 + 32 + 8)


@pytest.mark.parametrize("shares", [4, 2, 1])
def test_the_attention_shares_parts_add_up_to_the_whole_block(shares):
    """4 query heads over 2 key-value heads: two shares are whole key-value
    heads, four are one query head each with the key-value head it reads
    (which its neighbour holds too)."""
    cfg = WHOLE
    blk = REF.init(jax.random.PRNGKey(2), cfg)["blocks"][2]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 48, 32), F32)
    hd, heads, kv = 8, 4, 2
    with jax.default_matmul_precision("highest"):
        whole = jnp.stack([REF.attention(t, blk, cfg, IDENTITY) for t in x])
        count = heads // shares
        parts = []
        for i in range(shares):
            first = i * count
            q = np.arange(first * hd, (first + count) * hd)
            kv_first = first // (heads // kv)
            kv_count = max(count // (heads // kv), 1)
            k = np.arange(kv_first * hd, (kv_first + kv_count) * hd)
            layer = hybrid_lm.GroupedQueryAttention(
                num_heads=heads, num_kv_heads=kv, head_dim=hd,
                scale=hd ** -0.5, attention=None,
                held=None if shares == 1 else (first, count))
            parts.append(layer.apply({"params": {
                "q_proj": {"kernel": blk["wq"][:, q]},
                "k_proj": {"kernel": blk["wk"][:, k]},
                "v_proj": {"kernel": blk["wv"][:, k]},
                "o_proj": {"kernel": blk["wo"][q]}}}, x))
    assert float(jnp.abs(whole).max()) > 0.1
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-4, atol=2e-5)
    if shares > 1:
        assert float(jnp.abs(parts[0] - whole).max()) > 0.05
    with pytest.raises(ValueError, match="neither whole groups"):
        hybrid_lm.GroupedQueryAttention(
            num_heads=8, num_kv_heads=2, head_dim=hd, scale=1.0,
            attention=None, held=(1, 2)).init(jax.random.PRNGKey(0), x)


def test_the_expert_shares_parts_add_up_to_the_whole_block():
    """Four shares of 4 of the 16 experts, each through the same
    ``latent_out`` and each with the whole shared expert: their sum counts
    the shared expert four times, the block once."""
    cfg = WHOLE
    blk = REF.init(jax.random.PRNGKey(2), cfg)["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, 32), F32)
    rows = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        whole, moved, _ = REF.expert_block(rows, blk, blk["bias"], cfg,
                                           IDENTITY)
        shared = REF.shared_expert(rows, blk, IDENTITY)
        parts, biases = [], []
        for i in range(4):
            layer = moe.RoutedExperts(
                num_experts=16, top_k=3, intermediate=24, latent=16,
                expert_form="relu2", shared_intermediate=40,
                held=(4 * i, 4), scoring="sigmoid", routed_scale=5.0,
                norm_eps=1e-20, selection_bias=True,
                bias_update_speed=cfg["expert_bias_update_speed"])
            out, mutated = layer.apply(
                {"params": {
                    "router": blk["router"],
                    "up": blk["up"][4 * i:4 * i + 4],
                    "down": blk["down"][4 * i:4 * i + 4],
                    "latent_in": {"kernel": blk["lat_in"]},
                    "latent_out": {"kernel": blk["lat_out"]},
                    "shared_up": {"kernel": blk["shared_up"]},
                    "shared_down": {"kernel": blk["shared_down"]}},
                 "batch_stats": {"selection_bias": blk["bias"]}},
                x, mutable=["batch_stats", "counters"])
            parts.append(out.reshape(-1, 32))
            biases.append(mutated["batch_stats"]["selection_bias"])
    assert float(jnp.abs(whole - shared).max()) > 0.05   # the routed part
    np.testing.assert_allclose(sum(parts) - 3 * shared, whole, rtol=2e-4,
                               atol=2e-5)
    assert float(jnp.abs(parts[0] - whole).max()) > 0.05
    # every share moves the same bias alike: by the load over all 16
    for b in biases:
        np.testing.assert_allclose(b, moved, atol=1e-7)
    assert float(jnp.abs(moved - blk["bias"]).max()) > 0.01


# -- the model against the reference ------------------------------------------

def _biases(stats):
    return [np.asarray(stats[name]["moe"]["selection_bias"])
            for name in sorted(stats)]


def test_three_steps_through_fit_match_the_reference_and_its_biases():
    """The share's model through ``Module.fit``: losses, first gradient,
    the parameters' change and each E block's bias after every step are the
    reference's, which is given the same share."""
    cfg = dict(SHARE)
    job = nemotron_drivers.SinglePartHybridJob(cfg, TRAFFIC, 1, 5)
    batches = traffic_lib.generate(TRAFFIC, cfg, 5)
    feed = traffic_lib.Feed(batches, dt_data.DataBatch, cast=job.cast)
    key = jax.random.PRNGKey(5)
    job.make_state(REF.init, key)
    start = _biases(jax.device_get(job.mod.state.batch_stats))
    assert all(b.any() for b in start)                 # the seeded draw
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
    assert all(got["moved"])         # the bias moved the selection
    assert _gap(first, job.program_tree(want["first_gradient"])) < 2e-3
    assert _gap(change, job.program_tree(want["param_change"])) < 2e-2
    # the held experts saw a quarter of the assignments, none dropped
    for name, c in job.mod.step_counters.items():
        if name.endswith("/moe"):
            assert c["sum"][-2] == 0 and 0 < c["sum"][-3] < c["sum"][-1]


def test_the_model_reads_the_pattern_and_says_what_it_cannot_build():
    tokens = jnp.zeros((1, 16), jnp.int32)
    model = models.create("pattern_lm", vocab_size=64, embed_dim=32,
                          pattern="M*EE", attention=None)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               tokens))["params"]
    assert [sorted(set(params[f"block{i}"]) - {"norm"}) for i in range(4)] \
        == [["mamba"], ["attn"], ["moe"], ["moe"]]
    assert set(pattern_lm.PARTS) == set("M*E")
    with pytest.raises(ValueError, match="no part '-'"):
        models.PatternLM(pattern="M-E").init(jax.random.PRNGKey(0), tokens)
    # a rematerialised model keeps the two decoders' names in one list
    assert {"ssm_in_proj", "moe_route", "moe_up", "flash_out"} <= set(
        pattern_lm.SAVED) and "moe_gate" not in pattern_lm.SAVED
