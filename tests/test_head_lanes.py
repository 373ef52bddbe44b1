"""Heads of whole lane tiles (head size 128): the attention layer's norm,
rotary turn and gate on the by-head view of the projections' ``(B, S, H *
D)`` arrays (``attention.by_head``), where the flash kernels read and write
(PERF.md section 6, PR 45).  Everything against the layer's plain path, which
holds ``(B, S, H, D)`` arrays and a dense softmax; the interpreter, small
sizes."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from flash_edge_cases import equations

from dt_tpu.models import routed_lm
from dt_tpu.ops.pallas import attention as attn

B, S, WIDTH, D = 2, 256, 64, 128
YARN = (("attention_factor", 1.4158883083359672), ("beta_fast", 64),
        ("beta_slow", 1), ("factor", 64),
        ("original_max_position_embeddings", 4096))
# the three cells with heads of 128, at toy head counts: laguna-xs2's
# sliding and full layers (a gate a head, no norm, the whole head or half of
# it turned, YaRN), sdar30b's (a norm a head, the block rule) and keye30b's
# rotary rule (three rows of positions)
LAYERS = {
    "laguna.window": dict(num_heads=4, num_kv_heads=2, window=100, gate=True,
                          qk_norm=False, kind="window", rope_theta=1e4),
    "laguna.full": dict(num_heads=6, num_kv_heads=2, gate=True, qk_norm=False,
                        kind="full", rope_theta=5e5, rotary_dim=64,
                        yarn=YARN),
    "sdar30b": dict(num_heads=4, num_kv_heads=1,
                    mask=attn.BlockDiffusionMask(S // 2, 4)),
    "keye30b.mrope": dict(num_heads=4, num_kv_heads=2,
                          mrope_section=(16, 24, 24)),
}


def _positions(name):
    if "mrope" not in name:
        return None
    pos = np.arange(S)
    return jnp.asarray(np.stack([pos, pos // 2, pos // 3]))


def _layer_and_inputs(name, attention, dtype=jnp.float32):
    layer = routed_lm.RotaryAttention(head_dim=D, attention=attention,
                                      dtype=dtype, **LAYERS[name])
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, WIDTH)).astype(dtype)
    pos = _positions(name)
    made = layer.init(jax.random.PRNGKey(1), x, pos)["params"]
    # norm scales off one, so that their gradient is not a sum of zeros
    made = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(a.size),
                                              a.shape), made)
    return layer, {"params": made}, x, pos


def _weighed_sum_and_grads(name, attention, dtype=jnp.float32):
    """The layer's output weighed and summed in float32, and that sum's
    gradient in the variables and the input, as one jitted program."""
    layer, variables, x, pos = _layer_and_inputs(name, attention, dtype)
    w = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    run = lambda v, x: jnp.sum(  # noqa: E731
        layer.apply(v, x, pos, mutable=["counters"])[0].astype(jnp.float32)
        * w)
    return jax.jit(jax.value_and_grad(run, argnums=(0, 1)))(variables, x)


@functools.lru_cache(maxsize=None)
def _by_head(name, dtype):
    """The layer on the projections' arrays: each test below holds it to
    another path, in float32 both to the same run."""
    return _weighed_sum_and_grads(name, "flash", dtype)


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_the_by_head_path_is_the_plain_paths_layer(name):
    """Output and every gradient of the layer under ``attention="flash"``
    at head size 128 against the plain path in float32."""
    (a, da), (c, dc) = (_by_head(name, jnp.float32),
                        _weighed_sum_and_grads(name, None))
    np.testing.assert_allclose(a, c, rtol=2e-5)
    for got, want in zip(jax.tree_util.tree_leaves(da),
                         jax.tree_util.tree_leaves(dc)):
        np.testing.assert_allclose(got, want, atol=5e-5 * max(
            1.0, float(jnp.max(jnp.abs(want)))))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(LAYERS))
def test_the_by_head_path_is_the_four_axis_path_in_the_cells_precision(
        name, dtype, monkeypatch):
    """The same layer under ``attention="flash"`` twice, on the projections'
    arrays and (with ``lane_tiled`` answering no) on ``(B, S, H, D)`` arrays
    as at any other head size: the same kernels and the same float32
    arithmetic between the same roundings, so output and gradients agree to
    float32's noise in float32 and to a bfloat16 step of the largest value in
    bfloat16, the cells' precision."""
    ours = _by_head(name, dtype)
    monkeypatch.setattr(routed_lm, "lane_tiled", lambda *a: False)
    theirs = _weighed_sum_and_grads(name, "flash", dtype)
    step = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-5
    for got, want in zip(jax.tree_util.tree_leaves(ours),
                         jax.tree_util.tree_leaves(theirs)):
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want, rtol=0,
            atol=2 * step * max(1.0, float(np.abs(want).max())))


@pytest.mark.parametrize("name", ["laguna.window", "sdar30b"])
def test_no_array_of_a_heads_size_is_turned_or_cut_along_the_lanes(name):
    """The gradient's jaxpr at head size 128: both kernels take ``(B, S, H
    * D)`` operands; the only transposes of arrays with a head's ``D`` in
    them are the by-head view's (which moves no byte under the (8, 128)
    tiling); and no ``slice`` or ``concatenate`` cuts such an array along
    ``D`` (the turn's pairs come through a permutation product)."""
    layer, variables, x, pos = _layer_and_inputs(name, "flash")
    h, kv = layer.num_heads, layer.num_kv_heads
    eqns = list(equations(jax.make_jaxpr(jax.grad(
        lambda v, x: jnp.sum(layer.apply(v, x, pos, mutable=["counters"])[0]),
        argnums=(0, 1)))(variables, x).jaxpr))
    wide, narrow, row = (B, S, h * D), (B, S, kv * D), (B * h, 1, S)
    calls = [[v.aval.shape for v in e.invars] for e in eqns
             if e.primitive.name == "pallas_call"]
    assert calls == [[wide, narrow, narrow],
                     [wide, wide, row, row, narrow, narrow]]
    big = lambda e: e.invars[0].aval.size >= B * S * kv * D  # noqa: E731
    for e in eqns:
        if e.primitive.name == "transpose" and big(e):
            assert e.params["permutation"] == (0, 1, 3, 2, 4), e
        if e.primitive.name in ("slice", "concatenate") and big(e):
            assert e.invars[0].aval.shape[-1] != D or all(
                v.aval.shape[-1] == D for v in e.outvars), e


@pytest.mark.parametrize("part,scale", [(128, 1.0), (64, 1.4)])
def test_turn_heads_is_rope_part_and_its_own_transpose(part, scale):
    """``turn_heads`` on ``(B, S, H * D)`` against ``rope_part`` on ``(B, S,
    H, D)``, the whole head and half of it turned; its written backward
    pass against the transpose jax derives for ``rope_part``."""
    h = 3
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, h, D))
    w = jax.random.normal(jax.random.PRNGKey(1), (B, S, h * D))
    freq = 1e4 ** (-np.arange(part // 2, dtype=np.float32) / (part // 2))
    pos = jnp.arange(S)
    angle = pos.astype(jnp.float32)[:, None] * freq[None, :]
    tables = routed_lm._lane_tables(angle, scale, D)
    ours = lambda x: routed_lm.turn_heads(  # noqa: E731
        x.reshape(B, S, h * D), h, *tables, part // 2)
    theirs = lambda x: routed_lm.rope_part(  # noqa: E731
        x, pos, freq, scale).reshape(B, S, h * D)
    np.testing.assert_array_equal(ours(x), theirs(x))
    np.testing.assert_allclose(
        jax.grad(lambda x: jnp.sum(ours(x) * w))(x),
        jax.grad(lambda x: jnp.sum(theirs(x) * w))(x), atol=1e-6)


def test_the_view_is_the_arrays_rows_of_eight_by_head():
    """``by_head`` puts element ``[b, s, h * D + d]`` at ``[b, s // 8, h, s %
    8, d]`` and ``from_heads`` undoes it."""
    h = 3
    x = jnp.arange(B * 16 * h * D).reshape(B, 16, h * D)
    view = attn.by_head(x, h)
    assert view.shape == (B, 2, h, 8, D)
    assert int(view[1, 1, 2, 5, 7]) == int(x[1, 13, 2 * D + 7])
    np.testing.assert_array_equal(attn.from_heads(view), x)


def test_heads_of_64_and_the_plain_path_take_no_view():
    """At head size 64, and without the kernels, the layer holds ``(B, S,
    H, D)`` arrays as it did: the view's transpose is nowhere in the
    jaxpr."""
    for d, attention in ((64, "flash"), (128, None)):
        layer = routed_lm.RotaryAttention(num_heads=2, num_kv_heads=1,
                                          head_dim=d, attention=attention,
                                          gate=True)
        x = jnp.zeros((1, 128, 32))
        variables = layer.init(jax.random.PRNGKey(0), x)
        eqns = equations(jax.make_jaxpr(
            lambda v, x: layer.apply(v, x))(variables, x).jaxpr)
        assert not any(e.primitive.name == "transpose"
                       and e.params["permutation"] == (0, 1, 3, 2, 4)
                       for e in eqns)
