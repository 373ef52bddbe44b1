"""MoE layer: routing oracle, no-drop equivalence, EP-sharded parity; the
routed layer's one count of its selection (``selection_load``) against the
scatter forms, and the scatters a training call of the layer still makes.

CPU 8-device mesh (conftest).  Reference has no MoE (beyond-reference
capability, SURVEY §2.3 parallelism inventory completion).
"""

import math
import re
from collections import Counter

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dt_tpu.parallel import moe
from dt_tpu.parallel.moe import MoEMLP, switch_route


def test_switch_route_respects_capacity_and_order():
    # 6 tokens, 2 experts, capacity 2: tokens route to argmax in arrival
    # order; overflow dropped
    logits = jnp.asarray([
        [2.0, 0.0],   # -> e0 slot0
        [2.0, 0.0],   # -> e0 slot1
        [2.0, 0.0],   # -> e0 OVERFLOW (dropped)
        [0.0, 2.0],   # -> e1 slot0
        [0.0, 2.0],   # -> e1 slot1
        [2.0, 0.0],   # -> e0 OVERFLOW (dropped)
    ])
    dispatch, combine, aux = switch_route(logits, capacity=2)
    d = np.asarray(dispatch)
    assert d[0, 0, 0] == 1 and d[1, 0, 1] == 1
    assert d[2].sum() == 0 and d[5].sum() == 0     # dropped
    assert d[3, 1, 0] == 1 and d[4, 1, 1] == 1
    # combine carries the softmax gate prob on the same support
    c = np.asarray(combine)
    g = float(jax.nn.softmax(logits[0])[0])
    np.testing.assert_allclose(c[0, 0, 0], g, rtol=1e-6)
    assert (np.asarray(combine)[d == 0] == 0).all()
    # balanced 50/50 routing -> aux near its minimum (E * sum f*p ~ 1)
    assert 0.9 < float(aux) < 1.3


def test_moe_no_drop_matches_dense_expert_oracle():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 8, 16).astype(np.float32))
    layer = MoEMLP(num_experts=4, hidden_ratio=2, capacity_factor=4.0)
    variables = layer.init(jax.random.PRNGKey(0), x)
    out, state = layer.apply(variables, x, mutable=["aux_loss"])
    assert out.shape == x.shape

    # oracle: route every token to its argmax expert (capacity ample ->
    # no drops), output = gate * expert_mlp(token)
    p = variables["params"]
    tokens = np.asarray(x).reshape(-1, 16)
    logits = tokens @ np.asarray(p["router"]["kernel"])
    probs = np.exp(logits - logits.max(1, keepdims=True))
    probs /= probs.sum(1, keepdims=True)
    want = np.zeros_like(tokens)
    for t in range(tokens.shape[0]):
        e = int(np.argmax(probs[t]))
        hmid = np.maximum(tokens[t] @ np.asarray(p["wi"])[e], 0)
        want[t] = probs[t, e] * (hmid @ np.asarray(p["wo"])[e])
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 16), want,
                               rtol=1e-4, atol=1e-5)
    aux = state["aux_loss"]["moe"][0]
    assert np.isfinite(float(aux))


def test_moe_expert_parallel_matches_unsharded():
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("model",))
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 16, 32).astype(np.float32))

    plain = MoEMLP(num_experts=4, hidden_ratio=2, capacity_factor=2.0)
    variables = plain.init(jax.random.PRNGKey(0), x)
    ref, _ = plain.apply(variables, x, mutable=["aux_loss"])

    ep = MoEMLP(num_experts=4, hidden_ratio=2, capacity_factor=2.0,
                mesh=mesh, axis="model")

    @jax.jit
    def run(v, x):
        out, _ = ep.apply(v, x, mutable=["aux_loss"])
        return out

    with mesh:
        got = run(variables, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_moe_aux_loss_flows_through_module_fit():
    """Module.fit must fold sown aux losses into the objective — flax
    silently drops sows when the collection isn't mutable, which would
    train MoE routers with zero balancing pressure."""
    from dt_tpu import models, data
    from dt_tpu.training import Module

    model = models.TransformerLM(vocab_size=16, embed_dim=16, num_layers=1,
                                 num_heads=2, max_len=8, moe_experts=2)
    rng = np.random.RandomState(3)
    toks = rng.randint(1, 16, (8, 8)).astype(np.int32)

    from dt_tpu.ops import losses as L

    def seq_ce(logits, labels):
        return L.softmax_cross_entropy(logits.reshape(-1, 16),
                                       labels.reshape(-1))

    mod = Module(model, loss_fn=seq_ce, optimizer="adam",
                 optimizer_params={"learning_rate": 1e-2}, seed=0)
    mod.init_params(jnp.asarray(toks))
    before = np.array(
        mod.state.params["block0"]["moe"]["router"]["kernel"])
    train = data.NDArrayIter(toks, toks, batch_size=8)
    mod.fit(train, num_epoch=1)
    after = np.asarray(
        mod.state.params["block0"]["moe"]["router"]["kernel"])
    assert not np.allclose(before, after), \
        "router got no gradient — aux collection dropped?"


def test_moe_trains_with_aux_loss():
    import optax
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(4, 8, 16).astype(np.float32))
    y = jnp.asarray(rng.randn(4, 8, 16).astype(np.float32))
    layer = MoEMLP(num_experts=4, hidden_ratio=2)
    variables = layer.init(jax.random.PRNGKey(0), x)
    params = variables["params"]
    tx = optax.adam(1e-2)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt):
        def loss_of(p):
            out, st = layer.apply({"params": p}, x, mutable=["aux_loss"])
            # sown value is pre-weighted (aux_weight)
            return ((out - y) ** 2).mean() + st["aux_loss"]["moe"][0]
        l, g = jax.value_and_grad(loss_of)(params)
        up, opt2 = tx.update(g, opt, params)
        return optax.apply_updates(params, up), opt2, l

    losses = []
    for _ in range(20):
        params, opt, l = step(params, opt)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.9, losses


# -- the routed layer counts its selection once -------------------------------

def _scatter_forms(experts, bias, probs, speed, first, count, rows):
    """The counts as the scatter-adds over the ``T x k`` picks that the
    layer made of them before PR 48 (the bias's and the term's the same
    float32 one): (order, sizes, held, the moved bias, the load-balancing
    term)."""
    e, flat = bias.shape[0], experts.reshape(-1)
    load = jnp.zeros((e,), jnp.float32).at[flat].add(1.0)
    moved = bias + speed * jnp.sign(jnp.mean(load) - load)
    term = e * jnp.sum(load / probs.shape[0] * jnp.mean(probs, axis=0))
    local = jnp.where((flat >= first) & (flat < first + count), flat - first,
                      count)
    order = jnp.argsort(local, stable=True)[:rows].astype(jnp.int32)
    held = jnp.zeros((count + 1,), jnp.int32).at[local].add(1)[:count]
    sizes = jnp.diff(jnp.minimum(jnp.cumsum(held), rows), prepend=0)
    return order, sizes, held, moved, term


def _picks(kind, t, k, e):
    """A selection (T, k) of ``e`` outputs: ``"top_k"`` the ``k`` largest of
    random scores (distinct a token, as a router's), ``"one"`` every pick
    on output 3, ``"few"`` picks that leave every output from ``e // 2`` on
    unchosen."""
    rng = np.random.RandomState(t + k + e)
    if kind == "one":
        return jnp.full((t, k), 3, jnp.int32)
    scores = rng.rand(t, e if kind == "top_k" else e // 2)
    return jnp.asarray(np.argsort(-scores, axis=1)[:, :k], jnp.int32)


#: (picks, T, k, E, first, count, buffer rows; None: T x k)
COUNTED = {
    "E-no-multiple-of-128": ("top_k", 96, 3, 200, 0, 200, None),
    "k-1": ("top_k", 50, 1, 16, 4, 4, 50),
    "every-pick-on-one-expert": ("one", 32, 1, 8, 0, 8, None),
    "experts-nobody-picks": ("few", 64, 2, 24, 8, 16, None),
    "held-in-the-middle": ("top_k", 64, 4, 32, 10, 6, 64),
    "buffer-under-the-load": ("top_k", 128, 2, 8, 2, 4, 70),
    "E-512-k-22": ("top_k", 64, 22, 512, 64, 8, 40),
}


@pytest.mark.parametrize("case", list(COUNTED))
def test_the_selection_counted_once_is_the_scatter_forms(case):
    kind, t, k, e, first, count, rows = COUNTED[case]
    rows = t * k if rows is None else rows
    experts = _picks(kind, t, k, e)
    rng = np.random.RandomState(7)
    bias = jnp.asarray(rng.randn(e) * 0.01, jnp.float32)
    probs = jax.nn.softmax(jnp.asarray(rng.randn(t, e), jnp.float32))
    load = jax.jit(moe.selection_load, static_argnums=1)(experts, e)
    want = np.bincount(np.asarray(experts).reshape(-1), minlength=e)
    assert load.dtype == jnp.int32 and load.shape == (e,)
    np.testing.assert_array_equal(load, want)
    if kind == "one":
        assert want[3] == t * k and want.sum() == want[3]
    if kind == "few":
        assert not want[e // 2:].any() and want[first:first + count].any()
    order_w, sizes_w, held_w, moved_w, term_w = _scatter_forms(
        experts, bias, probs, 0.25, first, count, rows)
    order, sizes, held = moe.sort_held(experts, load, first, count, rows)
    np.testing.assert_array_equal(order, order_w)
    np.testing.assert_array_equal(sizes, sizes_w)
    np.testing.assert_array_equal(held, held_w)
    np.testing.assert_array_equal(held, want[first:first + count])
    if case == "buffer-under-the-load":
        assert int(held.sum()) > rows == int(sizes.sum())
    np.testing.assert_array_equal(moe.moved_bias(bias, load, 0.25), moved_w)
    np.testing.assert_allclose(moe.load_balancing_term(load, probs), term_w,
                               rtol=1e-6)


def _scatter_adds(jaxpr, found):
    """Every ``scatter-add`` of ``jaxpr`` and the jaxprs inside it, counted
    by (operand shape, updates shape)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scatter-add":
            found[tuple(eqn.invars[0].aval.shape),
                  tuple(eqn.invars[2].aval.shape)] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scatter_adds(sub, found)
    return found


def _training_call(layer):
    """The layer's training call as a step makes it: the selection bias
    written, the load-balancing term in the objective."""
    def objective(params, stats, x):
        y, mutated = layer.apply(
            {"params": params, "batch_stats": stats}, x,
            mutable=["batch_stats", "aux_loss", "counters"])
        return jnp.sum(y ** 2) + sum(
            jax.tree_util.tree_leaves(mutated["aux_loss"])), mutated
    return objective


def test_a_training_call_scatters_rows_and_never_the_selection():
    b, s, d, e, k, count, rows = 2, 24, 16, 12, 3, 4, 40
    t = b * s
    layer = moe.RoutedExperts(
        num_experts=e, top_k=k, intermediate=8, held=(4, count),
        buffer_rows=rows, aux_weight=0.01, selection_bias=True,
        shared_intermediate=8)
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, d))
    variables = layer.init(jax.random.PRNGKey(1), x)
    objective = _training_call(layer)
    args = (variables["params"], variables["batch_stats"], x)
    forward = {((t, d), (rows, d)): 1,        # combine's rows
               ((b,), (rows,)): 1,            # _count: the rows placed
               ((count,), ()): 1}             # the padding's one group
    assert _scatter_adds(jax.make_jaxpr(objective)(*args).jaxpr,
                         Counter()) == forward
    # the gradient adds what its gathers transpose to: the buffer rows'
    # weights back to their (token, slot), and each pick's weight back to
    # its own token's row of the scores (take_along_axis: one element a
    # pick into (T, E), no count)
    backward = {((t * k,), (rows,)): 1, ((t, e), (t, k)): 1}
    found = _scatter_adds(jax.make_jaxpr(jax.grad(
        objective, has_aux=True))(*args).jaxpr, Counter())
    assert found == {**forward, **backward}
    # nothing scatters the T x k picks into bins, the router's or the buffer's
    assert not any(len(operand) == 1 and math.prod(updates) == t * k
                   for operand, updates in found)
    assert not any(operand in ((e,), (count + 1,)) for operand, _ in found)


def test_no_array_of_every_pick_by_every_expert_at_512_by_22():
    """E = 512, k = 22, T = 1,024: the compiled training call holds no value
    of ``T x k x E`` elements outside a fusion (the count is a product of
    two narrow one-hots: no form of the program has a one-hot over all
    outputs), and its temporaries are smaller than that many bytes."""
    t, k, e = 1024, 22, 512
    layer = moe.RoutedExperts(
        num_experts=e, top_k=k, intermediate=8, held=(64, 8), latent=8,
        buffer_rows=512, aux_weight=0.01, selection_bias=True,
        expert_form="relu2")
    x = jax.random.normal(jax.random.PRNGKey(0), (1, t, 16))
    variables = jax.jit(layer.init)(jax.random.PRNGKey(1), x)
    compiled = jax.jit(jax.grad(_training_call(layer),
                                has_aux=True)).lower(
        variables["params"], variables["batch_stats"], x).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < t * k * e
    outside, largest = True, 0
    for line in compiled.as_text().splitlines():
        if line and not line[0].isspace():     # a computation opens or ends
            outside = not line.startswith(("%fused", "fused"))
        for dims in re.findall(r"\b(?:pred|[su]\d+|bf16|f\d+)\[([\d,]+)\]",
                               line) if outside else ():
            largest = max(largest, math.prod(map(int, dims.split(","))))
    assert t * e <= largest < t * k * e, largest
