"""Optimizer + scheduler tests.

Modeled on reference ``tests/python/unittest/test_optimizer.py``: each
optimizer's compiled update is checked against a step-by-step numpy replay of
the reference update rule; schedulers against closed-form values.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dt_tpu import optim
from dt_tpu.ops.rnn import LSTMWeights


def _run_steps(tx, params, grads_list):
    state = tx.init(params)
    for g in grads_list:
        updates, state = tx.update(g, state, params)
        params = optax.apply_updates(params, updates)
    return params, state


def test_sgd_plain():
    tx = optim.sgd(0.1)
    p = {"w": jnp.array([1.0, 2.0])}
    g = {"w": jnp.array([0.5, 0.5])}
    p2, _ = _run_steps(tx, p, [g])
    np.testing.assert_allclose(np.array(p2["w"]), [0.95, 1.95], rtol=1e-6)


def test_sgd_momentum_and_wd_replay():
    lr, mom, wd = 0.1, 0.9, 0.01
    tx = optim.sgd(lr, momentum=mom, weight_decay=wd)
    w = np.array([1.0, -2.0], np.float32)
    p = {"w": jnp.array(w)}
    gs = [np.array([0.3, -0.1], np.float32), np.array([0.2, 0.4], np.float32)]
    p2, _ = _run_steps(tx, p, [{"w": jnp.array(g)} for g in gs])
    # numpy replay of reference sgd_mom_update
    m = np.zeros_like(w)
    for g in gs:
        g = g + wd * w
        m = mom * m - lr * g
        w = w + m
    np.testing.assert_allclose(np.array(p2["w"]), w, rtol=1e-5)


def test_nag_replay():
    lr, mom = 0.05, 0.9
    tx = optim.nag(lr, momentum=mom)
    w = np.array([0.5], np.float32)
    p = {"w": jnp.array(w)}
    gs = [np.array([0.2], np.float32), np.array([-0.1], np.float32)]
    p2, _ = _run_steps(tx, p, [{"w": jnp.array(g)} for g in gs])
    m = np.zeros_like(w)
    for g in gs:
        m = mom * m + g
        w = w - lr * (g + mom * m)
    np.testing.assert_allclose(np.array(p2["w"]), w, rtol=1e-5)


def test_adam_replay():
    lr, b1, b2, eps = 0.001, 0.9, 0.999, 1e-8
    tx = optim.adam(lr)
    w = np.array([1.0, 2.0], np.float32)
    p = {"w": jnp.array(w)}
    gs = [np.array([0.1, -0.2], np.float32)] * 3
    p2, _ = _run_steps(tx, p, [{"w": jnp.array(g)} for g in gs])
    m = np.zeros_like(w)
    v = np.zeros_like(w)
    for t, g in enumerate(gs, 1):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        lr_t = lr * np.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        w = w - lr_t * m / (np.sqrt(v) + eps)
    np.testing.assert_allclose(np.array(p2["w"]), w, rtol=1e-5)


def test_adagrad_replay():
    """Reference AdaGrad: hist += g² (no wd in the accumulated grad);
    w -= lr * (g / sqrt(hist + eps) + wd * w)."""
    lr, wd = 0.1, 0.01
    tx = optim.adagrad(lr, weight_decay=wd)
    w = np.array([1.0], np.float32)
    p = {"w": jnp.array(w)}
    gs = [np.array([0.5], np.float32), np.array([-0.25], np.float32)]
    p2, _ = _run_steps(tx, p, [{"w": jnp.array(g)} for g in gs])
    h = np.zeros_like(w)
    for g in gs:
        h += g * g
        w = w - lr * (g / np.sqrt(h + 1e-7) + wd * w)
    np.testing.assert_allclose(np.array(p2["w"]), w, rtol=1e-5)


@pytest.mark.parametrize("name,kwargs", [
    ("rmsprop", {}),
    ("rmsprop", {"centered": True, "momentum": 0.9}),
    ("adadelta", {}),
    ("ftrl", {}),
    ("adamax", {}),
    ("nadam", {}),
    ("signum", {}),
    ("signsgd", {}),
    ("ftml", {}),
    ("sgld", {}),
    ("dcasgd", {}),
    ("lbsgd", {}),
    ("lamb", {}),
])
def test_all_optimizers_descend_quadratic(name, kwargs):
    """Every optimizer must reduce f(w)=||w||² from w=ones within 50 steps."""
    if name == "adadelta":
        tx = optim.create(name, **kwargs)
    else:
        tx = optim.create(name, learning_rate=0.05, **kwargs)
    p = {"w": jnp.ones(4)}
    state = tx.init(p)

    @jax.jit
    def step(p, state):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(p)
        u, state = tx.update(g, state, p)
        return optax.apply_updates(p, u), state

    f0 = float(jnp.sum(p["w"] ** 2))
    for _ in range(50):
        p, state = step(p, state)
    assert float(jnp.sum(p["w"] ** 2)) < f0, name


def test_signum_takes_sign_steps():
    tx = optim.create("signsgd", learning_rate=0.1)
    p = {"w": jnp.array([5.0, -5.0])}
    g = {"w": jnp.array([0.001, -100.0])}
    state = tx.init(p)
    u, _ = tx.update(g, state, p)
    np.testing.assert_allclose(np.array(u["w"]), [-0.1, 0.1], rtol=1e-6)


def test_multi_precision_no_drift():
    """bf16 params with tiny updates: MP must accumulate in f32 master.
    Mirrors the reference's mp_sgd_update fp32-master semantics."""
    lr = 1e-3
    tx_mp = optim.create("sgd", multi_precision=True, learning_rate=lr)
    p = {"w": jnp.ones(4, jnp.bfloat16)}
    state = tx_mp.init(p)
    g = {"w": jnp.full(4, 1e-3, jnp.bfloat16)}
    for _ in range(1000):
        u, state = tx_mp.update(g, state, p)
        p = optax.apply_updates(p, u)
    # master accumulated 1000 * 1e-6 = 1e-3 decrease
    master = np.array(state.master["w"])
    np.testing.assert_allclose(master, 1.0 - 1e-3, rtol=1e-4)
    # without MP, each update rounds to zero in bf16
    tx = optim.create("sgd", learning_rate=lr)
    p2 = {"w": jnp.ones(4, jnp.bfloat16)}
    s2 = tx.init(p2)
    u2, _ = tx.update(g, s2, p2)
    assert float(np.array(optax.apply_updates(p2, u2)["w"])[0]) == 1.0


def test_optimizer_with_namedtuple_params():
    """Param trees containing NamedTuples (LSTMWeights) must work."""
    tx = optim.create("adam", learning_rate=0.01)
    p = [LSTMWeights(wx=jnp.ones((2, 4)), wh=jnp.ones((1, 4)), b=jnp.zeros(4))]
    state = tx.init(p)
    g = jax.tree_util.tree_map(jnp.ones_like, p)
    u, state = tx.update(g, state, p)
    p2 = optax.apply_updates(p, u)
    assert isinstance(p2[0], LSTMWeights)
    assert float(p2[0].wx[0, 0]) < 1.0


def test_rescale_and_clip():
    tx = optim.sgd(1.0, rescale_grad=0.5, clip_gradient=0.1)
    p = {"w": jnp.array([0.0])}
    g = {"w": jnp.array([10.0])}
    u, _ = tx.update(g, tx.init(p), p)
    # 10*0.5=5 clipped to 0.1, lr 1 -> -0.1
    np.testing.assert_allclose(np.array(u["w"]), [-0.1], rtol=1e-6)


def test_create_unknown_raises():
    with pytest.raises(ValueError, match="unknown optimizer"):
        optim.create("nope")


# ---------------------------------------------------------------------------
# Schedulers
# ---------------------------------------------------------------------------


def test_factor_scheduler():
    """Reference drops only when num_update > count + step (strict >):
    update 10 itself still sees the pre-drop lr, update 11 the dropped."""
    s = optim.FactorScheduler(step=10, factor=0.5, base_lr=1.0)
    assert float(s(0)) == 1.0
    assert float(s(10)) == 1.0
    np.testing.assert_allclose(float(s(11)), 0.5)
    np.testing.assert_allclose(float(s(20)), 0.5)
    np.testing.assert_allclose(float(s(25)), 0.25)


def test_multifactor_scheduler():
    """Strict >: the drop lands on the update AFTER each threshold."""
    s = optim.MultiFactorScheduler(steps=[5, 15], factor=0.1, base_lr=1.0)
    assert float(s(4)) == 1.0
    assert float(s(5)) == 1.0
    np.testing.assert_allclose(float(s(6)), 0.1, rtol=1e-6)
    np.testing.assert_allclose(float(s(15)), 0.1, rtol=1e-6)
    np.testing.assert_allclose(float(s(20)), 0.01, rtol=1e-6)


def test_poly_scheduler_with_warmup():
    s = optim.PolyScheduler(max_update=100, base_lr=1.0, pwr=2,
                            warmup_steps=10, warmup_begin_lr=0.0)
    np.testing.assert_allclose(float(s(5)), 0.5, rtol=1e-6)  # linear warmup
    np.testing.assert_allclose(float(s(10)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(float(s(100)), 0.0, atol=1e-7)
    mid = float(s(55))  # frac=0.5 -> (1-0.5)^2 = 0.25
    np.testing.assert_allclose(mid, 0.25, rtol=1e-5)


def test_cosine_scheduler():
    s = optim.CosineScheduler(max_update=100, base_lr=1.0, final_lr=0.1)
    np.testing.assert_allclose(float(s(0)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(float(s(50)), 0.55, rtol=1e-5)
    np.testing.assert_allclose(float(s(100)), 0.1, rtol=1e-5)


def test_schedule_inside_optimizer():
    sched = optim.FactorScheduler(step=1, factor=0.5, base_lr=1.0)
    tx = optim.sgd(sched)
    p = {"w": jnp.array([0.0])}
    g = {"w": jnp.array([1.0])}
    state = tx.init(p)
    u1, state = tx.update(g, state, p)
    u2, state = tx.update(g, state, p)
    np.testing.assert_allclose(np.array(u1["w"]), [-1.0])
    np.testing.assert_allclose(np.array(u2["w"]), [-0.5])


def test_scheduler_jit_traceable():
    s = optim.CosineScheduler(max_update=10, base_lr=1.0)
    f = jax.jit(lambda step: s(step))
    np.testing.assert_allclose(float(f(jnp.asarray(0))), 1.0, rtol=1e-6)


def test_make_factory():
    s = optim.make("cosine", max_update=10, base_lr=0.5)
    assert isinstance(s, optim.CosineScheduler)
    with pytest.raises(ValueError):
        optim.make("exotic")


def test_multi_precision_state_donates():
    """f32 params under ``multi_precision=True`` (what flax yields for a
    bf16 compute dtype): the f32 masters must be fresh buffers, or the
    TrainState holds one buffer twice and the donated train step raises
    ``Attempt to donate the same buffer twice`` — Module donates on every
    backend but the multi-device CPU, so only a chip would see it."""
    from dt_tpu.training.train_state import TrainState
    tx = optim.create("sgd", multi_precision=True, learning_rate=0.1,
                      momentum=0.9)
    params = {"w": jnp.ones((4, 4), jnp.float32),
              "b": jnp.zeros((4,), jnp.bfloat16)}
    state = TrainState.create(None, params, tx, {})
    bump = jax.jit(lambda s: s.replace(step=s.step + 1), donate_argnums=0)
    out = bump(state)
    assert int(out.step) == 1
    np.testing.assert_array_equal(np.asarray(out.opt_state.master["w"]),
                                  np.asarray(out.params["w"]))
