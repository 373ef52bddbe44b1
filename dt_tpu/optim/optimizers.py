"""Optimizer zoo with the reference's update-rule semantics.

Reference: ``python/mxnet/optimizer/optimizer.py:41-1504`` (SGD, Signum, FTML,
LBSGD, DCASGD, NAG, SGLD, Adam, AdaGrad, RMSProp, AdaDelta, Ftrl, Adamax,
Nadam) and the fused C++ kernels ``src/operator/optimizer_op.cc``.  Each
optimizer is an ``optax.GradientTransformation``; updates are *deltas added to
params* (optax convention), so rules below negate the reference's
``weight -= ...`` forms.

Reference-semantics notes preserved on purpose:

- ``rescale_grad``/``clip_gradient`` are transformation stages, applied before
  wd like the reference's ``Optimizer._get_wd``/``clip`` pipeline.
- SGD/NAG apply *coupled* weight decay (wd folded into the gradient), like
  ``sgd_update``/``sgd_mom_update``.
- Multi-precision (fp32 master weights for bf16/fp16 params — the server-side
  ``store_realt_`` copies, ``src/kvstore/kvstore_dist_server.h:240-273``) is a
  wrapper: :func:`with_multi_precision`.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import optax

ScalarOrSchedule = Union[float, Callable[[jnp.ndarray], jnp.ndarray]]


def _lr_at(lr: ScalarOrSchedule, count):
    """Schedules receive the reference's 1-based ``num_update`` (mxnet
    increments the count BEFORE the lr lookup), not the 0-based slot
    counter — the strict-greater drop thresholds in
    :mod:`dt_tpu.optim.lr_scheduler` depend on this convention."""
    return lr(count + 1) if callable(lr) else jnp.asarray(lr, jnp.float32)


def _preprocess(g, w, rescale_grad, clip_gradient, wd):
    """The reference's grad pipeline: rescale -> clip -> +wd*w
    (``optimizer.py`` SGD.update_impl)."""
    g = g.astype(jnp.float32) * rescale_grad
    if clip_gradient is not None:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    if wd:
        g = g + wd * w.astype(jnp.float32)
    return g


class CountState(NamedTuple):
    count: jnp.ndarray


class MomentumState(NamedTuple):
    count: jnp.ndarray
    mom: Any


class TwoSlotState(NamedTuple):
    count: jnp.ndarray
    a: Any
    b: Any


class ThreeSlotState(NamedTuple):
    count: jnp.ndarray
    a: Any
    b: Any
    c: Any



def _multimap(fn, n_out, tree, *rest):
    """tree_map with multiple output trees, via explicit flatten/unflatten.

    Avoids ``is_leaf`` tricks that break when user param trees contain tuples
    or NamedTuples (e.g. ``dt_tpu.ops.rnn.LSTMWeights``).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    rests = [treedef.flatten_up_to(r) for r in rest]
    outs = [fn(*args) for args in zip(leaves, *rests)]
    return tuple(treedef.unflatten([o[i] for o in outs]) for i in range(n_out))

def _zeros_like_f32(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


def sgd(learning_rate: ScalarOrSchedule = 0.01, momentum: float = 0.0,
        weight_decay: float = 0.0, rescale_grad: float = 1.0,
        clip_gradient: Optional[float] = None) -> optax.GradientTransformation:
    """SGD with momentum.  Reference rule (``src/operator/optimizer_op-inl.h``
    sgd_mom_update): ``mom = momentum*mom - lr*(g + wd*w); w += mom``."""

    def init(params):
        if momentum == 0.0:
            return CountState(jnp.zeros((), jnp.int32))
        return MomentumState(jnp.zeros((), jnp.int32), _zeros_like_f32(params))

    def update(grads, state, params):
        lr = _lr_at(learning_rate, state.count)

        if momentum == 0.0:
            def u(g, w):
                g = _preprocess(g, w, rescale_grad, clip_gradient, weight_decay)
                return (-lr * g).astype(w.dtype)
            updates = jax.tree_util.tree_map(u, grads, params)
            return updates, CountState(state.count + 1)

        def u(g, w, m):
            g = _preprocess(g, w, rescale_grad, clip_gradient, weight_decay)
            new_m = momentum * m - lr * g
            return new_m.astype(w.dtype), new_m
        updates, new_mom = _multimap(u, 2, grads, params, state.mom)
        return updates, MomentumState(state.count + 1, new_mom)

    return optax.GradientTransformation(init, update)


def nag(learning_rate: ScalarOrSchedule = 0.01, momentum: float = 0.9,
        weight_decay: float = 0.0, rescale_grad: float = 1.0,
        clip_gradient: Optional[float] = None) -> optax.GradientTransformation:
    """Nesterov SGD.  Reference: NAG (``optimizer.py``):
    ``mom = momentum*mom + g; w -= lr*(g + momentum*mom)``."""

    def init(params):
        return MomentumState(jnp.zeros((), jnp.int32), _zeros_like_f32(params))

    def update(grads, state, params):
        lr = _lr_at(learning_rate, state.count)

        def u(g, w, m):
            g = _preprocess(g, w, rescale_grad, clip_gradient, weight_decay)
            new_m = momentum * m + g
            return (-lr * (g + momentum * new_m)).astype(w.dtype), new_m
        updates, new_mom = _multimap(u, 2, grads, params, state.mom)
        return updates, MomentumState(state.count + 1, new_mom)

    return optax.GradientTransformation(init, update)


def adam(learning_rate: ScalarOrSchedule = 0.001, beta1: float = 0.9,
         beta2: float = 0.999, epsilon: float = 1e-8,
         weight_decay: float = 0.0, rescale_grad: float = 1.0,
         clip_gradient: Optional[float] = None) -> optax.GradientTransformation:
    """Adam with bias correction.  Reference: Adam (``optimizer.py``,
    ``adam_update`` kernel) — wd is coupled (added to grad), not AdamW."""

    def init(params):
        return TwoSlotState(jnp.zeros((), jnp.int32), _zeros_like_f32(params),
                            _zeros_like_f32(params))

    def update(grads, state, params):
        t = state.count + 1
        lr = _lr_at(learning_rate, state.count)
        lr_t = lr * jnp.sqrt(1 - beta2 ** t.astype(jnp.float32)) / \
            (1 - beta1 ** t.astype(jnp.float32))

        def u(g, w, m, v):
            g = _preprocess(g, w, rescale_grad, clip_gradient, weight_decay)
            new_m = beta1 * m + (1 - beta1) * g
            new_v = beta2 * v + (1 - beta2) * g * g
            upd = -lr_t * new_m / (jnp.sqrt(new_v) + epsilon)
            return upd.astype(w.dtype), new_m, new_v
        updates, new_m, new_v = _multimap(u, 3, grads, params, state.a, state.b)
        return updates, TwoSlotState(t, new_m, new_v)

    return optax.GradientTransformation(init, update)


def adagrad(learning_rate: ScalarOrSchedule = 0.01, epsilon: float = 1e-7,
            weight_decay: float = 0.0, rescale_grad: float = 1.0,
            clip_gradient: Optional[float] = None) -> optax.GradientTransformation:
    """AdaGrad.  Reference: AdaGrad (``optimizer.py``): ``hist += g²``
    (wd NOT folded into the accumulated grad); ``w -= lr * (g /
    sqrt(hist + eps) + wd * w)`` — wd is a separate decoupled term."""

    def init(params):
        return MomentumState(jnp.zeros((), jnp.int32), _zeros_like_f32(params))

    def update(grads, state, params):
        lr = _lr_at(learning_rate, state.count)

        def u(g, w, h):
            g = _preprocess(g, w, rescale_grad, clip_gradient, 0.0)
            new_h = h + g * g
            upd = -lr * (g / jnp.sqrt(new_h + epsilon)
                         + weight_decay * w.astype(jnp.float32))
            return upd.astype(w.dtype), new_h
        updates, new_h = _multimap(u, 2, grads, params, state.mom)
        return updates, MomentumState(state.count + 1, new_h)

    return optax.GradientTransformation(init, update)


def rmsprop(learning_rate: ScalarOrSchedule = 0.001, rho: float = 0.9,
            momentum: float = 0.0, epsilon: float = 1e-8,
            centered: bool = False, weight_decay: float = 0.0,
            rescale_grad: float = 1.0,
            clip_gradient: Optional[float] = None) -> optax.GradientTransformation:
    """RMSProp (Tieleman–Hinton; centered variant per Graves 2013).
    Reference: RMSProp (``optimizer.py``, ``rmsprop_update``/
    ``rmspropalex_update`` kernels)."""

    def init(params):
        z = _zeros_like_f32(params)
        if centered:
            return ThreeSlotState(jnp.zeros((), jnp.int32), z, z, z)
        return TwoSlotState(jnp.zeros((), jnp.int32), z, z)

    def update(grads, state, params):
        lr = _lr_at(learning_rate, state.count)

        if centered:
            def u(g, w, n, gavg, d):
                g = _preprocess(g, w, rescale_grad, clip_gradient, weight_decay)
                new_n = rho * n + (1 - rho) * g * g
                new_g = rho * gavg + (1 - rho) * g
                new_d = momentum * d - lr * g / jnp.sqrt(
                    new_n - new_g * new_g + epsilon)
                return new_d.astype(w.dtype), new_n, new_g, new_d
            updates, n2, g2, d2 = _multimap(u, 4, grads, params, state.a,
                                            state.b, state.c)
            return updates, ThreeSlotState(state.count + 1, n2, g2, d2)

        def u(g, w, n, m):
            g = _preprocess(g, w, rescale_grad, clip_gradient, weight_decay)
            new_n = rho * n + (1 - rho) * g * g
            step = lr * g / jnp.sqrt(new_n + epsilon)
            new_m = momentum * m - step if momentum else -step
            upd = new_m if momentum else -step
            return upd.astype(w.dtype), new_n, (new_m if momentum else m)
        updates, n2, m2 = _multimap(u, 3, grads, params, state.a, state.b)
        return updates, TwoSlotState(state.count + 1, n2, m2)

    return optax.GradientTransformation(init, update)


def adadelta(rho: float = 0.9, epsilon: float = 1e-5, weight_decay: float = 0.0,
             rescale_grad: float = 1.0,
             clip_gradient: Optional[float] = None) -> optax.GradientTransformation:
    """AdaDelta (no LR).  Reference: AdaDelta (``optimizer.py``)."""

    def init(params):
        z = _zeros_like_f32(params)
        return TwoSlotState(jnp.zeros((), jnp.int32), z, z)

    def update(grads, state, params):
        def u(g, w, acc_g, acc_d):
            g = _preprocess(g, w, rescale_grad, clip_gradient, weight_decay)
            new_acc_g = rho * acc_g + (1 - rho) * g * g
            d = jnp.sqrt(acc_d + epsilon) / jnp.sqrt(new_acc_g + epsilon) * g
            new_acc_d = rho * acc_d + (1 - rho) * d * d
            return (-d).astype(w.dtype), new_acc_g, new_acc_d
        updates, ag, ad = _multimap(u, 3, grads, params, state.a, state.b)
        return updates, TwoSlotState(state.count + 1, ag, ad)

    return optax.GradientTransformation(init, update)


def ftrl(learning_rate: ScalarOrSchedule = 0.1, lamda1: float = 0.01,
         beta: float = 1.0, weight_decay: float = 0.0,
         rescale_grad: float = 1.0,
         clip_gradient: Optional[float] = None) -> optax.GradientTransformation:
    """FTRL-proximal.  Reference: Ftrl (``optimizer.py``, ``ftrl_update``):
    ``z += g - (sqrt(n+g²)-sqrt(n))/lr * w; n += g²;
    w = -z / ((beta+sqrt(n))/lr + wd) if |z| > l1 (soft-threshold)``."""

    def init(params):
        z = _zeros_like_f32(params)
        return TwoSlotState(jnp.zeros((), jnp.int32), z, z)

    def update(grads, state, params):
        lr = _lr_at(learning_rate, state.count)

        def u(g, w, z, n):
            g = _preprocess(g, w, rescale_grad, clip_gradient, 0.0)
            w32 = w.astype(jnp.float32)
            new_z = z + g - (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / lr * w32
            new_n = n + g * g
            new_w = jnp.where(
                jnp.abs(new_z) > lamda1,
                -(new_z - jnp.sign(new_z) * lamda1) /
                ((beta + jnp.sqrt(new_n)) / lr + weight_decay),
                0.0)
            return (new_w - w32).astype(w.dtype), new_z, new_n
        updates, z2, n2 = _multimap(u, 3, grads, params, state.a, state.b)
        return updates, TwoSlotState(state.count + 1, z2, n2)

    return optax.GradientTransformation(init, update)


def adamax(learning_rate: ScalarOrSchedule = 0.002, beta1: float = 0.9,
           beta2: float = 0.999, weight_decay: float = 0.0,
           rescale_grad: float = 1.0,
           clip_gradient: Optional[float] = None) -> optax.GradientTransformation:
    """Adamax (Adam w/ infinity norm).  Reference: Adamax (``optimizer.py``)."""

    def init(params):
        z = _zeros_like_f32(params)
        return TwoSlotState(jnp.zeros((), jnp.int32), z, z)

    def update(grads, state, params):
        t = state.count + 1
        lr = _lr_at(learning_rate, state.count)
        lr_t = lr / (1 - beta1 ** t.astype(jnp.float32))

        def u(g, w, m, v):
            g = _preprocess(g, w, rescale_grad, clip_gradient, weight_decay)
            new_m = beta1 * m + (1 - beta1) * g
            new_v = jnp.maximum(beta2 * v, jnp.abs(g))
            return (-lr_t * new_m / (new_v + 1e-8)).astype(w.dtype), new_m, new_v
        updates, m2, v2 = _multimap(u, 3, grads, params, state.a, state.b)
        return updates, TwoSlotState(t, m2, v2)

    return optax.GradientTransformation(init, update)


def nadam(learning_rate: ScalarOrSchedule = 0.001, beta1: float = 0.9,
          beta2: float = 0.999, epsilon: float = 1e-8,
          schedule_decay: float = 0.004, weight_decay: float = 0.0,
          rescale_grad: float = 1.0,
          clip_gradient: Optional[float] = None) -> optax.GradientTransformation:
    """Nadam (Adam + Nesterov momentum schedule).  Reference: Nadam
    (``optimizer.py``), Dozat 2016 momentum-cache schedule."""

    def init(params):
        z = _zeros_like_f32(params)
        # c = running product of momentum schedule
        return ThreeSlotState(jnp.zeros((), jnp.int32), z, z,
                              jnp.ones((), jnp.float32))

    def update(grads, state, params):
        t = (state.count + 1).astype(jnp.float32)
        lr = _lr_at(learning_rate, state.count)
        m_t = beta1 * (1 - 0.5 * 0.96 ** (t * schedule_decay))
        m_t1 = beta1 * (1 - 0.5 * 0.96 ** ((t + 1) * schedule_decay))
        m_prod = state.c * m_t
        m_prod1 = m_prod * m_t1

        def u(g, w, m, v):
            g = _preprocess(g, w, rescale_grad, clip_gradient, weight_decay)
            g_hat = g / (1 - m_prod)
            new_m = beta1 * m + (1 - beta1) * g
            m_hat = new_m / (1 - m_prod1)
            new_v = beta2 * v + (1 - beta2) * g * g
            v_hat = new_v / (1 - beta2 ** t)
            m_bar = (1 - m_t) * g_hat + m_t1 * m_hat
            return (-lr * m_bar / (jnp.sqrt(v_hat) + epsilon)).astype(w.dtype), \
                new_m, new_v
        updates, m2, v2 = _multimap(u, 3, grads, params, state.a, state.b)
        return updates, ThreeSlotState(state.count + 1, m2, v2, m_prod)

    return optax.GradientTransformation(init, update)


def signum(learning_rate: ScalarOrSchedule = 0.01, momentum: float = 0.9,
           weight_decay: float = 0.0, wd_lh: float = 0.0,
           rescale_grad: float = 1.0,
           clip_gradient: Optional[float] = None) -> optax.GradientTransformation:
    """signSGD / Signum (Bernstein et al. 2018).  Reference: Signum
    (``optimizer.py``, ``signum_update``): ``mom = momentum*mom -
    (1-momentum)*(g + wd*w); w -= lr*(sign(-mom)... )`` — net effect
    ``w -= lr*(sign(mom-direction) + wd_lh*w)``.  ``momentum=0`` gives
    signSGD."""

    def init(params):
        return MomentumState(jnp.zeros((), jnp.int32), _zeros_like_f32(params))

    def update(grads, state, params):
        lr = _lr_at(learning_rate, state.count)

        def u(g, w, m):
            g = _preprocess(g, w, rescale_grad, clip_gradient, weight_decay)
            if momentum:
                new_m = momentum * m + (1 - momentum) * g
            else:
                new_m = g
            upd = -lr * (jnp.sign(new_m) + wd_lh * w.astype(jnp.float32))
            return upd.astype(w.dtype), new_m
        updates, m2 = _multimap(u, 2, grads, params, state.mom)
        return updates, MomentumState(state.count + 1, m2)

    return optax.GradientTransformation(init, update)


def ftml(learning_rate: ScalarOrSchedule = 0.0025, beta1: float = 0.6,
         beta2: float = 0.999, epsilon: float = 1e-8,
         weight_decay: float = 0.0, rescale_grad: float = 1.0,
         clip_gradient: Optional[float] = None) -> optax.GradientTransformation:
    """FTML — Follow The Moving Leader (Zheng & Kwok 2017).  Reference: FTML
    (``optimizer.py``, ``ftml_update`` kernel)."""

    def init(params):
        z = _zeros_like_f32(params)
        return ThreeSlotState(jnp.zeros((), jnp.int32), z, z, z)

    def update(grads, state, params):
        t = (state.count + 1).astype(jnp.float32)
        lr = _lr_at(learning_rate, state.count)

        def u(g, w, d, v, z):
            g = _preprocess(g, w, rescale_grad, clip_gradient, weight_decay)
            new_v = beta2 * v + (1 - beta2) * g * g
            d_t = (1 - beta1 ** t) / lr * \
                (jnp.sqrt(new_v / (1 - beta2 ** t)) + epsilon)
            sigma = d_t - beta1 * d
            new_z = beta1 * z + (1 - beta1) * g - sigma * w.astype(jnp.float32)
            new_w = -new_z / d_t
            return (new_w - w.astype(jnp.float32)).astype(w.dtype), d_t, new_v, new_z
        updates, d2, v2, z2 = _multimap(u, 4, grads, params, state.a, state.b,
                                        state.c)
        return updates, ThreeSlotState(state.count + 1, d2, v2, z2)

    return optax.GradientTransformation(init, update)


def sgld(learning_rate: ScalarOrSchedule = 0.01, weight_decay: float = 0.0,
         rescale_grad: float = 1.0, clip_gradient: Optional[float] = None,
         seed: int = 0) -> optax.GradientTransformation:
    """Stochastic Gradient Langevin Dynamics.  Reference: SGLD
    (``optimizer.py``): ``w -= lr/2*(g+wd*w) + N(0, sqrt(lr))``."""

    def init(params):
        return MomentumState(jnp.zeros((), jnp.int32),
                             jax.random.PRNGKey(seed))

    def update(grads, state, params):
        lr = _lr_at(learning_rate, state.count)
        key, sub = jax.random.split(state.mom)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(sub, len(leaves))
        gleaves = treedef.flatten_up_to(grads)
        ups = []
        for g, w, k in zip(gleaves, leaves, keys):
            g = _preprocess(g, w, rescale_grad, clip_gradient, weight_decay)
            noise = jax.random.normal(k, w.shape) * jnp.sqrt(lr)
            ups.append((-lr / 2 * g + noise).astype(w.dtype))
        return treedef.unflatten(ups), MomentumState(state.count + 1, key)

    return optax.GradientTransformation(init, update)


def dcasgd(learning_rate: ScalarOrSchedule = 0.01, momentum: float = 0.0,
           lamda: float = 0.04, weight_decay: float = 0.0,
           rescale_grad: float = 1.0,
           clip_gradient: Optional[float] = None) -> optax.GradientTransformation:
    """Delay-Compensated ASGD (Zheng et al. 2016).  Reference: DCASGD
    (``optimizer.py``): compensates stale gradients with
    ``g + lambda*g²*(w - w_prev)``.  In the synchronous SPMD data plane there
    is no staleness; kept for API parity (previous-weight slot maintained)."""

    def init(params):
        return TwoSlotState(jnp.zeros((), jnp.int32), _zeros_like_f32(params),
                            jax.tree_util.tree_map(
                                lambda p: p.astype(jnp.float32), params))

    def update(grads, state, params):
        lr = _lr_at(learning_rate, state.count)

        def u(g, w, m, wp):
            g = _preprocess(g, w, rescale_grad, clip_gradient, weight_decay)
            w32 = w.astype(jnp.float32)
            comp = g + lamda * g * g * (w32 - wp)
            new_m = momentum * m - lr * comp
            return new_m.astype(w.dtype), new_m, w32
        updates, m2, wp2 = _multimap(u, 3, grads, params, state.a, state.b)
        return updates, TwoSlotState(state.count + 1, m2, wp2)

    return optax.GradientTransformation(init, update)


def lbsgd(learning_rate: ScalarOrSchedule = 0.01, momentum: float = 0.9,
          weight_decay: float = 0.0, eta: float = 0.001,
          rescale_grad: float = 1.0,
          clip_gradient: Optional[float] = None) -> optax.GradientTransformation:
    """Large-Batch SGD with LARS-style layer-wise adaptive rates.

    Reference: LBSGD (``optimizer.py``) implements warmup strategies +
    LARS coefficient ``eta*||w||/(||g||+wd*||w||)`` for large-batch training
    (You et al. 2017).  Warmup lives in the LR schedule here
    (``dt_tpu.optim.lr_scheduler`` warmup_* args)."""

    def init(params):
        return MomentumState(jnp.zeros((), jnp.int32), _zeros_like_f32(params))

    def update(grads, state, params):
        lr = _lr_at(learning_rate, state.count)

        def u(g, w, m):
            g32 = g.astype(jnp.float32) * rescale_grad
            if clip_gradient is not None:
                g32 = jnp.clip(g32, -clip_gradient, clip_gradient)
            w32 = w.astype(jnp.float32)
            wnorm = jnp.linalg.norm(w32)
            gnorm = jnp.linalg.norm(g32)
            lars = jnp.where(
                (wnorm > 0) & (gnorm > 0),
                eta * wnorm / (gnorm + weight_decay * wnorm + 1e-9), 1.0)
            g32 = g32 + weight_decay * w32
            new_m = momentum * m - lr * lars * g32
            return new_m.astype(w.dtype), new_m
        updates, m2 = _multimap(u, 2, grads, params, state.mom)
        return updates, MomentumState(state.count + 1, m2)

    return optax.GradientTransformation(init, update)


def lamb(learning_rate: ScalarOrSchedule = 0.001, beta1: float = 0.9,
         beta2: float = 0.999, epsilon: float = 1e-6,
         weight_decay: float = 0.0) -> optax.GradientTransformation:
    """LAMB (You et al. 2019) — beyond-reference extra for large-batch TPU
    training; delegates to optax."""
    return optax.lamb(learning_rate, b1=beta1, b2=beta2, eps=epsilon,
                      weight_decay=weight_decay)


# ---------------------------------------------------------------------------
# Multi-precision wrapper
# ---------------------------------------------------------------------------


class MultiPrecisionState(NamedTuple):
    master: Any  # f32 copies of params
    inner: Any


def with_multi_precision(inner: optax.GradientTransformation
                         ) -> optax.GradientTransformation:
    """Keep fp32 master weights for low-precision params.

    Reference: MP updates (``mp_sgd_update`` in ``optimizer_op.cc``; server
    master copies ``kvstore_dist_server.h:240-273``).  The inner optimizer
    sees f32 masters; the returned update makes the applied param exactly
    ``round_to_param_dtype(master + delta)``, so low-precision params never
    accumulate rounding drift.
    """

    def init(params):
        # jnp.array copies: ``astype`` on an f32 param (flax keeps params
        # f32 under a bf16 compute dtype) returns the SAME buffer, and a
        # TrainState holding one buffer twice cannot be donated
        master = jax.tree_util.tree_map(
            lambda p: jnp.array(p, dtype=jnp.float32), params)
        return MultiPrecisionState(master, inner.init(master))

    def update(grads, state, params):
        grads32 = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        deltas, new_inner = inner.update(grads32, state.inner, state.master)
        new_master = jax.tree_util.tree_map(
            lambda m, d: m + d.astype(jnp.float32), state.master, deltas)
        updates = jax.tree_util.tree_map(
            lambda w, nm: nm.astype(w.dtype) - w, params, new_master)
        return updates, MultiPrecisionState(new_master, new_inner)

    return optax.GradientTransformation(init, update)


# ---------------------------------------------------------------------------
# Registry (reference: Optimizer.create_optimizer / @register,
# ``optimizer.py:41-120``)
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[..., optax.GradientTransformation]] = {
    "sgd": sgd,
    "nag": nag,
    "adam": adam,
    "adagrad": adagrad,
    "rmsprop": rmsprop,
    "adadelta": adadelta,
    "ftrl": ftrl,
    "adamax": adamax,
    "nadam": nadam,
    "signum": signum,
    "signsgd": lambda learning_rate=0.01, **kw: signum(learning_rate,
                                                       momentum=0.0, **kw),
    "ftml": ftml,
    "sgld": sgld,
    "dcasgd": dcasgd,
    "lbsgd": lbsgd,
    "lamb": lamb,
}


def register(name: str, factory: Callable[..., optax.GradientTransformation]):
    """Register a custom optimizer under ``name`` (reference
    ``Optimizer.register`` decorator)."""
    _REGISTRY[name.lower()] = factory
    return factory


def create(name: str, multi_precision: bool = False, **kwargs
           ) -> optax.GradientTransformation:
    """Create an optimizer by name (reference ``mx.optimizer.create``)."""
    key = name.lower()
    if key not in _REGISTRY:
        raise ValueError(
            f"unknown optimizer {name!r}; registered: {sorted(_REGISTRY)}")
    tx = _REGISTRY[key](**kwargs)
    if multi_precision:
        tx = with_multi_precision(tx)
    return tx
