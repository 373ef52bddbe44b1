"""Model zoo tests: init + forward shapes, train/eval modes, BN stat updates.

Modeled on reference ``tests/python/unittest/test_gluon_model_zoo.py``
(instantiate every zoo model, check output shape)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dt_tpu import models


def _init_and_apply(model, x, training=False):
    rngs = {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}
    variables = model.init(rngs, x, training=training)
    out = model.apply(variables, x, training=training,
                      rngs={"dropout": jax.random.PRNGKey(2)} if training else None,
                      mutable=["batch_stats"] if training else False)
    return variables, out


@pytest.mark.parametrize("name,shape,classes", [
    ("lenet", (2, 28, 28, 1), 10),
    ("mlp", (2, 28, 28, 1), 10),
    ("resnet20_cifar", (2, 32, 32, 3), 10),
    ("resnet56_cifar", (2, 32, 32, 3), 10),
])
def test_small_models_forward(name, shape, classes):
    model = models.create(name, num_classes=classes)
    x = jnp.ones(shape)
    _, out = _init_and_apply(model, x)
    logits = out[0] if isinstance(out, tuple) else out
    assert logits.shape == (shape[0], classes)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("name,size", [
    ("resnet18", 64),
    ("resnet50", 64),
    ("vgg11_bn", 64),
    ("alexnet", 224),
    ("mobilenet", 64),
    pytest.param("mobilenet_v2", 64, marks=pytest.mark.slow),
    ("squeezenet", 64),
    pytest.param("densenet121", 64, marks=pytest.mark.slow),
    pytest.param("googlenet", 64, marks=pytest.mark.slow),
    ("resnext50", 64),
])
def test_imagenet_models_forward(name, size):
    model = models.create(name, num_classes=7)
    x = jnp.ones((1, size, size, 3))
    _, out = _init_and_apply(model, x)
    logits = out[0] if isinstance(out, tuple) else out
    assert logits.shape == (1, 7)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.slow
def test_inception_v3_forward():
    model = models.create("inception-v3", num_classes=5)
    x = jnp.ones((1, 299, 299, 3))
    _, out = _init_and_apply(model, x)
    assert out.shape == (1, 5)


@pytest.mark.slow
def test_inception_resnet_v2_forward():
    model = models.create("inception_resnet_v2", num_classes=5)
    x = jnp.ones((1, 299, 299, 3))
    _, out = _init_and_apply(model, x)
    assert out.shape == (1, 5)


def test_resnet_v2_variant():
    model = models.create("resnet18_v2", num_classes=4)
    x = jnp.ones((1, 64, 64, 3))
    _, out = _init_and_apply(model, x)
    assert out.shape == (1, 4)


def test_resnet50_param_count():
    """ResNet-50 v1 must have the canonical ~25.6M params."""
    model = models.create("resnet50", num_classes=1000)
    variables = jax.eval_shape(
        lambda x: model.init({"params": jax.random.PRNGKey(0)}, x,
                             training=False), jnp.ones((1, 224, 224, 3)))
    n = sum(np.prod(p.shape) for p in
            jax.tree_util.tree_leaves(variables["params"]))
    assert 25.4e6 < n < 25.8e6, n


def test_batch_stats_update_in_training():
    model = models.create("resnet20_cifar", num_classes=10)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 32, 32, 3)) * 3 + 1
    variables = model.init({"params": jax.random.PRNGKey(0)}, x, training=False)
    out, mutated = model.apply(variables, x, training=True,
                               mutable=["batch_stats"])
    before = jax.tree_util.tree_leaves(variables["batch_stats"])
    after = jax.tree_util.tree_leaves(mutated["batch_stats"])
    diffs = [float(jnp.abs(a - b).max()) for a, b in zip(before, after)]
    assert max(diffs) > 0, "training forward must update running stats"


def test_lstm_lm_forward_and_state():
    model = models.create("lstm_lm", vocab_size=50, embed_dim=16, hidden=16,
                          num_layers=2)
    tokens = jnp.zeros((5, 3), jnp.int32)
    rngs = {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}
    variables = model.init(rngs, tokens, training=False)
    (logits, (h, c)) = model.apply(variables, tokens, training=False)
    assert logits.shape == (5, 3, 50)
    assert h.shape == (2, 3, 16)
    # carry state forward
    (logits2, _) = model.apply(variables, tokens, state=(h, c), training=False)
    assert bool(jnp.isfinite(logits2).all())


def test_lstm_lm_tied_weights():
    model = models.create("lstm_lm", vocab_size=30, embed_dim=8, hidden=8,
                          num_layers=1, tie_weights=True)
    tokens = jnp.zeros((4, 2), jnp.int32)
    variables = model.init({"params": jax.random.PRNGKey(0)}, tokens,
                           training=False)
    logits, _ = model.apply(variables, tokens, training=False)
    assert logits.shape == (4, 2, 30)


def test_bf16_dtype_flows_through():
    model = models.create("resnet20_cifar", num_classes=10, dtype=jnp.bfloat16)
    x = jnp.ones((2, 32, 32, 3), jnp.bfloat16)
    variables = model.init({"params": jax.random.PRNGKey(0)}, x, training=False)
    out = model.apply(variables, x, training=False)
    assert out.dtype == jnp.bfloat16
    # params stay f32 (flax keeps param_dtype f32 by default)
    p = jax.tree_util.tree_leaves(variables["params"])[0]
    assert p.dtype == jnp.float32


def test_unknown_model_raises():
    with pytest.raises(ValueError, match="unknown model"):
        models.create("resnext9000")


def test_resnet_per_block_remat_equivalence():
    """models.create(..., remat=True) (per-block memory mirror,
    MXNET_BACKWARD_DO_MIRROR analog) must be a numerical no-op: same
    outputs AND same grads, only the backward's memory schedule differs
    (memory effect is TPU-only; XLA CPU folds the recompute away)."""
    import jax
    import jax.flatten_util
    import jax.numpy as jnp
    import numpy as np
    from dt_tpu import models
    from dt_tpu.ops import losses

    x = jnp.asarray(np.random.RandomState(0)
                    .uniform(-1, 1, (2, 32, 32, 3)).astype(np.float32))
    y = jnp.asarray([1, 3])
    outs = {}
    for remat in (False, True):
        m = models.create("resnet20_cifar", num_classes=4, remat=remat)
        # the weights as one program; the two runs compared stay op by op
        v = jax.jit(lambda x: m.init({"params": jax.random.PRNGKey(0)}, x,
                                     training=False))(x)

        def loss(p):
            out, _ = m.apply({"params": p,
                              "batch_stats": v["batch_stats"]},
                             x, training=True, mutable=["batch_stats"])
            return losses.softmax_cross_entropy(out, y)
        l, g = jax.value_and_grad(loss)(v["params"])
        flat, _ = jax.flatten_util.ravel_pytree(g)
        outs[remat] = (float(l), np.asarray(flat))
    assert outs[False][0] == outs[True][0]
    np.testing.assert_allclose(outs[False][1], outs[True][1],
                               rtol=1e-6, atol=1e-6)


def test_transformer_per_layer_remat_equivalence():
    """TransformerLM(remat=True): per-decoder-block memory mirror is a
    numerical no-op with an identical param tree (stable block{i}
    names)."""
    import jax
    import jax.flatten_util
    import jax.numpy as jnp
    import numpy as np
    from dt_tpu import models
    from dt_tpu.ops import losses

    x = jnp.asarray(np.random.RandomState(0).randint(0, 50, (2, 16)))
    outs = {}
    for remat in (False, True):
        m = models.create("transformer_lm", vocab_size=50, num_layers=2,
                          embed_dim=32, num_heads=4, max_len=16,
                          remat=remat)
        # the weights as one program; the two runs compared stay op by op
        v = jax.jit(lambda x: m.init({"params": jax.random.PRNGKey(0)}, x,
                                     training=False))(x)

        def loss(p):
            lg = m.apply({"params": p}, x, training=False)
            return losses.softmax_cross_entropy(lg.reshape(-1, 50),
                                                x.reshape(-1))
        l, g = jax.value_and_grad(loss)(v["params"])
        flat, _ = jax.flatten_util.ravel_pytree(g)
        outs[remat] = (float(l), np.asarray(flat))
    assert outs[False][0] == outs[True][0]
    np.testing.assert_allclose(outs[False][1], outs[True][1], rtol=1e-6,
                               atol=1e-7)
