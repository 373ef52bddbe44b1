"""Tests: fault injection + retries, LibSVM iter, visualization,
inception-bn/v4, fit pipelining correctness."""

import logging
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dt_tpu import data, models
from dt_tpu.elastic import Scheduler, WorkerClient


def test_drop_msg_fault_injection_with_retries(monkeypatch):
    """PS_DROP_MSG analog: 30% of control messages dropped; retries keep
    the protocol exact (the transport-fuzz test, SURVEY §5.2)."""
    monkeypatch.setenv("DT_DROP_MSG", "30")
    s = Scheduler(initial_workers=["a", "b"])
    try:
        ca = WorkerClient("127.0.0.1", s.port, host="a", is_new=False)
        cb = WorkerClient("127.0.0.1", s.port, host="b", is_new=False)
        outs = {}

        def push(c, v):
            outs[c.host] = c.allreduce("g", np.full(4, v, np.float32))

        for rnd in range(3):  # several rounds under drops
            outs.clear()
            ts = [threading.Thread(target=push, args=(c, i + 1.0))
                  for i, c in enumerate((ca, cb))]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            np.testing.assert_allclose(outs["a"], 1.5)
            np.testing.assert_allclose(outs["b"], 1.5)
    finally:
        s.close()


def test_libsvm_iter(tmp_path):
    p = tmp_path / "data.svm"
    p.write_text("1 0:0.5 3:1.5\n0 1:2.0\n1 2:3.0 0:1.0\n")
    it = data.LibSVMIter(str(p), data_shape=(4,), batch_size=2,
                         indexing="zero", last_batch_handle="pad")
    b = it.next()
    np.testing.assert_allclose(b.data[0], [0.5, 0, 0, 1.5])
    np.testing.assert_allclose(b.label[:2], [1, 0])
    # one-based is the DEFAULT (LibSVM standard)
    p1 = tmp_path / "one.svm"
    p1.write_text("1 1:0.5 4:1.5\n")
    it1 = data.LibSVMIter(str(p1), data_shape=(4,), batch_size=1)
    np.testing.assert_allclose(it1.next().data[0], [0.5, 0, 0, 1.5])
    # zero-based file under the one-based default fails loudly on index 0
    with pytest.raises(ValueError, match="out of range"):
        data.LibSVMIter(str(p), data_shape=(4,), batch_size=1)
    # out-of-range raises instead of silently wrapping
    pbad = tmp_path / "bad.svm"
    pbad.write_text("1 7:2.0\n")
    with pytest.raises(ValueError, match="out of range"):
        data.LibSVMIter(str(pbad), data_shape=(4,), batch_size=1)


@pytest.mark.slow
def test_inception_bn_and_v4_forward():
    for name, size in (("inception_bn", 64), ("inception_v4", 299)):
        model = models.create(name, num_classes=4)
        x = jnp.ones((1, size, size, 3))
        rngs = {"params": jax.random.PRNGKey(0),
                "dropout": jax.random.PRNGKey(1)}
        variables = model.init(rngs, x, training=False)
        out = model.apply(variables, x, training=False)
        assert out.shape == (1, 4), name


def test_visualization_summary():
    from dt_tpu import visualization as viz
    model = models.create("mlp", num_classes=3, hidden=(8,))
    x = np.ones((1, 4, 4, 1), np.float32)
    variables = model.init({"params": jax.random.PRNGKey(0)},
                           jnp.asarray(x), training=False)
    counts = viz.param_summary(variables)
    assert counts["total"] > 0
    hlo = viz.dump_hlo(
        lambda v, x: model.apply(v, x, training=False), variables,
        jnp.asarray(x))
    assert "dot" in hlo or "stablehlo" in hlo or "func" in hlo


def test_plot_network_dot(tmp_path):
    """plot_network (reference visualization.py:198): dot source over the
    traced jaxpr — inputs as ovals, conv/dense boxes with the reference's
    labels, shape-annotated edges, params hidden by default."""
    from dt_tpu import visualization as viz
    model = models.create("lenet", num_classes=4)
    x = np.ones((2, 28, 28, 1), np.float32)
    out = str(tmp_path / "net.dot")
    dot = viz.plot_network(model, jnp.asarray(x), title="lenet",
                           save_path=out)
    assert dot.startswith('digraph "lenet"')
    assert dot.rstrip().endswith("}")
    assert "Convolution" in dot and "FullyConnected" in dot
    assert "Pooling" in dot
    assert "shape=oval" in dot          # the data input
    assert "param[" not in dot           # hide_weights default
    assert "->" in dot and "2x28x28x1" in dot  # shape-labeled edge
    import os
    assert os.path.exists(out) and open(out).read() == dot
    # weights visible on request
    dot2 = viz.plot_network(model, jnp.asarray(x), hide_weights=False)
    assert "param[" in dot2
    # plain callables trace too; big graphs truncate
    dot3 = viz.plot_network(lambda a: (a @ a).sum(), np.eye(4),
                            max_nodes=1)
    assert "more ops" in dot3 or dot3.count("[label=") <= 4


def test_fit_metric_pipelining_counts_all_batches():
    """The one-step-behind metric update must still account every batch
    (incl. the final one)."""
    from dt_tpu.training import Module, metrics
    rng = np.random.RandomState(0)
    x = rng.normal(0, 1, (48, 4, 4, 1)).astype(np.float32)
    y = (x.mean((1, 2, 3)) > 0).astype(np.int32)
    train = data.NDArrayIter(x, y, batch_size=16)
    mod = Module(models.create("mlp", num_classes=2, hidden=(4,)))
    m = mod.fit(train, num_epoch=1, eval_metric="acc")
    assert m.num_inst == 48  # 3 batches x 16, none skipped


def test_nce_loss_numpy_oracle():
    """nce_loss == mean BCE-with-logits over the K+1 dot-product scores
    (reference example/nce-loss/nce.py LogisticRegressionOutput path)."""
    import numpy as np
    import jax.numpy as jnp
    from dt_tpu.ops import losses

    rng = np.random.RandomState(0)
    B, K, D, V = 4, 3, 8, 20
    hidden = rng.normal(size=(B, D)).astype(np.float32)
    table = rng.normal(size=(V, D)).astype(np.float32)
    ids = rng.randint(0, V, (B, K + 1))
    w = np.zeros((B, K + 1), np.float32)
    w[:, 0] = 1.0

    got = float(losses.nce_loss_from_ids(
        jnp.asarray(hidden), jnp.asarray(table), jnp.asarray(ids),
        jnp.asarray(w)))
    # numpy oracle
    scores = np.einsum("bd,bkd->bk", hidden, table[ids])
    p = 1.0 / (1.0 + np.exp(-scores))
    bce = -(w * np.log(p) + (1 - w) * np.log(1 - p))
    np.testing.assert_allclose(got, bce.mean(), rtol=1e-5)


def test_stochastic_depth_expected_value_and_determinism():
    """Eval-mode stochastic-depth residuals are blended by the survival
    probability; death_rate=0 is exactly the plain network."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dt_tpu import models

    x = jnp.asarray(np.random.RandomState(0).normal(
        size=(2, 16, 16, 3)).astype(np.float32))
    plain = models.create("resnet20_cifar", num_classes=4)
    sd0 = models.create("resnet20_cifar", num_classes=4,
                        stochastic_depth=0.0)
    v = plain.init({"params": jax.random.PRNGKey(0)}, x, training=False)
    np.testing.assert_array_equal(
        np.asarray(plain.apply(v, x, training=False)),
        np.asarray(sd0.apply(v, x, training=False)))

    sd = models.create("resnet20_cifar", num_classes=4,
                       stochastic_depth=0.8)
    out1 = sd.apply(v, x, training=False)
    out2 = sd.apply(v, x, training=False)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    # blending changes eval output vs the plain net
    assert float(jnp.abs(out1 - plain.apply(v, x, training=False)).max()) \
        > 1e-6
    # train mode: different rng draws drop different blocks
    t1 = sd.apply(v, x, training=True,
                  rngs={"dropout": jax.random.PRNGKey(1)},
                  mutable=["batch_stats"])[0]
    t2 = sd.apply(v, x, training=True,
                  rngs={"dropout": jax.random.PRNGKey(2)},
                  mutable=["batch_stats"])[0]
    assert float(jnp.abs(t1 - t2).max()) > 1e-6
