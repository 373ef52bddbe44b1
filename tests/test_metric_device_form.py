"""The device form of a metric (``dt_tpu/training/metrics.py``): ``fit`` and
``score`` reduce a step's logits to the metric's per-row statistics inside the
compiled program and fetch those; a metric that declares none is handed the
float32 softmax of the logits on the host, as before.  (Reference analog:
``python/mxnet/metric.py`` took NDArrays and pulled every output to numpy,
``asnumpy()`` per update; the per-row reduction has no counterpart there.)"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dt_tpu import data, models
from dt_tpu.obs import device as obs_device
from dt_tpu.obs import metrics as obs_metrics
from dt_tpu.obs import trace as obs_trace
from dt_tpu.training import Module, metrics
from dt_tpu.training import module as module_lib

CLASSES = 12

# every metric with a device form, and composites of them
DEVICE_METRICS = {
    "acc": lambda: metrics.Accuracy(),
    "top3": lambda: metrics.TopKAccuracy(3),
    "ce": lambda: metrics.CrossEntropy(),
    "ce_eps": lambda: metrics.CrossEntropy(eps=1e-2),
    "nll": lambda: metrics.NegativeLogLikelihood(),
    "perplexity": lambda: metrics.Perplexity(),
    "perplexity_ignore": lambda: metrics.Perplexity(ignore_label=0),
    "acc+ce": lambda: metrics.create(["acc", "ce"]),
    "top2+perplexity_ignore": lambda: metrics.CompositeEvalMetric(
        [metrics.TopKAccuracy(2), metrics.Perplexity(ignore_label=1)]),
}
HOST_METRICS = {
    "custom": lambda: metrics.CustomMetric(lambda lb, p: 0.0),
    "f1": lambda: metrics.F1(),
    "mae": lambda: metrics.MAE(),
    "mse": lambda: metrics.MSE(),
    "rmse": lambda: metrics.RMSE(),
    "loss": lambda: metrics.Loss(),
    "acc+f1": lambda: metrics.create(["acc", "f1"]),
}


def _softmax32(logits):
    z = np.asarray(logits).astype(np.float32)
    e = np.exp(z - z.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _logits(shape, dtype, seed):
    """Rows without ties, exact in bfloat16: each a permutation of one grid
    of quarter steps (where two classes tie, which of them numpy's
    argpartition keeps is not defined)."""
    rng = np.random.RandomState(seed)
    grid = (np.arange(CLASSES) - CLASSES / 2) * 0.25
    rows = int(np.prod(shape))
    z = np.stack([rng.permutation(grid) for _ in range(rows)])
    return jnp.asarray(z.reshape(shape + (CLASSES,)), dtype)


def _values(m):
    return [v for _, v in m.get_name_value()]


@pytest.mark.parametrize("pad", [0, 3], ids=["full", "padded"])
@pytest.mark.parametrize("shape", [(8,), (8, 5)], ids=["2d", "3d"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(DEVICE_METRICS))
def test_update_reduced_equals_update_on_the_float32_softmax(
        name, dtype, shape, pad):
    logits = _logits(shape, jnp.dtype(dtype), seed=len(name))
    labels = np.random.RandomState(7).randint(0, CLASSES, shape)
    n_real = shape[0] - pad
    on_device, on_host = DEVICE_METRICS[name](), DEVICE_METRICS[name]()
    stats = metrics.device_form(on_device)
    reduced = jax.jit(lambda lg, lb: metrics.device_reduce(stats, lg, lb))(
        logits, jnp.asarray(labels))
    for v in reduced.values():      # per row: the logits' shape, classes off
        assert v.shape == shape
    on_device.update_reduced(
        labels[:n_real], {k: np.asarray(v)[:n_real]
                          for k, v in reduced.items()})
    on_host.update(labels[:n_real], _softmax32(logits)[:n_real])
    np.testing.assert_allclose(_values(on_device), _values(on_host),
                               rtol=2e-6)
    members = getattr(on_device, "metrics", [on_device])
    for got, want in zip(members, getattr(on_host, "metrics", [on_host])):
        assert got.num_inst == want.num_inst > 0
        np.testing.assert_allclose(got.sum_metric, want.sum_metric,
                                   rtol=2e-6)


def test_the_statistics_are_float32_whatever_the_logits():
    logits = _logits((8,), jnp.bfloat16, seed=0)
    labels = jnp.zeros((8,), jnp.int32)
    assert metrics.label_logp(logits, labels).dtype == jnp.float32
    assert metrics.argmax(logits, labels).dtype == jnp.int32
    assert metrics.topk_hit(logits, labels, k=2).dtype == jnp.bool_


def test_a_composite_asks_for_the_union_of_its_members_statistics():
    m = metrics.create(["acc", "ce", "perplexity",
                        metrics.TopKAccuracy(5)])
    assert sorted(metrics.device_form(m)) == \
        ["argmax", "label_logp", "top5_hit"]


@pytest.mark.parametrize("name", sorted(HOST_METRICS))
def test_metrics_without_a_device_form_say_so(name):
    assert metrics.device_form(HOST_METRICS[name]()) is None


class PlainCE(metrics.EvalMetric):
    """A user's metric that declares no device form."""

    def __init__(self):
        super().__init__("plain-ce")

    def update(self, labels, preds):
        labels = np.asarray(labels).astype(int).reshape(-1)
        p = np.asarray(preds).reshape(labels.size, -1)
        self.sum_metric += float(
            -np.log(p[np.arange(labels.size), labels]).sum())
        self.num_inst += labels.size


class HalfAccuracy(metrics.Accuracy):
    """Overrides ``update`` below the class that declared the device form:
    the inherited ``update_reduced`` no longer stands in for it."""

    def update(self, labels, preds):
        super().update(labels, preds)
        self.sum_metric -= 0.5 * np.asarray(labels).size


class MeanTopLogit(metrics.EvalMetric):
    """A user's metric WITH a device form of its own."""

    def __init__(self):
        super().__init__("top-logit")

    def update(self, labels, preds):
        raise AssertionError("the device form should have been used")

    def device_stats(self):
        return {"top_prob": lambda logits, labels: jnp.max(
            jax.nn.softmax(logits.astype(jnp.float32), axis=-1), axis=-1)}

    def update_reduced(self, labels, reduced):
        self.sum_metric += float(reduced["top_prob"].sum())
        self.num_inst += reduced["top_prob"].size


def test_a_subclass_that_overrides_update_alone_keeps_the_host_path():
    assert metrics.device_form(PlainCE()) is None
    assert metrics.device_form(HalfAccuracy()) is None
    assert sorted(metrics.device_form(MeanTopLogit())) == ["top_prob"]


# ---------------------------------------------------------------------------
# through Module.fit
# ---------------------------------------------------------------------------

def _xy(n=40, seed=0):
    x = np.random.RandomState(seed).normal(size=(n, 6)).astype(np.float32)
    y = np.random.RandomState(seed + 1).randint(0, 3, n).astype(np.int32)
    return x, y


def _module(**kw):
    return Module(models.create("mlp", num_classes=3, hidden=(16,)),
                  optimizer="sgd", optimizer_params={"learning_rate": 0.1},
                  seed=3, **kw)


def _fit(metric, n=40, batch=8, **kw):
    x, y = _xy(n)
    mod = _module(**kw)
    got = mod.fit(data.NDArrayIter(x, y, batch_size=batch),
                  eval_metric=metric, num_epoch=1)
    return mod, dict(got.get_name_value())


def _custom_acc():
    return metrics.CustomMetric(
        lambda lb, p: float((p.argmax(-1) == lb).mean()), name="acc")


@pytest.mark.parametrize("make,twin,key", [
    (_custom_acc, "acc", "accuracy"),
    (PlainCE, "ce", "cross-entropy"),
    (HalfAccuracy, None, None),
], ids=["CustomMetric", "user_subclass", "overridden_update"])
def test_fit_without_a_device_form_reports_as_before_on_the_host_path(
        make, twin, key, caplog):
    with caplog.at_level(logging.INFO, logger="dt_tpu"):
        mod, got = _fit(make())
    assert mod.metric_flushes == {"device": 0, "host": 5}
    assert mod._metric_spec is None
    said = [r for r in caplog.records if "no device form" in r.getMessage()]
    assert len(said) == 1 and repr(make().name) in said[0].getMessage()
    (value,) = got.values()
    if twin is None:
        # what the override computes from probabilities: never the parent's
        _, plain = _fit("acc")
        assert value == pytest.approx(plain["accuracy"] - 0.5)
    else:
        # the built-in twin goes the other way round and agrees
        mod2, want = _fit(twin)
        assert mod2.metric_flushes == {"device": 5, "host": 0}
        assert value == pytest.approx(want[key], rel=1e-5)


def test_a_user_metric_with_its_own_device_form_takes_the_device_path():
    mod, got = _fit(MeanTopLogit())
    assert mod.metric_flushes == {"device": 5, "host": 0}
    assert mod._metric_spec == ("top_prob",)
    assert 1 / 3 < got["top-logit"] <= 1.0


def test_the_fallback_is_logged_once_a_module_and_counted_per_fit_call(
        caplog):
    x, y = _xy()
    mod = _module()
    with caplog.at_level(logging.INFO, logger="dt_tpu"):
        for _ in range(2):
            mod.fit(data.NDArrayIter(x, y, batch_size=8),
                    eval_metric=_custom_acc(), num_epoch=2)
            assert mod.metric_flushes == {"device": 0, "host": 10}
    assert sum("no device form" in r.getMessage()
               for r in caplog.records) == 1
    mod.fit(data.NDArrayIter(x, y, batch_size=8), eval_metric="acc")
    assert mod.metric_flushes == {"device": 5, "host": 0}


def test_the_paths_are_gauges_in_the_metrics_plane():
    obs_metrics.registry().clear()
    obs_metrics.set_enabled(True)
    try:
        _fit(["acc", "ce"])
        snap = obs_metrics.registry().snapshot()
        gauges = {g[0]: g[2] for g in snap["gauges"]}
        assert gauges["fit.metric_device_steps"] == 5
        assert gauges["fit.metric_host_steps"] == 0
        _fit(_custom_acc())
        snap = obs_metrics.registry().snapshot()
        gauges = {g[0]: g[2] for g in snap["gauges"]}
        assert gauges["fit.metric_device_steps"] == 0
        assert gauges["fit.metric_host_steps"] == 5
    finally:
        obs_metrics.set_enabled(None)
        obs_metrics.registry().clear()
        obs_trace.tracer().reset_counters()
        obs_trace.tracer().drain()


def test_both_paths_report_the_same_value_for_bfloat16_logits():
    """The repair to the host path: its softmax is taken in float32."""
    logits = np.asarray(_logits((8,), jnp.bfloat16, seed=4))
    labels = np.arange(8) % CLASSES
    probs = module_lib._softmax_np(logits)
    assert probs.dtype == np.float32
    np.testing.assert_allclose(probs, _softmax32(logits), rtol=1e-6)
    assert logits.dtype == jnp.bfloat16     # the caller's array untouched
    on_host, on_device = metrics.CrossEntropy(), metrics.CrossEntropy()
    Module._update_metric(on_host, labels, 8, jnp.asarray(logits))
    Module._update_metric(on_device, labels, 8, metrics.device_reduce(
        on_device.device_stats(), jnp.asarray(logits), jnp.asarray(labels)))
    assert on_host.get()[1] == pytest.approx(on_device.get()[1], rel=2e-6)


def test_padded_last_batch_is_cut_on_the_host():
    """44 rows in batches of 8: the last batch carries 4 pad rows."""
    mod, got = _fit(["acc", "ce"], n=44)
    mod2, want = _fit(metrics.CompositeEvalMetric(
        [_custom_acc(), PlainCE()]), n=44)
    assert mod.metric_flushes["device"] == mod2.metric_flushes["host"] == 6
    assert got["cross-entropy"] == pytest.approx(want["plain-ce"], rel=1e-5)
    # 44 real rows were counted, not 48
    x, y = _xy(44)
    m = metrics.CrossEntropy()
    mod._update_metric(
        m, y[40:48], 4, mod._reduce_for(m)(
            mod._eval_step(mod.state, mod._place(np.resize(x[40:], (8, 6)))),
            mod._place(np.resize(y[40:], 8))))
    assert m.num_inst == 4


# ---------------------------------------------------------------------------
# the compiled steps: keyed by the statistics, no class axis among the outputs
# ---------------------------------------------------------------------------

def test_four_fit_calls_with_one_metric_compile_train_step_once():
    x, y = _xy()
    mod = _module()
    steps = []
    for _ in range(4):
        mod.fit(data.NDArrayIter(x, y, batch_size=8), eval_metric="ce",
                num_epoch=1)
        steps.append(mod._train_step)
    assert all(s is steps[0] for s in steps)
    assert steps[0]._cache_size() == 1
    # perplexity reads the same statistic: the same program serves it
    mod.fit(data.NDArrayIter(x, y, batch_size=8), eval_metric="perplexity")
    assert mod._train_step is steps[0] and steps[0]._cache_size() == 1
    # another statistic: rebuilt once, and compiled once
    mod.fit(data.NDArrayIter(x, y, batch_size=8), eval_metric="acc")
    mod.fit(data.NDArrayIter(x, y, batch_size=8), eval_metric="acc")
    assert mod._train_step is not steps[0]
    assert mod._train_step._cache_size() == 1


def test_a_change_of_metric_is_named_in_the_recompile_ledger():
    obs_device._reset_for_tests()
    obs_device.set_enabled(True)
    try:
        x, y = _xy()
        mod = _module()
        for metric in ("ce", "ce", "acc"):
            mod.fit(data.NDArrayIter(x, y, batch_size=8),
                    eval_metric=metric)
        s = obs_device.summary()
        assert s["by_what"]["train_step"]["builds"] == 2
        log = [r for r in s["recompile_log"] if r["what"] == "train_step"]
        assert [r["changed"] for r in log] == [["metric"]]
    finally:
        obs_device.set_enabled(None)
        obs_device._reset_for_tests()
        obs_trace.tracer().reset_counters()
        obs_trace.tracer().drain()


def _step_args(mod, batch=8):
    x, y = _xy(batch)
    return (mod.state, mod._place(x), mod._place(y), jax.random.PRNGKey(0))


@pytest.mark.parametrize("accum", [1, 2])
@pytest.mark.parametrize("step", ["_train_step", "_grad_step"])
def test_no_output_of_the_device_paths_step_has_the_class_axis(step, accum):
    mod = _module(grad_accum=accum)
    mod.init_params(_xy(8)[0])
    mod._use_metric(metrics.create(["acc", "ce", metrics.TopKAccuracy(2)]))
    out = jax.eval_shape(getattr(mod, step), *_step_args(mod))
    metric_out = out[2] if step == "_train_step" else out[3]
    assert sorted(metric_out) == ["argmax", "label_logp", "top2_hit"]
    assert all(v.shape == (8,) for v in metric_out.values())
    # nothing the step returns beside the state itself is batch x classes
    rest = out[1:] if step == "_train_step" else out[2:]
    assert not [a.shape for a in jax.tree_util.tree_leaves(rest)
                if a.shape[-1:] == (3,) and a.shape != (3,)]
    # and the host path's step still returns the logits
    mod._use_metric(_custom_acc())
    out = jax.eval_shape(getattr(mod, step), *_step_args(mod))
    logits = out[2] if step == "_train_step" else out[3]
    assert logits.shape == (8, 3)


def test_the_statistics_come_back_sharded_along_the_batch_like_the_logits():
    mod = _module()
    mod.init_params(_xy(8)[0])
    mod._use_metric(metrics.create("ce"))
    assert mod.mesh.shape["data"] == 8      # the suite's CPU mesh
    _, _, out = mod._train_step(*_step_args(mod))
    rows = sorted(s.index[0].start for s in
                  out["label_logp"].addressable_shards)
    assert rows == list(range(8))           # one row a device


def test_grad_accum_and_the_host_sync_grad_step_report_the_plain_steps_metric():
    _, plain = _fit(["acc", "ce"])
    mod, accum = _fit(["acc", "ce"], grad_accum=2)
    assert mod.metric_flushes == {"device": 5, "host": 0}
    assert accum["accuracy"] == plain["accuracy"]
    assert accum["cross-entropy"] == pytest.approx(plain["cross-entropy"],
                                                   rel=1e-5)
    # the host-sync mode's first phase returns the same statistics for the
    # same state and batch as the one-program step
    mod = _module()
    mod.init_params(_xy(8)[0])
    mod._use_metric(metrics.create(["acc", "ce"]))
    args = _step_args(mod)
    _, _, from_grad = mod._grad_step(*args)[1:]
    _, _, from_train = mod._train_step(*args)
    for k in ("argmax", "label_logp"):
        np.testing.assert_array_equal(np.asarray(from_grad[k]),
                                      np.asarray(from_train[k]))
    a, b = metrics.create(["acc", "ce"]), metrics.create(["acc", "ce"])
    y = _xy(8)[1]
    assert Module._update_metric(a, y, 8, from_grad) == "device"
    assert Module._update_metric(b, y, 8, from_train) == "device"
    assert _values(a) == _values(b)


def test_score_takes_the_same_reduction_and_predict_keeps_the_logits():
    x, y = _xy(44)
    mod, _ = _fit("acc", n=44)
    feed = data.NDArrayIter(x, y, batch_size=8)
    got = dict(mod.score(feed, ["acc", "ce", "perplexity"]))
    want = dict(mod.score(feed, metrics.CompositeEvalMetric(
        [_custom_acc(), PlainCE()])))
    assert got["cross-entropy"] == pytest.approx(want["plain-ce"], rel=1e-5)
    assert got["perplexity"] == pytest.approx(np.exp(want["plain-ce"]),
                                              rel=1e-5)
    logits = mod.predict(x[:8])
    assert logits.shape == (8, 3)
    assert got["accuracy"] == pytest.approx(
        float((mod.predict(np.resize(x, (48, 6))).argmax(-1)[:44]
               == y).mean()))
    # one jitted reduction a set of statistics, kept between calls
    assert sorted(mod._score_reduce) == [("argmax", "label_logp")]


def test_a_sequence_models_3d_logits_go_the_device_path():
    """batch x sequence x classes, as a language model's step returns."""
    model = models.TransformerLM(vocab_size=17, embed_dim=16, num_layers=1,
                                 num_heads=2, max_len=8)
    toks = np.random.RandomState(0).randint(0, 17, (16, 8)).astype(np.int32)
    feed = data.NDArrayIter(toks, np.roll(toks, -1, axis=1), batch_size=8)
    mod = Module(model, optimizer="sgd",
                 optimizer_params={"learning_rate": 0.01}, seed=0)
    per_step = []
    mod.fit(feed, eval_metric="ce", num_epoch=1, batch_end_callback=lambda p:
            per_step.append(p.eval_metric.get_name_value()[0][1]))
    assert mod.metric_flushes == {"device": 2, "host": 0}
    out = jax.eval_shape(mod._train_step, mod.state, mod._place(toks[:8]),
                         mod._place(toks[:8]), jax.random.PRNGKey(0))[2]
    assert {k: v.shape for k, v in out.items()} == {"label_logp": (8, 8)}
    # near log(17) at the start, as the host path reads it
    mod2 = Module(model, optimizer="sgd",
                  optimizer_params={"learning_rate": 0.01}, seed=0)
    host = []
    mod2.fit(feed, eval_metric=PlainCE(), num_epoch=1,
             batch_end_callback=lambda p:
             host.append(p.eval_metric.get_name_value()[0][1]))
    np.testing.assert_allclose(per_step, host, rtol=1e-5)
    assert abs(per_step[0] - np.log(17)) < 0.5
