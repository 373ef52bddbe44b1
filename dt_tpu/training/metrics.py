"""Evaluation metric zoo.

Reference: ``python/mxnet/metric.py:1`` (1,424 LoC — EvalMetric base with
update/reset/get, Accuracy, TopKAccuracy, F1, MAE/MSE/RMSE, CrossEntropy,
NegativeLogLikelihood, Perplexity, CompositeEvalMetric, CustomMetric,
``metric.create``).  Updates take numpy/jax arrays; accumulation is
host-side floats exactly like the reference.

**What a step hands the host.**  A metric with a *device form* names the
per-row statistics it reads (``device_stats``: ``label_logp``, ``argmax``,
``top<k>_hit``, each with the logits' shape less the class axis) and
accumulates from them (``update_reduced``).  ``Module.fit`` and ``score``
then reduce the logits over the class axis inside the compiled program and
fetch those statistics, a few bytes a row, and the host never sees the
logits.  ``Accuracy``, ``TopKAccuracy``, ``CrossEntropy``,
``NegativeLogLikelihood``, ``Perplexity`` and a composite of such metrics
have one.  Every other metric (``F1``, ``MAE``, ``MSE``, ``RMSE``, ``Loss``,
``CustomMetric``, a user subclass that declares none) is handed the float32
softmax of the whole logits on the host, as before: batch x classes (x
sequence) values cross from the device every step, 823 MB at GPT-2's
vocabulary (``docs/metrics.md``).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np


def _np(x) -> np.ndarray:
    return np.asarray(x)


# ---------------------------------------------------------------------------
# per-row statistics: pure functions of (logits, labels), traced into the
# compiled step.  Each is a reduction over the class axis that XLA fuses with
# its float32 cast, so no float32 copy of the logits is ever written.  A
# statistic's NAME is its identity: the compiled steps are keyed by the names
# a metric asks for.
# ---------------------------------------------------------------------------

def _label_logit(z, labels):
    """The label's own logit, as a sum over a one-hot mask (a reduction like
    the others: a gather would want the float32 logits written out)."""
    classes = jnp.arange(z.shape[-1], dtype=jnp.int32)
    hot = classes == labels.astype(jnp.int32)[..., None]
    return jnp.sum(jnp.where(hot, z, 0.0), axis=-1)


def label_logp(logits, labels):
    """float32 ``log softmax(logits)[label]``; labels outside the classes
    give ``-logsumexp`` (rows a metric ignores)."""
    z = logits.astype(jnp.float32)
    return _label_logit(z, labels) - jax.nn.logsumexp(z, axis=-1)


def argmax(logits, labels):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def topk_hit(logits, labels, k):
    """Whether fewer than ``k`` classes score above the label's."""
    z = logits.astype(jnp.float32)
    above = jnp.sum(z > _label_logit(z, labels)[..., None], axis=-1)
    return above < k


def weighted_label_logp(logits, labels):
    """``labels`` (..., 2) float32 ``[target id, weight]``
    (``data.block_diffusion_noise``) -> float32 ``weight * log
    softmax(logits)[target]``."""
    return labels[..., 1] * label_logp(logits, labels[..., 0])


def device_form(metric) -> Optional[Dict[str, Callable]]:
    """``metric.device_stats()``, or None (the host path) where a subclass
    overrode ``update`` below the class whose ``update_reduced`` would stand
    in for it: the two would no longer accumulate the same thing."""
    def owner(name):
        return next(c for c in type(metric).__mro__ if name in vars(c))
    stats = metric.device_stats()
    if stats and issubclass(owner("update_reduced"), owner("update")):
        return stats
    return None


def stats_key(stats: Optional[Dict[str, Callable]]) -> Optional[Tuple[str, ...]]:
    """What compiled programs are kept by: the statistics' names (None: the
    logits themselves)."""
    return tuple(sorted(stats)) if stats else None


def device_reduce(stats: Dict[str, Callable], logits, labels):
    """What a compiled step returns in the logits' place: each statistic of
    ``stats`` by name, with the logits' shape less the class axis."""
    rows = logits.shape[:-1]
    if labels.size == math.prod(rows):
        labels = labels.reshape(rows)
    # else several numbers a row (a target and a weight): as they are
    return {name: f(logits, labels) for name, f in stats.items()}


class EvalMetric:
    """Base metric (reference ``mx.metric.EvalMetric``)."""

    def __init__(self, name: str):
        self.name = name
        self.reset()

    def reset(self):
        self.num_inst = 0
        self.sum_metric = 0.0

    def update(self, labels, preds):
        raise NotImplementedError

    def device_stats(self) -> Optional[Dict[str, Callable]]:
        """The device form: ``{name: f(logits, labels)}``, the per-row
        statistics ``update_reduced`` reads, each a pure jax function that
        returns an array with the logits' shape less the class axis.  None
        (the default): the metric is handed probabilities on the host."""
        return None

    def update_reduced(self, labels, reduced: Dict[str, np.ndarray]):
        """Accumulate from the statistics ``device_stats`` named what
        ``update(labels, softmax(logits))`` would have."""
        raise NotImplementedError

    def get(self) -> Tuple[str, float]:
        if self.num_inst == 0:
            return self.name, float("nan")
        return self.name, self.sum_metric / self.num_inst

    def get_name_value(self) -> List[Tuple[str, float]]:
        return [self.get()]


class Accuracy(EvalMetric):
    """Top-1 accuracy; preds may be logits/probs (argmax) or class ids."""

    def __init__(self, name: str = "accuracy"):
        super().__init__(name)

    def update(self, labels, preds):
        labels = _np(labels)
        preds = _np(preds)
        if preds.ndim == labels.ndim + 1:
            preds = preds.argmax(-1)
        labels = labels.reshape(-1)
        preds = preds.reshape(-1)
        self.sum_metric += float((preds == labels).sum())
        self.num_inst += labels.size

    def device_stats(self):
        return {"argmax": argmax}

    def update_reduced(self, labels, reduced):
        self.update(labels, reduced["argmax"].reshape(np.shape(labels)))


class TopKAccuracy(EvalMetric):
    """Reference: ``mx.metric.TopKAccuracy`` (top_k attr)."""

    def __init__(self, top_k: int = 5, name: Optional[str] = None):
        self.top_k = top_k
        super().__init__(name or f"top_k_accuracy_{top_k}")

    def update(self, labels, preds):
        labels = _np(labels).reshape(-1)
        preds = _np(preds).reshape(labels.size, -1)
        topk = np.argpartition(preds, -self.top_k, axis=-1)[:, -self.top_k:]
        self.sum_metric += float((topk == labels[:, None]).any(-1).sum())
        self.num_inst += labels.size

    def device_stats(self):
        return {f"top{self.top_k}_hit":
                functools.partial(topk_hit, k=self.top_k)}

    def update_reduced(self, labels, reduced):
        hit = reduced[f"top{self.top_k}_hit"]
        self.sum_metric += float(hit.sum())
        self.num_inst += hit.size


class F1(EvalMetric):
    """Binary F1 (reference ``mx.metric.F1``, average='macro' over updates)."""

    def __init__(self, name: str = "f1"):
        super().__init__(name)

    def reset(self):
        super().reset()
        self.tp = self.fp = self.fn = 0

    def update(self, labels, preds):
        labels = _np(labels).reshape(-1)
        preds = _np(preds)
        if preds.ndim > 1:
            preds = preds.argmax(-1)
        preds = preds.reshape(-1)
        self.tp += int(((preds == 1) & (labels == 1)).sum())
        self.fp += int(((preds == 1) & (labels == 0)).sum())
        self.fn += int(((preds == 0) & (labels == 1)).sum())
        precision = self.tp / max(self.tp + self.fp, 1)
        recall = self.tp / max(self.tp + self.fn, 1)
        f1 = 2 * precision * recall / max(precision + recall, 1e-12)
        self.sum_metric = f1
        self.num_inst = 1


class MAE(EvalMetric):
    def __init__(self, name: str = "mae"):
        super().__init__(name)

    def update(self, labels, preds):
        labels = _np(labels)
        preds = _np(preds).reshape(labels.shape)
        self.sum_metric += float(np.abs(labels - preds).mean() * labels.shape[0])
        self.num_inst += labels.shape[0]


class MSE(EvalMetric):
    def __init__(self, name: str = "mse"):
        super().__init__(name)

    def update(self, labels, preds):
        labels = _np(labels)
        preds = _np(preds).reshape(labels.shape)
        self.sum_metric += float(((labels - preds) ** 2).mean() * labels.shape[0])
        self.num_inst += labels.shape[0]


class RMSE(MSE):
    def __init__(self, name: str = "rmse"):
        super().__init__(name)

    def get(self):
        if self.num_inst == 0:
            return self.name, float("nan")
        return self.name, float(np.sqrt(self.sum_metric / self.num_inst))


class CrossEntropy(EvalMetric):
    """Mean -log p(label).  ``preds`` are probabilities (reference
    convention)."""

    def __init__(self, eps: float = 1e-12, name: str = "cross-entropy"):
        self.eps = eps
        super().__init__(name)

    def update(self, labels, preds):
        labels = _np(labels).astype(int).reshape(-1)
        preds = _np(preds).reshape(labels.size, -1)
        p = preds[np.arange(labels.size), labels]
        self.sum_metric += float(-np.log(np.maximum(p, self.eps)).sum())
        self.num_inst += labels.size

    def device_stats(self):
        return {"label_logp": label_logp}

    def update_reduced(self, labels, reduced):
        logp = reduced["label_logp"].reshape(-1)
        self.sum_metric += float(
            -np.maximum(logp, math.log(self.eps)).sum())
        self.num_inst += logp.size


class NegativeLogLikelihood(CrossEntropy):
    def __init__(self, eps: float = 1e-12, name: str = "nll-loss"):
        super().__init__(eps, name)


class Perplexity(CrossEntropy):
    """exp(mean CE), optional ignore_label (reference ``mx.metric.Perplexity``,
    used by the PTB LM example)."""

    def __init__(self, ignore_label: Optional[int] = None, eps: float = 1e-12,
                 name: str = "perplexity"):
        self.ignore_label = ignore_label
        super().__init__(eps, name)

    def update(self, labels, preds):
        labels = _np(labels).astype(int).reshape(-1)
        preds = _np(preds).reshape(labels.size, -1)
        if self.ignore_label is not None:
            keep = labels != self.ignore_label
            labels, preds = labels[keep], preds[keep]
        p = preds[np.arange(labels.size), labels]
        self.sum_metric += float(-np.log(np.maximum(p, self.eps)).sum())
        self.num_inst += labels.size

    def update_reduced(self, labels, reduced):
        logp = reduced["label_logp"].reshape(-1)
        if self.ignore_label is not None:
            logp = logp[_np(labels).reshape(-1) != self.ignore_label]
        super().update_reduced(None, {"label_logp": logp})

    def get(self):
        if self.num_inst == 0:
            return self.name, float("nan")
        return self.name, float(np.exp(self.sum_metric / self.num_inst))


class WeightedCrossEntropy(EvalMetric):
    """The objective of training by diffusion over blocks
    (``ops.losses.weighted_masked_cross_entropy``) as a metric: ``labels``
    (..., 2) hold a target id and a weight a row; the mean over all rows of
    ``-weight * log p(target)``.  ``preds`` are probabilities."""

    def __init__(self, eps: float = 1e-12, name: str = "weighted-ce"):
        self.eps = eps
        super().__init__(name)

    def update(self, labels, preds):
        labels = _np(labels).reshape(-1, 2)
        preds = _np(preds).reshape(labels.shape[0], -1)
        p = preds[np.arange(labels.shape[0]), labels[:, 0].astype(int)]
        self.sum_metric += float(
            -(labels[:, 1] * np.log(np.maximum(p, self.eps))).sum())
        self.num_inst += labels.shape[0]

    def device_stats(self):
        return {"weighted_label_logp": weighted_label_logp}

    def update_reduced(self, labels, reduced):
        weighted = reduced["weighted_label_logp"].reshape(-1)
        self.sum_metric += float(-weighted.sum())
        self.num_inst += weighted.size


class Loss(EvalMetric):
    """Running mean of a scalar loss (reference ``mx.metric.Loss``)."""

    def __init__(self, name: str = "loss"):
        super().__init__(name)

    def update(self, labels, preds):
        self.sum_metric += float(_np(preds).sum())
        self.num_inst += max(_np(preds).size, 1)


class CustomMetric(EvalMetric):
    """Wrap ``feval(label, pred) -> float`` (reference
    ``mx.metric.CustomMetric`` / ``np`` helper)."""

    def __init__(self, feval: Callable, name: str = "custom"):
        self._feval = feval
        super().__init__(name)

    def update(self, labels, preds):
        self.sum_metric += float(self._feval(_np(labels), _np(preds)))
        self.num_inst += 1


class CompositeEvalMetric(EvalMetric):
    """Aggregate several metrics (reference
    ``mx.metric.CompositeEvalMetric``)."""

    def __init__(self, metrics: Sequence[EvalMetric],
                 name: str = "composite"):
        self.metrics = list(metrics)
        super().__init__(name)

    def reset(self):
        for m in getattr(self, "metrics", []):
            m.reset()
        self.num_inst = 0
        self.sum_metric = 0.0

    def update(self, labels, preds):
        for m in self.metrics:
            m.update(labels, preds)
        self.num_inst = 1

    def device_stats(self):
        """The union of the members' statistics, so that ``["acc", "ce"]``
        is one pass over the logits; None if any member has none."""
        forms = [device_form(m) for m in self.metrics]
        if not forms or not all(forms):
            return None
        return {k: f for form in forms for k, f in form.items()}

    def update_reduced(self, labels, reduced):
        for m in self.metrics:
            m.update_reduced(labels, reduced)
        self.num_inst = 1

    def get(self):
        names, vals = [], []
        for m in self.metrics:
            n, v = m.get()
            names.append(n)
            vals.append(v)
        return names, vals

    def get_name_value(self):
        return [m.get() for m in self.metrics]


_REGISTRY: Dict[str, Callable[..., EvalMetric]] = {
    "acc": Accuracy,
    "accuracy": Accuracy,
    "top_k_accuracy": TopKAccuracy,
    "f1": F1,
    "mae": MAE,
    "mse": MSE,
    "rmse": RMSE,
    "ce": CrossEntropy,
    "cross-entropy": CrossEntropy,
    "nll_loss": NegativeLogLikelihood,
    "perplexity": Perplexity,
    "weighted-ce": WeightedCrossEntropy,
    "loss": Loss,
}


def create(metric: Union[str, EvalMetric, Sequence], **kwargs) -> EvalMetric:
    """``mx.metric.create`` semantics: str name, instance passthrough, or
    list -> composite."""
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, (list, tuple)):
        return CompositeEvalMetric([create(m) for m in metric])
    if callable(metric):
        return CustomMetric(metric)
    key = metric.lower()
    if key not in _REGISTRY:
        raise ValueError(f"unknown metric {metric!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[key](**kwargs)
