"""Loss ops.

Reference analogs: ``src/operator/softmax_output.cc:1`` (SoftmaxOutput — the
symbol-era classification head), ``src/operator/regression_output.cc``
(LinearRegressionOutput / LogisticRegressionOutput / MAERegressionOutput),
``src/operator/make_loss.cc``, gluon losses (``python/mxnet/gluon/loss.py``).
All return per-batch scalars (mean) unless noted.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


def softmax_cross_entropy(logits: Array, labels: Array,
                          *, smoothing: float = 0.0,
                          ignore_label: Optional[int] = None) -> Array:
    """Softmax + CE, integer labels.  Reference: SoftmaxOutput
    (``src/operator/softmax_output.cc``); ``smoothing`` matches the
    ``smooth_alpha`` attr, ``ignore_label`` the masking attr.
    """
    num_classes = logits.shape[-1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(labels, num_classes, dtype=logp.dtype)
    if smoothing > 0.0:
        onehot = onehot * (1.0 - smoothing) + smoothing / num_classes
    nll = -jnp.sum(onehot * logp, axis=-1)
    if ignore_label is not None:
        mask = (labels != ignore_label).astype(nll.dtype)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def weighted_masked_cross_entropy(logits: Array, labels: Array) -> Array:
    """The loss of training by diffusion over blocks: ``labels`` (..., 2)
    float32 hold a target id and a weight for every row of ``logits``
    (..., V) (``data.block_diffusion_noise``: ``1 / t_b`` where the position
    was masked, 0 where not); the weighted ``-log p(target)`` summed and
    divided by the number of rows, masked or not."""
    z = logits.astype(jnp.float32)
    # the target's logit as a reduction over a one-hot mask, beside the
    # log-sum-exp: no float32 log-softmax of the logits is written out
    hot = jnp.arange(z.shape[-1], dtype=jnp.int32) \
        == labels[..., 0].astype(jnp.int32)[..., None]
    nll = jax.nn.logsumexp(z, axis=-1) - jnp.sum(jnp.where(hot, z, 0.0),
                                                 axis=-1)
    return jnp.sum(labels[..., 1] * nll) / nll.size


def l2_loss(pred: Array, label: Array) -> Array:
    """Reference: LinearRegressionOutput (0.5*(p-y)^2 mean)."""
    return 0.5 * jnp.mean(jnp.square(pred.astype(jnp.float32) - label))


def l1_loss(pred: Array, label: Array) -> Array:
    """Reference: MAERegressionOutput."""
    return jnp.mean(jnp.abs(pred.astype(jnp.float32) - label))


def logistic_loss(pred: Array, label: Array) -> Array:
    """Reference: LogisticRegressionOutput (sigmoid BCE)."""
    p = pred.astype(jnp.float32)
    return jnp.mean(jnp.maximum(p, 0) - p * label + jnp.log1p(jnp.exp(-jnp.abs(p))))


def huber_loss(pred: Array, label: Array, rho: float = 1.0) -> Array:
    """Reference: gluon HuberLoss."""
    d = jnp.abs(pred.astype(jnp.float32) - label)
    return jnp.mean(jnp.where(d <= rho, 0.5 * d * d / rho, d - 0.5 * rho))


def hinge_loss(pred: Array, label: Array, margin: float = 1.0) -> Array:
    """Reference: ``src/operator/svm_output.cc`` (SVMOutput, L1 hinge)."""
    return jnp.mean(jnp.maximum(0.0, margin - pred.astype(jnp.float32) * label))


def nce_loss(hidden: Array, label_embeds: Array,
             label_weight: Array) -> Array:
    """Noise-contrastive estimation / sampled-softmax loss.

    Reference ``example/nce-loss/nce.py:27-35`` (``nce_loss``): the
    hidden vector is scored against the embeddings of (1 true + K
    sampled noise) labels by dot product and trained as K+1 binary
    logistic classifications — true label target 1, noise targets 0 —
    approximating the full-vocab softmax at O(K) cost.

    ``hidden``: (B, D); ``label_embeds``: (B, K+1, D);
    ``label_weight``: (B, K+1) targets in {0, 1}.  Mean BCE-with-logits
    over all B x (K+1) pairs (the reference's LogisticRegressionOutput).
    """
    pred = jnp.sum(hidden[:, None, :].astype(jnp.float32)
                   * label_embeds.astype(jnp.float32), axis=-1)
    t = label_weight.astype(jnp.float32)
    # numerically-stable BCE with logits
    return jnp.mean(jnp.maximum(pred, 0.0) - pred * t
                    + jnp.log1p(jnp.exp(-jnp.abs(pred))))


def nce_loss_from_ids(hidden: Array, embed_table: Array, label_ids: Array,
                      label_weight: Array) -> Array:
    """`nce_loss` with the label embeddings gathered from a (V, D) table
    (the reference's shared ``embed_weight``, ``nce.py:28-31``);
    ``label_ids``: (B, K+1) int — column 0 the true label, the rest
    sampled noise."""
    return nce_loss(hidden, embed_table[label_ids], label_weight)


def kl_divergence(logp_pred: Array, p_label: Array) -> Array:
    """Reference: gluon KLDivLoss (inputs are log-probs, probs).  Like the
    reference (``python/mxnet/gluon/loss.py`` KLDivLoss: mean over all
    non-batch axes), the class axis is averaged, not summed."""
    return jnp.mean(p_label * (jnp.log(jnp.maximum(p_label, 1e-12))
                               - logp_pred))


def ctc_loss(logits: Array, logit_lengths: Array, labels: Array,
             label_lengths: Array, blank: int = 0) -> Array:
    """CTC loss via the standard log-alpha forward recursion under lax.scan.

    Reference: ``src/operator/nn/ctc_loss.cc`` (warp-ctc/cuDNN backed).
    ``logits``: (B, T, V); ``labels``: (B, L) padded with anything beyond
    ``label_lengths``.  Returns mean loss over batch.
    """
    b, t, v = logits.shape
    l = labels.shape[1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    # Extended label sequence with blanks: length 2L+1.
    ext = jnp.full((b, 2 * l + 1), blank, dtype=labels.dtype)
    ext = ext.at[:, 1::2].set(labels)
    s = 2 * l + 1
    neg_inf = -1e30
    # alpha init
    alpha0 = jnp.full((b, s), neg_inf)
    alpha0 = alpha0.at[:, 0].set(logp[:, 0, blank])
    alpha0 = alpha0.at[:, 1].set(jnp.take_along_axis(
        logp[:, 0, :], ext[:, 1:2], axis=1)[:, 0])

    same_as_prev2 = jnp.concatenate(
        [jnp.ones((b, 2), bool), ext[:, 2:] == ext[:, :-2]], axis=1)

    def step(alpha, logp_t):
        a_shift1 = jnp.concatenate([jnp.full((b, 1), neg_inf), alpha[:, :-1]], 1)
        a_shift2 = jnp.concatenate([jnp.full((b, 2), neg_inf), alpha[:, :-2]], 1)
        a_shift2 = jnp.where(same_as_prev2, neg_inf, a_shift2)
        merged = jnp.logaddexp(jnp.logaddexp(alpha, a_shift1), a_shift2)
        emit = jnp.take_along_axis(logp_t, ext, axis=1)
        return merged + emit, None

    # scan over time, masking steps beyond each sequence's length
    def masked_step(carry, inp):
        alpha, t_idx = carry
        logp_t = inp
        new_alpha, _ = step(alpha, logp_t)
        keep = (t_idx < logit_lengths)[:, None]
        alpha = jnp.where(keep, new_alpha, alpha)
        return (alpha, t_idx + 1), None

    (alpha, _), _ = jax.lax.scan(masked_step, (alpha0, jnp.ones((), jnp.int32)),
                                 jnp.swapaxes(logp, 0, 1)[1:])
    end = 2 * label_lengths  # index of last blank
    last = jnp.take_along_axis(alpha, end[:, None], axis=1)[:, 0]
    last2 = jnp.take_along_axis(alpha, jnp.maximum(end - 1, 0)[:, None], axis=1)[:, 0]
    # Empty label sequence (end==0): only the all-blank path exists.
    last2 = jnp.where(end == 0, -jnp.inf, last2)
    return jnp.mean(-jnp.logaddexp(last, last2))
