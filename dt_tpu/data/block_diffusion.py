"""The noising transform of training by diffusion over blocks (BD3-LM, SDAR):
what a user puts between a token iterator and ``Module.fit`` (the
reference's iterators wrap one another the same way: ``mx.io.ResizeIter``,
``python/mxnet/io/io.py:283``; it has no transform that draws noise).

A sequence ``x0`` of ``L`` tokens is cut into blocks of ``block_length``;
each block ``b`` draws a noise level ``t_b`` uniform in ``[t_min, 1]`` and
each of its tokens is replaced by ``mask_id`` with probability ``t_b``,
giving ``xt``.  The model (``models.RoutedLM(block_length=...)``) is handed
``[xt ; x0]`` and predicts ``x0`` at the masked positions; the loss
(``ops.losses.weighted_masked_cross_entropy``) weighs a masked position of
block ``b`` by ``1 / t_b``.

**How targets and weights travel.**  ``DataBatch.label`` is one float32
array ``(B, L, 2)``: ``[..., 0]`` the target id (``x0``; ids are exact in
float32 below 2^24) and ``[..., 1]`` the weight, ``1 / t_b`` where the
position was masked and 0 where it was not.  One array, so that ``fit``'s
placement, padding cut and batch sharding treat it as any label; the loss
and the metric's device form (``training.metrics.WeightedCrossEntropy``)
split it.
"""

from __future__ import annotations

import numpy as np

from dt_tpu.data.io import DataBatch, DataIter


def block_diffusion_noise(tokens: np.ndarray, block_length: int,
                          mask_id: int, rng: np.random.Generator,
                          t_min: float = 1e-3):
    """``tokens`` (B, L) int -> (data (B, 2 L) int32 ``[xt ; x0]``, label
    (B, L, 2) float32 ``[target, weight]``), drawn from ``rng``."""
    tokens = np.asarray(tokens)
    b, length = tokens.shape
    if length % block_length:
        raise ValueError(f"{length} tokens are not whole blocks of "
                         f"{block_length}")
    if mask_id >= 1 << 24:
        raise ValueError("ids of 2^24 and more are not exact in float32")
    t = rng.uniform(t_min, 1.0, (b, length // block_length))
    t = np.repeat(t, block_length, axis=1)                     # (B, L)
    masked = rng.random((b, length)) < t
    xt = np.where(masked, mask_id, tokens)
    data = np.concatenate([xt, tokens], axis=1).astype(np.int32)
    label = np.stack([tokens.astype(np.float32),
                      np.where(masked, 1.0 / t, 0.0).astype(np.float32)],
                     axis=-1)
    return data, label


class BlockDiffusionIter(DataIter):
    """Wraps an iterator whose batches' ``data`` are tokens (B, L): each
    batch comes out noised anew (``block_diffusion_noise``), its labels
    replaced.  The draws follow ``seed`` and the batches' order."""

    def __init__(self, tokens_iter: DataIter, block_length: int,
                 mask_id: int, seed: int = 0, t_min: float = 1e-3):
        super().__init__(tokens_iter.batch_size)
        self._inner = tokens_iter
        self._args = (block_length, mask_id)
        self._t_min = t_min
        self._rng = np.random.default_rng(seed)

    def reset(self) -> None:
        self._inner.reset()

    def next(self) -> DataBatch:
        batch = self._inner.next()
        data, label = block_diffusion_noise(batch.data, *self._args,
                                            self._rng, self._t_min)
        return DataBatch(data, label, batch.pad, batch.bucket_key)

    @property
    def steps_per_epoch(self):
        return self._inner.steps_per_epoch
