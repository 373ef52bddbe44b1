"""Traffic for training by diffusion over blocks: a generator beside
``traffic.py``'s, named by a traffic file as
``sdar_traffic:block_diffusion_tokens``.  Plain numpy, nothing of the
program: ``tests/benchmark/test_sdar_cell.py`` holds the program's own
transform (``dt_tpu.data.block_diffusion_noise``) to the same draws."""

import numpy as np


def block_diffusion_tokens(rng, traffic, cfg):
    """One batch ``(data, labels)``: ``batch`` sequences ``x0`` of
    ``seq_len`` tokens uniform over the vocabulary rows held less the last,
    which is the mask id; each block of ``block_length`` draws a noise
    level ``t_b`` uniform in ``[t_min, 1]`` and each of its tokens is
    replaced by the mask id with probability ``t_b``, giving ``xt``.
    ``data`` is ``[xt ; x0]`` (batch, 2 seq_len) int32; ``labels`` (batch,
    seq_len, 2) float32 hold the target id and the weight, ``1 / t_b``
    where the position was masked and 0 where not."""
    b, length, block = traffic["batch"], traffic["seq_len"], \
        traffic["block_length"]
    mask_id = cfg["mask_token_id"]
    if mask_id != cfg["vocab_size"] - 1 or length % block:
        raise ValueError(f"the mask id {mask_id} is the last row held and "
                         f"{length} tokens are whole blocks of {block}")
    x0 = rng.integers(0, mask_id, (b, length))
    t = np.repeat(rng.uniform(traffic["t_min"], 1.0, (b, length // block)),
                  block, axis=1)
    masked = rng.random((b, length)) < t
    data = np.concatenate([np.where(masked, mask_id, x0), x0],
                          axis=1).astype(np.int32)
    labels = np.stack([x0.astype(np.float32),
                       np.where(masked, 1.0 / t, 0.0).astype(np.float32)],
                      axis=-1)
    return data, labels
