"""2-bit gradient compression with error-feedback residual.

Reference: ``src/kvstore/gradient_compression.{h,cc,cu}`` — workers quantize
``grad + residual`` to 2-bit codes {0, +threshold, -threshold}, keep the
quantization error as the next step's residual, servers dequantize and merge
(``kvstore_dist_server.h:606-673``).  16 codes pack into one uint32, a 16x
wire reduction for DCN-crossing gradients.

Two implementations with identical semantics:
- jnp (jit-able, TPU) — for in-graph compression before a DCN collective;
- numpy — for the host-sync data plane (client packs, scheduler unpacks).

Code values: 0 -> 0.0, 1 -> +threshold, 2 -> -threshold (code 3 unused).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

CODES_PER_WORD = 16  # 2 bits each in a uint32


def _padded_words(n: int) -> int:
    return -(-n // CODES_PER_WORD)


# ---------------------------------------------------------------------------
# jnp path (jit-able)
# ---------------------------------------------------------------------------


def quantize_2bit(grad: jax.Array, residual: jax.Array,
                  threshold: float = 0.5) -> Tuple[jax.Array, jax.Array]:
    """Quantize ``grad + residual`` -> (packed uint32 words, new residual).

    Deterministic thresholding like the reference's 2-bit kernel
    (``gradient_compression.cc`` quantize_2bit): >= +t -> +t, <= -t -> -t,
    else 0; residual keeps the difference (error feedback).
    """
    flat = (grad + residual).ravel()
    n = flat.shape[0]
    codes = jnp.where(flat >= threshold, jnp.uint32(1),
                      jnp.where(flat <= -threshold, jnp.uint32(2),
                                jnp.uint32(0)))
    decoded = jnp.where(codes == 1, threshold,
                        jnp.where(codes == 2, -threshold, 0.0))
    new_residual = (flat - decoded).reshape(grad.shape).astype(residual.dtype)
    pad = _padded_words(n) * CODES_PER_WORD - n
    codes = jnp.pad(codes, (0, pad)).reshape(-1, CODES_PER_WORD)
    shifts = jnp.arange(CODES_PER_WORD, dtype=jnp.uint32) * 2
    # codes occupy disjoint bit ranges, so sum == bitwise-or
    packed = jnp.sum(codes << shifts[None, :], axis=1, dtype=jnp.uint32)
    return packed, new_residual


def dequantize_2bit(packed: jax.Array, n: int, threshold: float = 0.5,
                    dtype=jnp.float32) -> jax.Array:
    """Unpack uint32 words -> flat array of n values in {0, ±threshold}."""
    shifts = jnp.arange(CODES_PER_WORD, dtype=jnp.uint32) * 2
    codes = (packed[:, None] >> shifts[None, :]) & jnp.uint32(3)
    vals = jnp.where(codes == 1, threshold,
                     jnp.where(codes == 2, -threshold, 0.0))
    return vals.ravel()[:n].astype(dtype)


# ---------------------------------------------------------------------------
# numpy path (host data plane)
# ---------------------------------------------------------------------------


def np_quantize_2bit(grad: np.ndarray, residual: np.ndarray,
                     threshold: float = 0.5) -> Tuple[np.ndarray, np.ndarray]:
    flat = (grad + residual).ravel()
    n = flat.shape[0]
    codes = np.zeros(n, np.uint32)
    codes[flat >= threshold] = 1
    codes[flat <= -threshold] = 2
    decoded = np.zeros(n, np.float32)
    decoded[codes == 1] = threshold
    decoded[codes == 2] = -threshold
    new_residual = (flat - decoded).reshape(grad.shape).astype(residual.dtype)
    pad = _padded_words(n) * CODES_PER_WORD - n
    codes = np.pad(codes, (0, pad)).reshape(-1, CODES_PER_WORD)
    shifts = (np.arange(CODES_PER_WORD, dtype=np.uint32) * 2)
    packed = np.bitwise_or.reduce(codes << shifts[None, :], axis=1) \
        .astype(np.uint32)
    return packed, new_residual


def packed_chunks(packed: np.ndarray, n: int, per_elems: int):
    """Split a packed 2-bit stream into per-chunk (words, n_chunk) pairs
    on the ELEMENT grid — ``per_elems`` must be a multiple of
    ``CODES_PER_WORD`` so every chunk is whole uint32 words.  The
    chunked-allreduce wire path ships each pair as its own
    ``{"packed", "n", "threshold"}`` round (subkey ``key#c<i>``); the
    slices are views, so chunking copies nothing."""
    if per_elems % CODES_PER_WORD:
        raise ValueError(f"per_elems {per_elems} must be a multiple of "
                         f"{CODES_PER_WORD}")
    words_per = per_elems // CODES_PER_WORD
    out = []
    for start in range(0, n, per_elems):
        w0 = start // CODES_PER_WORD
        out.append((packed[w0:w0 + words_per], min(per_elems, n - start)))
    return out


def np_dequantize_2bit(packed: np.ndarray, n: int, threshold: float = 0.5,
                       dtype=np.float32) -> np.ndarray:
    shifts = (np.arange(CODES_PER_WORD, dtype=np.uint32) * 2)
    codes = (packed[:, None] >> shifts[None, :]) & np.uint32(3)
    vals = np.zeros(codes.shape, dtype)
    vals[codes == 1] = threshold
    vals[codes == 2] = -threshold
    return vals.ravel()[:n]


class GradientCompression:
    """Stateful wrapper holding the error-feedback residual
    (reference ``GradientCompression`` + per-key residual buffers)."""

    def __init__(self, threshold: float = 0.5):
        if threshold <= 0:
            raise ValueError("threshold must be positive")
        self.threshold = threshold
        self._residual: np.ndarray = None
        self._residual_dev = None
        self._jit_compress = None

    def compress(self, grad: np.ndarray) -> np.ndarray:
        if self._residual is None or self._residual.shape != grad.shape:
            self._residual = np.zeros_like(grad, np.float32)
        packed, self._residual = np_quantize_2bit(
            grad.astype(np.float32), self._residual, self.threshold)
        return packed

    def compress_on_device(self, grad: jax.Array) -> jax.Array:
        """In-graph quantize on the accelerator BEFORE the host fetch —
        the production entry for the host-sync plane (``Module.fit``):
        only the packed words (16x fewer bytes) cross the device-host
        boundary, and the error-feedback residual never leaves HBM.
        :func:`quantize_2bit` is the quantizer: XLA fuses threshold,
        decode, residual and pack into one pass at full lane width,
        where a Pallas kernel over the wire format's 16-code rows fills
        16 of a vector's 128 lanes (0.625x on the chip,
        ``PALLAS_TPU_r02.jsonl``)."""
        if self._residual_dev is None or \
                self._residual_dev.shape != grad.shape:
            self._residual_dev = jnp.zeros(grad.shape, jnp.float32)
            self._jit_compress = jax.jit(
                lambda g, r: quantize_2bit(g, r, self.threshold))
        packed, self._residual_dev = self._jit_compress(
            grad.astype(jnp.float32), self._residual_dev)
        return packed

    def decompress(self, packed: np.ndarray, n: int) -> np.ndarray:
        return np_dequantize_2bit(packed, n, self.threshold)
