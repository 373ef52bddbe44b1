"""A decoder of rotary grouped-query attention and routed experts, trained by
diffusion over blocks.

Beyond the reference's RNN ceiling (the cuDNN fused LSTM,
``src/operator/cudnn_rnn-inl.h:1``; SURVEY.md §5.7) and beside ``HybridLM``
(``hybrid_lm.py``, whose ``RMSNorm`` this reuses): what the sparse-expert
decoders of 2025 share and that one lacks.  Rotary
positions (``rope``), a learned RMSNorm on each head's query and key before
them, a ``head_dim`` that is not ``embed_dim / num_heads``, an expert layer
(``parallel/moe.py`` ``RoutedExperts``) where the gated feed-forward stands,
an untied head::

    a = rms(x) ;  q = rms_h(a Wq) , k = rms_h(a Wk) , v = a Wv
    q, k = rope(q, pos), rope(k, pos)
    h = x + softmax(q k^T / sqrt(head_dim) + M) v Wo
    x' = h + experts(rms(h))
    logits = rms(x_last) Whead                          (float32)

**Training by diffusion over blocks** (BD3-LM, SDAR): the model is handed ``[xt ; x0]``, ``2 L`` positions a sequence: ``x0`` the
``L`` tokens of data, ``xt`` the same with each block's tokens replaced by
the mask id at that block's noise level (``data.block_diffusion_noise``
makes both, with the targets and weights).  Both halves sit at rotary
positions ``0 .. L-1`` and ``M`` is ``BlockDiffusionMask(L, block_length)``:
a noisy query sees its own block's noisy keys and the clean keys of the
blocks before; a clean query the clean keys up to its own block.  The
logits are of the noisy half only, ``(B, L, V)``; the loss is
``ops.losses.weighted_masked_cross_entropy``.  (Generation denoises one block
at a time over the clean blocks before it: the serving path's, not here.)

Module names and ``jax.named_scope``s tell the parts apart in an operation's
scope path: ``block3/attn/q_proj``, ``block3/attn/rope``,
``block3/moe/route`` (``dispatch``, ``experts``, ``combine``), ``embed``,
``lm_head``.

With ``remat`` each block is rematerialised: it keeps its input and the
values named in ``SAVED`` (the flash kernel's output among them, so the
kernel runs once a layer) and computes the rest again in the backward pass.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as linen
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dt_tpu.models.hybrid_lm import RMSNorm
from dt_tpu.ops.pallas.attention import (BlockDiffusionMask, DEFAULT_BLOCK,
                                         NEG_INF, flash_attention)
from dt_tpu.parallel.moe import RoutedExperts

F32 = jnp.float32


def rope(x, positions, theta: float):
    """Rotary positions on ``x`` (B, S, H, D) at ``positions`` (S,): the
    pair ``(x_i, x_{i + D/2})`` turned by ``pos * theta^(-2i/D)``, in
    float32."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = positions.astype(F32)[:, None] * freq[None, :]      # (S, D/2)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    v = x.astype(F32)
    a, b = v[..., :half], v[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


class RotaryAttention(linen.Module):
    """Grouped-query attention with a learned RMSNorm on each head's query
    and key, then rotary positions, under ``mask``: a
    ``BlockDiffusionMask`` over ``[noisy ; clean]``, both halves at
    positions ``0 .. half-1``."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    mask: BlockDiffusionMask
    rope_theta: float = 1e6
    eps: float = 1e-6
    attention: Optional[str] = "flash"   # 'flash' (Pallas) | None (plain)
    dtype: Any = F32

    @linen.compact
    def __call__(self, x):
        b, s, d = x.shape
        h, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        dense = lambda n, name: linen.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        q, k, v = checkpoint_name(
            (dense(h * hd, "q_proj")(x), dense(kv * hd, "k_proj")(x),
             dense(kv * hd, "v_proj")(x)), "attn_qkv")
        q = q.reshape(b, s, h, hd)
        k, v = k.reshape(b, s, kv, hd), v.reshape(b, s, kv, hd)
        q = RMSNorm(self.eps, self.dtype, name="q_norm")(q)
        k = RMSNorm(self.eps, self.dtype, name="k_norm")(k)
        mask = self.mask
        with jax.named_scope("rope"):
            pos = jnp.arange(s) % mask.half
            q, k = rope(q, pos, self.rope_theta), rope(k, pos,
                                                       self.rope_theta)
        # the kernel takes one head count: each key-value head is repeated
        # for the query heads it serves (its gradient sums over them)
        k, v = (jnp.repeat(t, h // kv, axis=2) for t in (k, v))
        if self.attention == "flash":
            out = self._flash(q, k, v)
        else:
            out = self._plain(q, k, v)
        return checkpoint_name(
            dense(d, "o_proj")(out.reshape(b, s, h * hd)), "attn_out")

    def _flash(self, q, k, v):
        s, mask = q.shape[1], self.mask
        # each half padded to the tile: a padded key lies in a block after
        # every real query's, so the rule hides it (half is whole blocks)
        pad = (-mask.half) % DEFAULT_BLOCK
        if pad:
            halves = lambda t: jnp.pad(  # noqa: E731
                t.reshape((t.shape[0], 2, mask.half) + t.shape[2:]),
                ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))).reshape(
                    (t.shape[0], 2 * (mask.half + pad)) + t.shape[2:])
            q, k, v = halves(q), halves(k), halves(v)
        out = flash_attention(
            q, k, v, mask=BlockDiffusionMask(mask.half + pad, mask.block))
        if pad:
            out = out.reshape((out.shape[0], 2, mask.half + pad)
                              + out.shape[2:])[:, :, :mask.half].reshape(
                                  (out.shape[0], s) + out.shape[2:])
        return out

    def _plain(self, q, k, v):
        """A dense masked softmax in float32: the kernels' oracle."""
        s = q.shape[1]
        pos = jnp.arange(s)
        allowed = self.mask.allowed(pos[:, None], pos[None, :])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(F32),
                            k.astype(F32)) * self.head_dim ** -0.5
        probs = jax.nn.softmax(jnp.where(allowed, scores, NEG_INF), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs,
                          v.astype(F32)).astype(q.dtype)


#: what a rematerialised ``RoutedBlock`` keeps from its forward pass, by
#: ``checkpoint_name``; the backward pass computes the rest again from the
#: block's input.  Bytes a layer, for T positions (B x 2 L) of width d, H
#: heads of D, k experts a token and a buffer of R rows of expert width I,
#: ``held`` experts, in the compute dtype of c bytes:
#:   flash_out   T x H x D x c      the flash kernel's output
#:   flash_lse   T x H x 4          its log-sum-exp, float32
#:   attn_out    T x d x c          o_proj's output
#:   moe_route   T x k x 4 + 2 x R x 4 + held x 4   weights; order and the
#:               token each row holds; sizes (and 2 x R x 4 more: the
#:               indices jax derives from those two for the two gathers)
#:   moe_up      R x I x 4          float32, as ragged_dot returns it
#: Named and not kept: moe_gate (as moe_up: the pair fits the chip with
#: under half a gigabyte to spare) and attn_qkv (T x (H + 2 KV) x D x c,
#: the three projections' outputs: fewest milliseconds a gigabyte).
#: PERF.md section 6, PR 35, has each name's measured milliseconds and
#: bytes, and what the chip has room for.
SAVED = ("flash_out", "flash_lse", "attn_out", "moe_route", "moe_up")


class RoutedBlock(linen.Module):
    """One layer: attention, then the routed experts, each on the RMSNorm
    of the stream and added back."""
    attn: Any                 # kwargs of RotaryAttention
    moe: Any                  # kwargs of RoutedExperts
    eps: float = 1e-6
    dtype: Any = F32

    @linen.compact
    def __call__(self, x):
        h = RMSNorm(self.eps, self.dtype, name="input_norm")(x)
        h = RotaryAttention(eps=self.eps, dtype=self.dtype, name="attn",
                            **dict(self.attn))(h)
        x = x + h.astype(x.dtype)
        h = RMSNorm(self.eps, self.dtype, name="post_norm")(x)
        h = RoutedExperts(dtype=self.dtype, name="moe", **dict(self.moe))(h)
        return x + h.astype(x.dtype)


class RoutedLM(linen.Module):
    """``tokens`` ``[xt ; x0]`` (B, 2 L) -> float32 logits (B, L, V) of the
    noisy half, under the mask of blocks of ``block_length``.  The defaults
    are a small model; a published one passes its own ``config.json``'s
    numbers (``benchmark/sdar_drivers.py`` does).  ``held_experts`` and
    ``buffer_rows`` are ``RoutedExperts``' ``held`` and ``buffer_rows``."""
    vocab_size: int = 32000
    embed_dim: int = 256
    num_layers: int = 2
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 32
    rope_theta: float = 1e6
    num_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate: int = 128
    held_experts: Optional[tuple] = None
    buffer_rows: Optional[int] = None
    aux_loss_coef: float = 0.001
    block_length: int = 4
    attention: Optional[str] = "flash"
    rms_norm_eps: float = 1e-6
    dtype: Any = F32
    # per-block rematerialisation: a block keeps its input and the values
    # named in SAVED, and the backward pass computes the rest again
    remat: bool = False
    saved_names = SAVED     # no field: the policy's list, and the gauge's

    @linen.compact
    def __call__(self, tokens, training: bool = True):
        b, s = tokens.shape
        if s % 2:
            raise ValueError(f"[xt ; x0] has an even length, not {s}")
        mask = BlockDiffusionMask(s // 2, self.block_length)
        attn = dict(num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                    head_dim=self.head_dim, rope_theta=self.rope_theta,
                    mask=mask, attention=self.attention)
        moe = dict(num_experts=self.num_experts,
                   top_k=self.num_experts_per_tok,
                   intermediate=self.moe_intermediate,
                   held=self.held_experts, buffer_rows=self.buffer_rows,
                   aux_weight=self.aux_loss_coef)
        init = linen.initializers.normal(0.02)
        table = self.param("embedding", init,
                           (self.vocab_size, self.embed_dim), F32)
        with jax.named_scope("embed"):
            x = jnp.take(table, tokens, axis=0).astype(self.dtype)
        block_cls = linen.remat(
            RoutedBlock, policy=jax.checkpoint_policies.save_only_these_names(
                *self.saved_names)) if self.remat else RoutedBlock
        for i in range(self.num_layers):
            x = block_cls(tuple(sorted(attn.items())),
                          tuple(sorted(moe.items())), self.rms_norm_eps,
                          self.dtype, name=f"block{i}")(x)
        x = x[:, :mask.half]            # the head over the noisy half only
        x = RMSNorm(self.rms_norm_eps, self.dtype, name="final_norm")(x)
        head = self.param("lm_head", init,
                          (self.vocab_size, self.embed_dim), F32)
        with jax.named_scope("lm_head"):
            return jnp.einsum("bsd,vd->bsv", x, head.astype(self.dtype),
                              preferred_element_type=F32)
