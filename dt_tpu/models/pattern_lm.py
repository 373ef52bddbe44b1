"""A decoder whose blocks are each one part alone, named by a letter of a
pattern string: a Mamba-2 mixer (``M``), grouped-query attention without
positions (``*``) or routed experts that work in a latent (``E``).

Beyond the reference's RNN ceiling (the cuDNN fused LSTM,
``src/operator/cudnn_rnn-inl.h:1``; SURVEY.md §5.7) and beside ``HybridLM``
(``hybrid_lm.py``: every layer a mixer *and* a gated feed-forward, two norms
and two adds) and ``RoutedLM`` (``routed_lm.py``: every layer attention or a
short convolution and then experts at the stream's width): the hybrid
decoders whose published ``hybrid_override_pattern`` spells the depth out
block by block.  Every block is one norm, one part, one add::

    x <- x + part(rms(x) * g)          RMSNorm, eps rms_norm_eps; no biases but the convolution's

and the parts are this package's own (``hybrid_lm.Mamba2Mixer``,
``hybrid_lm.GroupedQueryAttention``, ``parallel.moe.RoutedExperts``), each
told which share of its heads or experts this chip holds.  With ``a`` the
normed stream, at the sizes one published decoder of this kind has (88
blocks, 40 : 40 : 8; published sizes first, then what one chip of the 64
that share a block holds)::

    M  Mamba-2:   H = 128 heads of P = 64 (d_inner 8192), G = 8 groups, N = 128, 4 taps, chunk 128
        [z | xBC | dt] = a Win                       Win 4096 x (8192 | 8192 + 2*8*128 | 128) = 4096 x 18,560
        xBC = silu(conv4(xBC) + b)                   depthwise, causal, zero before position 0, with a bias
        [x | B | C] = xBC                            x as [T, 128, 64] ; B, C as [T, 8, 128] ; head h reads group h // 16
        D_t = softplus(dt_t + dt_bias) ;  A = -exp(A_log)                       per head, float32
        s_t = exp(D_t A) s_{t-1} + D_t x_t B_t^T ;  y_t = s_t C_t + Dskip x_t    state 64 x 128 a head
        u = y * silu(z) ;  n = u * rsqrt(mean over its group's 1,024 channels of u^2 + eps) * w      the norm comes after the gate, one group at a time
        part = n Wout                                Wout 8192 x 4096
      held: heads 0..15 and group 0: Win's columns [z 0:1024 | x 0:1024 | B_0 | C_0 | dt 0:16] (4096 x 2,320), Wout's rows 0:1024

    *  attention: q = a Wq as [T, 32, 128] ; k = a Wk, v = a Wv as [T, 2, 128] ; query head h reads key-value head h // 16
        o = softmax_causal(q k^T / sqrt(128)) v ;  part = o Wo                  no positions
      held: query heads 0..3 and key-value head 0: Wq 4096 x 512, Wk, Wv 4096 x 128, Wo 512 x 4096

    E  experts in a latent: E = 512, k = 22, latent 1,024, width 2,688, shared 5,376
        s = sigmoid_f32(a Wr)                        Wr 4096 x 512, float32, product at highest precision
        S = the 22 of largest s + b                  b: a selection bias, the selection only ; no groups of experts
        w_e = 5 * s_e / (sum_S s + 1e-20)            renormalised, scaled by 5, weights from the unbiased s
        l = a Wlin                                   Wlin 4096 x 1024
        r = sum_{e in S, e held} w_e * (relu(l Wup_e)^2) Wdown_e                Wup_e 1024 x 2688, Wdown_e 2688 x 1024 ; not gated
        part = r Wlout + (relu(a Wsu)^2) Wsd         Wlout 1024 x 4096 ; Wsu 4096 x 5376, Wsd 5376 x 4096
      held: experts 0..7 of 512 ; Wr, b, Wlin, Wlout and the shared expert whole

    logits = (rms(x) * g) Whead over the vocabulary rows held (untied, float32) ; loss: next-token cross-entropy over them

What the absent heads and experts would have added is left out, and the
partial result goes on to the next block: on one chip a block runs without
its all-reduce and its exchange, and no code stands in for the other chips.
The selection bias is a variable of the ``batch_stats`` collection that a
training step moves against the load (``RoutedExperts``); the layer's
load-balancing term rides ``aux_loss`` and its counts ``counters``:
``training.Module`` carries all three, as for ``RoutedLM``.

Module names tell the parts apart in an operation's scope path:
``block0/mamba/in_proj`` (``conv1d``, ``ssd_scan``, ``gated_norm``,
``out_proj``), ``block9/attn/q_proj``, ``block1/moe/route`` (``latent``,
``dispatch``, ``experts``, ``combine``, ``shared``), ``block3/norm``,
``embed``, ``lm_head``: the names the other two decoders' parts carry, so a
metric that reads ``/mamba/``, ``/attn/`` or ``/moe/`` reads this one too.

With ``remat`` each block is rematerialised: it keeps its input and the
values named in ``SAVED`` and computes the rest again in the backward pass.
A block of one part never computes its last product again (nothing in the
backward pass reads it), so no name stands on one.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as linen
import jax
import jax.numpy as jnp

from dt_tpu.models.hybrid_lm import (GroupedQueryAttention, Mamba2Mixer,
                                     RMSNorm)
from dt_tpu.parallel.moe import RoutedExperts

F32 = jnp.float32

#: the letters of a pattern -> the part's module name in a block, and its
#: module
PARTS = {"M": ("mamba", Mamba2Mixer), "*": ("attn", GroupedQueryAttention),
         "E": ("moe", RoutedExperts)}

#: what a rematerialised ``PartBlock`` keeps from its forward pass, by
#: ``checkpoint_name`` (``hybrid_lm.SAVED``'s and ``routed_lm.SAVED``'s names
#: in one model: a block has one part, so each name is kept in the blocks
#: whose part gives it).  Bytes a block, for T positions of width d in the
#: compute dtype of c bytes, a share of H heads of P over G groups of N, a
#: buffer of R rows, experts of width I in a latent of L:
#:   ssm_in_proj  T x (2 H P + 2 G N + H) x c   an M block's in_proj output
#:   flash_out    T x heads x D x c             a * block's kernel output
#:   flash_lse    T x heads x 4                 its log-sum-exp, float32
#:   moe_route    T x k x 4 + 2 x R x 4 + held x 4 + E x 4   an E block's
#:                weights, order, the token each row holds, sizes, and the
#:                selection's load (the load-balancing term's gradient)
#:   moe_latent   T x L x c                     the tokens in the latent
#:   moe_up       R x I x 4                     float32, as the grouped product returns it
#: Named and not kept: shared_up (T x shared width x c: 88 MB a block at
#: 8,192 positions of 5,376; PERF.md section 7, PR 47's list, has the
#: milliseconds it would spare and the room the chip has) and mixer_out (the
#: part's last product, which a block of one part never computes again).
SAVED = ("ssm_in_proj", "flash_out", "flash_lse", "moe_route", "moe_latent",
         "moe_up")


class PartBlock(linen.Module):
    """One block: the part its ``kind`` names (a letter of ``PARTS``) on the
    RMSNorm of the stream, added back."""
    kind: str
    part: Any                 # kwargs of the part's module, as sorted items
    eps: float = 1e-5
    dtype: Any = F32

    @linen.compact
    def __call__(self, x):
        name, module = PARTS[self.kind]
        h = RMSNorm(self.eps, self.dtype, name="norm")(x)
        h = module(dtype=self.dtype, name=name, **dict(self.part))(h)
        return x + h.astype(x.dtype)


class PatternLM(linen.Module):
    """``tokens`` (B, S) int32 -> float32 logits (B, S, V), through the
    blocks ``pattern`` spells (``"MEMEMEMEM*E"``).  The defaults are a small
    model; a published one passes its own ``config.json``'s numbers
    (``benchmark/nemotron_drivers.py`` does).

    The three shares, each ``(first, count)`` or None for the whole layer:
    ``held_ssm_heads`` of the ``ssm_heads`` (whole groups: ``Mamba2Mixer``),
    ``held_heads`` of the ``num_heads`` (with the key-value heads they read:
    ``GroupedQueryAttention``), ``held_experts`` of the ``num_experts``
    (``RoutedExperts``, with its ``buffer_rows``).  The head counts, the
    groups and the router's width stay the whole model's."""
    vocab_size: int = 32000
    embed_dim: int = 256
    pattern: str = "ME*E"
    # attention blocks
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 32
    held_heads: Optional[tuple] = None
    attention: Optional[str] = "flash"
    # state-space blocks
    ssm_heads: int = 8
    ssm_head_dim: int = 16
    ssm_state: int = 16
    ssm_groups: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 128
    held_ssm_heads: Optional[tuple] = None
    # expert blocks
    num_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate: int = 64
    moe_latent: Optional[int] = 64
    shared_intermediate: Optional[int] = 128
    held_experts: Optional[tuple] = None
    buffer_rows: Optional[int] = None
    routed_scale: float = 1.0
    router_norm_eps: float = 1e-20
    aux_loss_coef: float = 0.0
    bias_update_speed: float = 0.001
    rms_norm_eps: float = 1e-5
    dtype: Any = F32
    # per-block rematerialisation: a block keeps its input and the values
    # named in SAVED, and the backward pass computes the rest again
    remat: bool = False
    saved_names = SAVED     # no field: the policy's list, and the gauge's

    @linen.compact
    def __call__(self, tokens, training: bool = True):
        parts = {
            "M": dict(
                n_heads=self.ssm_heads, d_head=self.ssm_head_dim,
                d_state=self.ssm_state, n_groups=self.ssm_groups,
                d_conv=self.ssm_conv, chunk=self.ssm_chunk,
                held=self.held_ssm_heads, eps=self.rms_norm_eps),
            "*": dict(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, scale=self.head_dim ** -0.5,
                attention=self.attention, held=self.held_heads),
            "E": dict(
                num_experts=self.num_experts, top_k=self.num_experts_per_tok,
                intermediate=self.moe_intermediate, latent=self.moe_latent,
                expert_form="relu2",
                shared_intermediate=self.shared_intermediate,
                held=self.held_experts, buffer_rows=self.buffer_rows,
                scoring="sigmoid", routed_scale=self.routed_scale,
                norm_eps=self.router_norm_eps, selection_bias=True,
                bias_update_speed=self.bias_update_speed,
                aux_weight=self.aux_loss_coef)}
        init = linen.initializers.normal(0.02)
        table = self.param("embedding", init,
                           (self.vocab_size, self.embed_dim), F32)
        with jax.named_scope("embed"):
            x = jnp.take(table, tokens, axis=0).astype(self.dtype)
        block_cls = linen.remat(
            PartBlock, policy=jax.checkpoint_policies.save_only_these_names(
                *self.saved_names)) if self.remat else PartBlock
        for i, kind in enumerate(self.pattern):
            if kind not in parts:
                raise ValueError(f"no part {kind!r} in pattern "
                                 f"{self.pattern!r} (of {sorted(PARTS)})")
            x = block_cls(kind, tuple(sorted(parts[kind].items())),
                          self.rms_norm_eps, self.dtype, name=f"block{i}")(x)
        x = RMSNorm(self.rms_norm_eps, self.dtype, name="final_norm")(x)
        head = self.param("lm_head", init,
                          (self.vocab_size, self.embed_dim), F32)
        with jax.named_scope("lm_head"):
            return jnp.einsum("bsd,vd->bsv", x, head.astype(self.dtype),
                              preferred_element_type=F32)
