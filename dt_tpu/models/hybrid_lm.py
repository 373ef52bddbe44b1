"""A decoder whose layers are read from a pattern: state-space (Mamba-2)
mixers among grouped-query attention layers, each followed by a gated
feed-forward.

Beyond the reference's RNN ceiling (the cuDNN fused LSTM,
``src/operator/cudnn_rnn-inl.h:1``; SURVEY.md §5.7) and beside
``TransformerLM``'s 2019 block: what the hybrid decoders of 2025 share.
Pre-norm RMSNorm with a learned scale; ``silu(x Wg) * (x Wu)`` feed-forward
without bias; no positional encoding (the recurrence orders the sequence);
attention with fewer key-value heads than query heads and a given score
scale; scalar multipliers on the embedding, every residual branch and the
logits; a head tied to the embedding; per-block rematerialisation that
keeps the values named in ``SAVED`` (a few projections' outputs) and computes
the rest of a block again in the backward pass.

    h = embedding_multiplier * E[token]
    h = h + residual_multiplier * mixer_l(rms(h))     mixer: "mamba" | "attention"
    h = h + residual_multiplier * mlp(rms(h))
    logits = rms(h) E^T / logits_scaling              (float32)

The Mamba-2 mixer (``ops/ssm.py`` has the recurrence)::

    [z | xBC | dt] = x W_in                 widths d_inner, d_inner + 2 G N, H
    xBC = silu(conv(xBC))                   causal depthwise, d_conv taps, bias
    x, B, C = split(xBC)                    H heads of P; G groups of N
    dt = softplus(dt + dt_bias); a = -exp(A_log)
    y = ssd_scan(x, dt, a, B, C) + D x      chunked, chunk positions at a time
    out = rms_g(y * silu(z)) W_out          the norm after the gate

Module names tell the parts apart in an operation's scope path
(``block3/mamba/in_proj``, ``block5/attn/q_proj``, ``block0/mlp/gate``), and
``jax.named_scope``s ``conv1d``, ``ssd_scan`` and ``gated_norm`` mark the
mixer's parts that are no module; ``embed`` and ``lm_head`` mark the ends.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import flax.linen as linen
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dt_tpu.ops import ssm

F32 = jnp.float32


class RMSNorm(linen.Module):
    eps: float = 1e-5
    dtype: Any = F32

    @linen.compact
    def __call__(self, x):
        scale = self.param("scale", linen.initializers.ones,
                           (x.shape[-1],), F32)
        v = x.astype(F32)
        v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1,
                                       keepdims=True) + self.eps)
        return (v * scale).astype(self.dtype)


class GatedMLP(linen.Module):
    intermediate: int
    dtype: Any = F32

    @linen.compact
    def __call__(self, x):
        dense = lambda n, name: linen.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        h = jax.nn.silu(
            checkpoint_name(dense(self.intermediate, "gate")(x), "mlp_gate")) \
            * checkpoint_name(dense(self.intermediate, "up")(x), "mlp_up")
        return dense(x.shape[-1], "down")(h)


def held_share(held, whole: int, per_group: int, what: str):
    """``held`` (first, count) of ``whole`` heads, or None for all of them ->
    (the heads held, the groups of ``per_group`` heads they read), checked:
    a share is whole groups, or an even part of one group, which it then
    reads whole (a key-value head can be every chip's that reads it; a
    group of B and C under a norm of its own cannot, and ``Mamba2Mixer``
    refuses that)."""
    if held is None:
        return whole, whole // per_group
    first, count = held
    if first < 0 or count < 1 or first + count > whole:
        raise ValueError(f"heads {first}..{first + count} of {whole}")
    if first % per_group == 0 and count % per_group == 0:
        return count, count // per_group
    if per_group % count == 0 and first % count == 0:
        return count, 1
    raise ValueError(f"heads {first}..{first + count} of {whole} {what} "
                     f"heads are neither whole groups of {per_group} nor "
                     f"an even part of one")


class GroupedQueryAttention(linen.Module):
    """``num_kv_heads`` key-value heads, each serving
    ``num_heads // num_kv_heads`` consecutive query heads; no positions;
    causal softmax of ``scale * q k^T``.

    ``held = (first, count)``: this chip holds query heads ``first ..
    first + count`` of the ``num_heads`` and the key-value heads they read,
    as tensor parallelism deals them: whole key-value heads with all their
    query heads, or, where the chips outnumber the key-value heads, an even
    part of one key-value head's query heads with that head (which the
    other chips that read it hold too: 32 query heads over 2 deal over 8
    chips as 4 and 1).  ``q_proj``, ``k_proj`` and ``v_proj`` have those
    heads' columns, ``o_proj`` their rows, and the layer returns their part
    of the result, ``o_held Wo[held rows]``.
    Summing the parts of all the shares gives the layer (no bias, and the
    softmax is a head's own).  What the other heads would have added is left
    out; no code stands in for the chips that hold them.  None: all the
    heads, the computation it always was.

    Under ``attention="flash"`` with heads of whole lane tiles
    (``attention.lane_tiled``: a head size that 128 divides) the kernels read
    q, k, v as the projections leave them, (B, S, H * D), and write the
    output there for ``o_proj`` (PERF.md section 6, PR 45); at any other
    head size the operands are (B, S, H, D) as before."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    scale: float
    attention: Optional[str] = "flash"   # 'flash' (Pallas) | None (plain)
    dtype: Any = F32
    held: Optional[tuple] = None         # (first, count) query heads

    @linen.compact
    def __call__(self, x):
        b, s, d = x.shape
        heads, kv_heads = held_share(
            self.held, self.num_heads, self.num_heads // self.num_kv_heads,
            "query")
        rep = heads // kv_heads
        dense = lambda n, name: linen.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        q = dense(heads * self.head_dim, "q_proj")(x)
        k = dense(kv_heads * self.head_dim, "k_proj")(x)
        v = dense(kv_heads * self.head_dim, "v_proj")(x)
        from dt_tpu.ops.pallas.attention import (flash_attention,
                                                 DEFAULT_BLOCK, lane_tiled)
        # heads of whole lane tiles stay where the projections leave them
        tiled = self.attention == "flash" and lane_tiled(self.head_dim, s)
        if not tiled:
            q = q.reshape(b, s, heads, self.head_dim)
            k, v = (t.reshape(b, s, kv_heads, self.head_dim)
                    for t in (k, v))
        if self.attention == "flash":
            # k and v keep their heads: the kernels' index maps read the
            # head that serves a query head
            pad = (-s) % DEFAULT_BLOCK
            if pad:   # as TransformerLM: padded keys lie after every real query
                q, k, v = (jnp.pad(t, ((0, 0), (0, pad))
                                   + ((0, 0),) * (t.ndim - 2))
                           for t in (q, k, v))
            out = flash_attention(q, k, v, causal=True, scale=self.scale,
                                  heads=heads if tiled else None)[:, :s]
        else:
            from dt_tpu.parallel.ring_attention import full_attention
            # the oracle takes one head count: each key-value head spread
            # over the query heads it serves
            k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
            out = full_attention(q, k, v, causal=True, scale=self.scale)
        return checkpoint_name(
            dense(d, "o_proj")(out.reshape(b, s, -1)), "mixer_out")


def _a_log_init(key, shape, dtype=F32):
    """Mamba-2's: A uniform in 1..16."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=F32, lo=1e-3, hi=0.1):
    """Mamba-2's: dt log-uniform in 0.001..0.1, through the inverse of the
    softplus."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (jnp.log(hi) - jnp.log(lo)) + jnp.log(lo))
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2Mixer(linen.Module):
    """The Mamba-2 mixer of the module's docstring: ``n_heads`` heads of
    ``d_head`` channels reading ``n_groups`` groups of B and C (head ``h``
    reads group ``h // (n_heads // n_groups)``).

    The gated norm is over each group's own channels (``d_inner /
    n_groups`` of them: Mamba-2's own norm under tensor parallelism, and the
    published norm of the decoders that have ``n_groups`` > 1 for that);
    with one group that is the norm over all ``d_inner``.

    ``held = (first, count)``: this chip holds heads ``first .. first +
    count`` of the ``n_heads`` and the groups they read (whole ones: a share
    is a multiple of ``n_heads // n_groups`` heads), as tensor parallelism
    deals them: ``in_proj`` has the columns ``[z | x | B | C | dt]`` of those
    heads and groups, the taps, ``dt_bias``, ``A_log``, ``D`` and the norm's
    scale their channels, ``out_proj`` their rows, and the layer returns
    their part of the result, ``n_held Wout[held rows]``.  Summing the parts
    of all the shares gives the layer: nothing in it crosses a group (the
    convolution is depthwise, the recurrence a head's own, the norm a
    group's own).  What the other heads would have added is left out; no
    code stands in for the chips that hold them.  None: all the heads, the
    computation it always was."""
    n_heads: int
    d_head: int
    d_state: int
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 256
    conv_bias: bool = True
    eps: float = 1e-5
    dtype: Any = F32
    held: Optional[tuple] = None          # (first, count) heads

    @linen.compact
    def __call__(self, x):
        b, l, d = x.shape
        per_group = self.n_heads // self.n_groups
        h, g = held_share(self.held, self.n_heads, per_group, "state-space")
        if h % per_group:
            raise ValueError("a share of the state-space heads is whole "
                             "groups, each normed alone")
        p, n = self.d_head, self.d_state
        d_inner, conv_dim = h * p, h * p + 2 * g * n
        zxbcdt = checkpoint_name(
            linen.Dense(d_inner + conv_dim + h, use_bias=False,
                        dtype=self.dtype, name="in_proj")(x), "ssm_in_proj")
        z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim], axis=-1)
        conv_w = self.param("conv_kernel",
                            linen.initializers.lecun_normal(),
                            (self.d_conv, conv_dim), F32)
        conv_b = self.param("conv_bias", linen.initializers.zeros,
                            (conv_dim,), F32) if self.conv_bias else None
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,), F32)
        a_log = self.param("A_log", _a_log_init, (h,), F32)
        skip = self.param("D", linen.initializers.ones, (h,), F32)
        norm_scale = self.param("norm_scale", linen.initializers.ones,
                                (d_inner,), F32)
        with jax.named_scope("conv1d"):
            xbc = jax.nn.silu(ssm.causal_conv1d(xbc, conv_w, conv_b))
        with jax.named_scope("ssd_scan"):
            xs, bm, cm = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
            xs = xs.reshape(b, l, h, p)
            y = ssm.ssd_scan(
                xs, jax.nn.softplus(dt.astype(F32) + dt_bias),
                -jnp.exp(a_log), bm.reshape(b, l, g, n),
                cm.reshape(b, l, g, n), chunk=self.chunk)
            y = y + (skip[:, None] * xs.astype(F32)).astype(y.dtype)
        with jax.named_scope("gated_norm"):
            y = ssm.gated_rms_norm(y.reshape(b, l, d_inner), z, norm_scale,
                                   self.eps, g)
        return checkpoint_name(
            linen.Dense(d, use_bias=False, dtype=self.dtype,
                        name="out_proj")(y), "mixer_out")


#: what a rematerialised ``HybridBlock`` keeps from its forward pass, by
#: ``checkpoint_name``; the backward pass computes the rest again from the
#: block's input.  Bytes a layer, for T positions (B x S) of width d in the
#: compute dtype of c bytes:
#:   ssm_in_proj  T x (2 d_inner + 2 G N + H) x c   a Mamba-2 layer's in_proj
#:   mixer_out    T x d x c       the mixer's or the attention's last product
#: Named and not kept: mlp_gate and mlp_up (T x intermediate x c each: the
#: chip has no room for them beside these; PERF.md section 6, PR 35, has
#: each name's measured milliseconds a gigabyte).  The flash kernel's
#: ``flash_out`` is not here: one attention layer in ten, and the benchmark
#: counts its calls from a file.
SAVED = ("ssm_in_proj", "mixer_out")


class HybridBlock(linen.Module):
    """One layer: the mixer its ``kind`` names, then the feed-forward, each
    on the RMSNorm of the stream and added back times
    ``residual_multiplier``."""
    kind: str                 # 'mamba' | 'attention'
    intermediate: int
    residual_multiplier: float
    mixer: Any                # kwargs of the mixer's module
    eps: float = 1e-5
    dtype: Any = F32

    @linen.compact
    def __call__(self, x):
        h = RMSNorm(self.eps, self.dtype, name="input_norm")(x)
        if self.kind == "mamba":
            h = Mamba2Mixer(eps=self.eps, dtype=self.dtype, name="mamba",
                            **dict(self.mixer))(h)
        elif self.kind == "attention":
            h = GroupedQueryAttention(dtype=self.dtype, name="attn",
                                      **dict(self.mixer))(h)
        else:
            raise ValueError(f"unknown layer type {self.kind!r}")
        x = x + (self.residual_multiplier * h).astype(x.dtype)
        h = RMSNorm(self.eps, self.dtype, name="post_norm")(x)
        h = GatedMLP(self.intermediate, self.dtype, name="mlp")(h)
        return x + (self.residual_multiplier * h).astype(x.dtype)


class HybridLM(linen.Module):
    """``tokens`` (B, S) int32 -> float32 logits (B, S, V).  The defaults
    are a small model; a published one passes its own ``config.json``'s
    numbers (``benchmark/hybrid_drivers.py`` does)."""
    vocab_size: int = 32000
    embed_dim: int = 512
    layer_types: Sequence[str] = ("mamba", "mamba", "attention", "mamba")
    intermediate: int = 2048
    # attention layers
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: Optional[int] = None        # embed_dim // num_heads
    attention_multiplier: Optional[float] = None   # 1 / sqrt(head_dim)
    attention: Optional[str] = "flash"
    # state-space layers
    ssm_heads: int = 16
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    ssm_conv_bias: bool = True
    # the four scalars and the head
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    tie_word_embeddings: bool = True
    rms_norm_eps: float = 1e-5
    dtype: Any = F32
    # per-block rematerialisation: a block keeps its input and the values
    # named in SAVED, and the backward pass computes the rest again
    remat: bool = False
    saved_names = SAVED     # no field: the policy's list, and the gauge's

    @linen.compact
    def __call__(self, tokens, training: bool = True):
        head_dim = self.head_dim or self.embed_dim // self.num_heads
        scale = self.attention_multiplier
        mixers = {
            "attention": dict(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=head_dim, attention=self.attention,
                scale=head_dim ** -0.5 if scale is None else scale),
            "mamba": dict(
                n_heads=self.ssm_heads, d_head=self.ssm_head_dim,
                d_state=self.ssm_state, n_groups=self.ssm_groups,
                d_conv=self.ssm_conv, chunk=self.ssm_chunk,
                conv_bias=self.ssm_conv_bias)}
        table = self.param("embedding", linen.initializers.normal(0.02),
                           (self.vocab_size, self.embed_dim), F32)
        with jax.named_scope("embed"):
            x = (jnp.take(table, tokens, axis=0)
                 * self.embedding_multiplier).astype(self.dtype)
        block_cls = linen.remat(
            HybridBlock, policy=jax.checkpoint_policies.save_only_these_names(
                *self.saved_names)) if self.remat else HybridBlock
        for i, kind in enumerate(self.layer_types):
            x = block_cls(kind, self.intermediate, self.residual_multiplier,
                          tuple(sorted(mixers[kind].items())),
                          self.rms_norm_eps, self.dtype, name=f"block{i}")(x)
        x = RMSNorm(self.rms_norm_eps, self.dtype, name="final_norm")(x)
        head = table if self.tie_word_embeddings else self.param(
            "lm_head", linen.initializers.normal(0.02),
            (self.vocab_size, self.embed_dim), F32)
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("bsd,vd->bsv", x, head.astype(self.dtype),
                                preferred_element_type=F32)
            return logits / self.logits_scaling
