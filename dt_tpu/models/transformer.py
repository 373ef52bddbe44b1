"""Transformer language model with pluggable sequence parallelism.

Beyond the reference's RNN ceiling (the cuDNN fused LSTM,
``src/operator/cudnn_rnn-inl.h:1``; SURVEY.md §5.7) — the long-context
first-class citizen: pre-norm decoder blocks whose attention runs as plain
full attention (single device), ring attention (``seq_parallel='ring'``), or
Ulysses all-to-all (``seq_parallel='ulysses'``) over a mesh axis, letting
sequence length scale with the mesh.

Tensor-parallel-friendly layout: QKV/MLP matmuls are (D, 3D)/(D, 4D) —
shardable over a ``model`` mesh axis with ``with_sharding_constraint`` (see
``__graft_entry__.dryrun_multichip`` for the wired-up dp x tp x sp step).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as linen
import jax
import jax.numpy as jnp

from dt_tpu.ops import nn as ops


class MultiHeadAttention(linen.Module):
    num_heads: int
    seq_parallel: Optional[str] = None  # None|'ring'|'ulysses'|'flash'
    mesh: Any = None
    axis_name: str = "data"
    dtype: Any = jnp.float32

    @linen.compact
    def __call__(self, x, training=True):
        b, s, d = x.shape
        head_dim = d // self.num_heads
        qkv = linen.Dense(3 * d, use_bias=False, dtype=self.dtype,
                          name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, self.num_heads, head_dim)
        k = k.reshape(b, s, self.num_heads, head_dim)
        v = v.reshape(b, s, self.num_heads, head_dim)
        if self.seq_parallel == "ring":
            from dt_tpu.parallel.ring_attention import ring_attention
            out = ring_attention(q, k, v, self.mesh,
                                 axis_name=self.axis_name, causal=True)
        elif self.seq_parallel == "ulysses":
            from dt_tpu.parallel.ulysses import ulysses_attention
            out = ulysses_attention(q, k, v, self.mesh,
                                    axis_name=self.axis_name, causal=True)
        elif self.seq_parallel == "flash":
            from dt_tpu.ops.pallas.attention import (flash_attention,
                                                     DEFAULT_BLOCK)
            pad = (-s) % DEFAULT_BLOCK
            if pad:
                # pad queries AND keys at the end to the block size; the
                # causal mask keeps padded keys (positions > any real
                # query) out of real rows, and padded rows are sliced off
                padded = [jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for t in (q, k, v)]
                out = flash_attention(*padded, causal=True)[:, :s]
            else:
                out = flash_attention(q, k, v, causal=True)
        else:
            from dt_tpu.parallel.ring_attention import full_attention
            out = full_attention(q, k, v, causal=True)
        out = out.reshape(b, s, d)
        return linen.Dense(d, use_bias=False, dtype=self.dtype,
                           name="proj")(out)


class DecoderBlock(linen.Module):
    num_heads: int
    mlp_ratio: int = 4
    seq_parallel: Optional[str] = None
    mesh: Any = None
    axis_name: str = "data"
    dropout: float = 0.0
    moe_experts: int = 0      # >0 replaces the FFN with an MoE block
    moe_axis: str = "model"   # mesh axis experts shard over (EP)
    dtype: Any = jnp.float32

    @linen.compact
    def __call__(self, x, training=True):
        d = x.shape[-1]
        h = linen.LayerNorm(dtype=self.dtype)(x)
        h = MultiHeadAttention(self.num_heads, self.seq_parallel, self.mesh,
                               self.axis_name, self.dtype)(h, training)
        if training and self.dropout > 0:
            h = ops.dropout(h, self.dropout, training=True,
                            rng=self.make_rng("dropout"))
        x = x + h
        h = linen.LayerNorm(dtype=self.dtype)(x)
        if self.moe_experts:
            from dt_tpu.parallel.moe import MoEMLP
            h = MoEMLP(num_experts=self.moe_experts,
                       hidden_ratio=self.mlp_ratio, mesh=self.mesh,
                       axis=self.moe_axis, dtype=self.dtype,
                       name="moe")(h)
        else:
            h = linen.Dense(self.mlp_ratio * d, dtype=self.dtype,
                            name="mlp_in")(h)
            h = jax.nn.gelu(h)
            h = linen.Dense(d, dtype=self.dtype, name="mlp_out")(h)
        if training and self.dropout > 0:
            h = ops.dropout(h, self.dropout, training=True,
                            rng=self.make_rng("dropout"))
        return x + h


class PipeStage(linen.Module):
    """One pipeline stage: ``layers`` decoder blocks applied in order.
    Params of ALL stages are stacked on a leading S axis and sharded
    over the ``pipe`` mesh axis (``parallel/pipeline.py``)."""
    layers: int
    num_heads: int
    dtype: Any = jnp.float32

    @linen.compact
    def __call__(self, h):
        for i in range(self.layers):
            h = DecoderBlock(self.num_heads, 4, None, None, "data", 0.0,
                             0, "model", self.dtype,
                             name=f"layer{i}")(h, False)
        return h


class _PipeOuter(linen.Module):
    """The non-pipelined ends: embedding (+pos) before the pipe, final
    norm + LM head after it."""
    vocab_size: int
    embed_dim: int
    max_len: int
    dtype: Any = jnp.float32

    def setup(self):
        self.embed = linen.Embed(self.vocab_size, self.embed_dim,
                                 dtype=self.dtype, name="embed")
        self.pos_embed = self.param("pos_embed",
                                    linen.initializers.normal(0.02),
                                    (self.max_len, self.embed_dim),
                                    self.dtype)
        self.ln_f = linen.LayerNorm(dtype=self.dtype)
        self.lm_head = linen.Dense(self.vocab_size, use_bias=False,
                                   dtype=self.dtype)

    def encode(self, tokens):
        s = tokens.shape[1]
        return self.embed(tokens) + self.pos_embed[None, :s]

    def head(self, x):
        return self.lm_head(self.ln_f(x))

    def __call__(self, tokens):  # init path: touches every param
        return self.head(self.encode(tokens))


class PipelinedTransformerLM:
    """TransformerLM with its decoder blocks run as a GPipe pipeline
    (VERDICT r4 next 4 — a REAL model through the pipeline, not a tanh
    toy).

    Duck-types the flax surface ``Module`` consumes (``init``/``apply``),
    so ``training.Module.fit`` drives it unchanged: embedding and LM head
    run replicated; the ``num_layers`` decoder blocks fold into
    ``num_stages`` stage-stacked param groups streamed through
    ``parallel.pipeline.pipeline_apply`` (microbatches over the ``pipe``
    mesh axis, optionally composed with a ``data`` axis for dp x pp).

    Reference capability: manual per-layer ``group2ctx`` placement with
    cross-device copies (``example/model-parallel/``,
    ``src/operator/cross_device_copy.cc``) — no microbatch scheduling;
    this is the TPU-native upgrade.  Dropout is not supported inside the
    pipe (rngs would have to thread the shard_map schedule); use the
    plain ``TransformerLM`` when dropout matters.
    """

    def __init__(self, vocab_size=32000, embed_dim=512, num_layers=6,
                 num_heads=8, max_len=8192, num_stages=2, num_micro=4,
                 mesh=None, axis_name="pipe", batch_axis=None,
                 remat_stages=False, dtype=jnp.float32):
        if num_layers % num_stages:
            raise ValueError(f"num_layers={num_layers} must divide into "
                             f"num_stages={num_stages}")
        self.vocab_size = vocab_size
        self.embed_dim = embed_dim
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.max_len = max_len
        self.num_stages = num_stages
        self.num_micro = num_micro
        self.mesh = mesh
        self.axis_name = axis_name
        self.batch_axis = batch_axis
        self.remat_stages = remat_stages
        self.dtype = dtype
        self._outer = _PipeOuter(vocab_size, embed_dim, max_len, dtype)
        self._stage = PipeStage(num_layers // num_stages, num_heads,
                                dtype)

    def init(self, rngs, tokens, training=False):
        key = rngs["params"] if isinstance(rngs, dict) else rngs
        k_outer, k_stages = jax.random.split(key)
        outer = self._outer.init({"params": k_outer}, tokens)["params"]
        dummy = jnp.zeros(tokens.shape + (self.embed_dim,), self.dtype)
        per_stage = [
            self._stage.init({"params": k}, dummy)["params"]
            for k in jax.random.split(k_stages, self.num_stages)]
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *per_stage)
        return {"params": {"outer": outer, "stages": stacked}}

    def _stage_fn(self):
        def fn(stage_params, h):
            return self._stage.apply({"params": stage_params}, h)
        if self.remat_stages:
            fn = jax.checkpoint(fn)
        return fn

    def _forward(self, params, tokens):
        x = self._outer.apply({"params": params["outer"]}, tokens,
                              method=_PipeOuter.encode)
        b = x.shape[0]
        if self.mesh is not None and \
                self.mesh.shape.get(self.axis_name, 1) > 1:
            m = self.num_micro
            if b % m:
                raise ValueError(f"batch {b} must divide into "
                                 f"num_micro={m} microbatches")
            if self.batch_axis:
                dp = self.mesh.shape.get(self.batch_axis, 1)
                if (b // m) % dp:
                    raise ValueError(
                        f"microbatch size {b // m} (batch {b} / "
                        f"num_micro {m}) must divide by the "
                        f"{self.batch_axis!r} axis ({dp} devices)")
            micro = x.reshape((m, b // m) + x.shape[1:])
            from dt_tpu.parallel.pipeline import pipeline_apply
            ys = pipeline_apply(self._stage_fn(), params["stages"], micro,
                                self.mesh, axis_name=self.axis_name,
                                batch_axis=self.batch_axis)
            h = ys.reshape((b,) + ys.shape[2:])
        else:
            # single-device (and init) path: stages in sequence — the
            # numerical oracle the pipelined schedule must match
            fn = self._stage_fn()
            h = x
            for i in range(self.num_stages):
                p_i = jax.tree_util.tree_map(lambda p, i=i: p[i],
                                             params["stages"])
                h = fn(p_i, h)
        return self._outer.apply({"params": params["outer"]}, h,
                                 method=_PipeOuter.head)

    def apply(self, variables, tokens, training=False, rngs=None,
              mutable=None):
        logits = self._forward(variables["params"], tokens)
        if mutable is not None:
            return logits, {}
        return logits


class TransformerLM(linen.Module):
    vocab_size: int = 32000
    embed_dim: int = 512
    num_layers: int = 6
    num_heads: int = 8
    max_len: int = 8192
    seq_parallel: Optional[str] = None
    mesh: Any = None
    axis_name: str = "data"
    dropout: float = 0.0
    moe_experts: int = 0
    moe_axis: str = "model"
    dtype: Any = jnp.float32
    # Per-LAYER rematerialization: each decoder block's activations are
    # recomputed in backward instead of stored — at long context this is
    # the difference between O(layers * S * d) and O(S * d) live
    # activation HBM (the reference's memory mirror; composes with
    # ring/ulysses sequence parallelism and grad_accum).  Stable
    # `block{i}` names keep checkpoints interchangeable.  The memory
    # effect needs the chip's compiler: XLA CPU folds recompute away.
    remat: bool = False

    @linen.compact
    def __call__(self, tokens, training: bool = True):
        """``tokens``: (B, S) int32 -> logits (B, S, V)."""
        b, s = tokens.shape
        x = linen.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype,
                        name="embed")(tokens)
        pos = self.param("pos_embed", linen.initializers.normal(0.02),
                         (self.max_len, self.embed_dim), self.dtype)
        x = x + pos[None, :s]
        block_cls = linen.remat(DecoderBlock, static_argnums=(2,)) \
            if self.remat else DecoderBlock
        for i in range(self.num_layers):
            x = block_cls(self.num_heads, 4, self.seq_parallel, self.mesh,
                          self.axis_name, self.dropout,
                          self.moe_experts, self.moe_axis,
                          self.dtype, name=f"block{i}")(x, training)
        x = linen.LayerNorm(dtype=self.dtype)(x)
        return linen.Dense(self.vocab_size, use_bias=False,
                           dtype=self.dtype, name="lm_head")(x)
