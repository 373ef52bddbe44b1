"""A decoder of rotary grouped-query attention and routed experts, trained by
diffusion over blocks or as a causal next-token model: one whose attention
reads only the keys a learned indexer picks, or one whose layers differ from
one another (windowed and full attention with their own head counts and
rotary rules, a gate a head, a leading dense layer, a shared expert; or
gated short convolutions among attention layers, experts chosen under a
selection bias that is balanced without a loss, a head tied to the table).

Beyond the reference's RNN ceiling (the cuDNN fused LSTM,
``src/operator/cudnn_rnn-inl.h:1``; SURVEY.md §5.7) and beside ``HybridLM``
(``hybrid_lm.py``, whose ``RMSNorm`` this reuses): what the sparse-expert
decoders of 2025 share and that one lacks.  Rotary
positions (``rope``; ``mrope`` where a position has three parts), a learned
RMSNorm on each head's query and key before them, a ``head_dim`` that is not
``embed_dim / num_heads``, an expert layer (``parallel/moe.py``
``RoutedExperts``) where the gated feed-forward stands, an untied head::

    a = rms(x) ;  q = rms_h(a Wq) , k = rms_h(a Wk) , v = a Wv
    q, k = rope(q, pos), rope(k, pos)
    h = x + softmax(q k^T / sqrt(head_dim) + M) v Wo
    x' = h + experts(rms(h))
    logits = rms(x_last) Whead                          (float32)

Two objectives (``RoutedLM.objective``).

**Training by diffusion over blocks** (``"block_diffusion"``; BD3-LM, SDAR):
the model is handed ``[xt ; x0]``, ``2 L`` positions a sequence: ``x0`` the
``L`` tokens of data, ``xt`` the same with each block's tokens replaced by
the mask id at that block's noise level (``data.block_diffusion_noise``
makes both, with the targets and weights).  Both halves sit at rotary
positions ``0 .. L-1`` and ``M`` is ``BlockDiffusionMask(L, block_length)``:
a noisy query sees its own block's noisy keys and the clean keys of the
blocks before; a clean query the clean keys up to its own block.  The
logits are of the noisy half only, ``(B, L, V)``; the loss is
``ops.losses.weighted_masked_cross_entropy``.  (Generation denoises one block
at a time over the clean blocks before it: the serving path's, not here.)

**Causal next-token training** (``"causal"``): ``T`` tokens a sequence,
``M`` the causal mask, logits over all ``T`` positions, the loss
``ops.losses.softmax_cross_entropy`` against the next token.  With
``indexer`` every layer carries a learned index (``ops/sparse_index.py``;
DeepSeek-V3.2-Exp's sparse attention) and attends only to the ``k`` keys it
picks; ``a~`` is the stop-gradient of ``a``, ``H_I`` index heads of ``D_I``::

    q, k = mrope(q, pos[3, T]), mrope(k, pos[3, T])     sections of the D/2 frequency pairs
    qI = (a~ WqI) as [T, H_I, D_I] ;  kI = layer_norm(a~ WkI) as [T, D_I]
    w = a~ Ww * H_I^-0.5 as [T, H_I]
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(D_I)        s <= t
    S_t = the k keys s <= t of largest I[t, s]          (every s <= t while t < k)
    h = x + [softmax over s in S_t of q_t . k_s / sqrt(D)] v Wo
    loss = CE(next token, all T positions) + aux_loss_coef load_balance
           + kl_weight sum_layers mean_t KL(pbar_t || softmax over S_t of I[t, .])
    pbar_t[s] = stop_gradient(sum_h p_h[t, s] / H) ,  s in S_t

``p_h`` are the main attention's own probabilities.  Under grouped-query
attention one selection serves all the heads of a position.  The index
learns from the KL term alone (its inputs are ``a~``, and the term reads the
main attention as constants) and the rest of the model from the
cross-entropy alone (the selection passes no gradient).  The selection
reaches the flash kernels as data, a packed bitmap
(``ops/pallas/attention.py`` ``SelectedKeysMask``); the KL term is sown
under ``("aux_loss", "indexer_kl")`` and the layer's counts under
``("counters", "dsa")`` (``DSA_COUNTERS``).

**Layers that differ inside one decoder** (``RoutedLM.layers``, one record a
layer: kind of attention, ``num_heads``, rotary rule, dense or routed
feed-forward; causal objective).  With ``a`` the normed stream, ``D`` the
head size, ``W`` the window, ``E`` experts, ``k`` a token, as one published
decoder of this kind has them (64 heads in a sliding layer and 48 in a full
one over 8 key-value heads of 128, ``W`` 512, ``E`` 256, ``k`` 8)::

    a = rms(x)
    q = (a Wq) as [T, H_l, D] ;  k = (a Wk) as [T, KV, D] ;  v = (a Wv) as [T, KV, D]
    full layer:     q, k = yarn_rope(q, pos), yarn_rope(k, pos)      the first rotary_dim of D dims turned, the rest passed through
    sliding layer:  q, k = rope(q, pos, theta), rope(k, pos, theta)  all D dims
    full:     o_t = softmax over s <= t           of q_t . k_s / sqrt(D)  v
    sliding:  o_t = softmax over t - W < s <= t   of q_t . k_s / sqrt(D)  v      W keys, the query's own among them
    g = sigmoid(a Wg) as [T, H_l]                                                 one gate a head
    h = x + ((g[..., None] * o) as [T, H_l D]) Wo
    dense layer:    x' = h + Wdown(silu(b Wgate) * (b Wup)) ,  b = rms(h)
    routed layer:   s = sigmoid_f32(b Wr) over E ;  S = top_k(s) ;  w_e = scale * s_e / sum_S s
                    x' = h + shared(b) + sum_{e in S, e held} w_e expert_e(b)     shared and routed experts gated SiLU
    logits = rms(x_last) Whead   (float32) ;  loss = CE(next token, all T) + aux_loss_coef * load_balance

``yarn_rope`` (``yarn_frequencies``, ``rope_part``): over the ``rotary_dim /
2`` frequency pairs ``f_i = theta^(-2i / rotary_dim)``; a ramp between the
pairs that make ``beta_fast`` and ``beta_slow`` turns inside
``original_max_position_embeddings`` blends ``f_i`` (kept) with ``f_i /
factor`` (interpolated), as the published YaRN rule (arXiv:2309.00071) and
its reference implementation have it; cosine and sine are multiplied by
``attention_factor``.  The turned pair is ``(x_i, x_{i + rotary_dim / 2})``
inside the turned part, ``_turn``'s convention.  No norm on the heads'
queries and keys (``qk_norm=False``).  The window reaches the flash kernels
as the static rule ``WindowMask`` (``ops/pallas/attention.py``: the grids
span only the tiles a band can touch), and a windowed layer sows the rule's
static counts under ``("counters", "win")`` (``WIN_COUNTERS``).

**Gated short convolutions among attention layers, experts under a
selection bias** (a layer's record with ``attention: "conv"``;
``selection_bias``, ``tie_word_embeddings``; causal objective).  As one
published decoder of this kind has them (LFM2's ``Lfm2ShortConv``,
``Lfm2Attention`` and ``Lfm2DecoderLayer``: 3 taps, 32 query heads over 8
key-value heads of 64, ``E`` 32 experts of width 1,792, ``k`` 4, two leading
dense layers of width 7,168, eps 1e-5)::

    a = rms(x)                                                          operator_norm
    conv layer:       [B | C | u] = a Win                               Win d x 3d, no bias; three equal parts, in this order
                      c_t = sum_{j=0..2} w_j * (B * u)_{t-2+j}          depthwise over d channels, 3 taps, zero before position 0, no bias, no activation
                      h = x + (C * c) Wout                              Wout d x d
    attention layer:  q = rms_h(a Wq) as [T, H, D] ;  k = rms_h(a Wk) as [T, KV, D] ;  v = (a Wv) as [T, KV, D]
                      q, k = rope(q, pos, theta), rope(k, pos, theta)   the whole head, pairs (x_i, x_{i+D/2})
                      h = x + [softmax over s <= t of q_t . k_s / sqrt(D)] v Wo
    b = rms(h)                                                          ffn_norm
    dense layer:      x' = h + W2(silu(b W1) * (b W3))
    routed layer:     s = sigmoid_f32(b Wr) over E
                      S = top_k(s + bias)                               bias (E,) float32: the selection only
                      w_e = routed_scale * s_e / (sum_{S} s + router_norm_eps) ,  e in S      from s, not from s + bias
                      x' = h + sum_{e in S, e held} w_e W2_e(silu(b W1_e) * (b W3_e))
    logits = rms(x_last) Table^T     (float32; tie_word_embeddings)
    loss = CE(next token, all T positions)                              no auxiliary term (aux_loss_coef 0)
    after a training step, each routed layer:  load_e = its assignments to e in the step, all E, all tokens
                      bias_e <- bias_e + u * sign(mean_e(load) - load_e)

The mixer is ``ShortConv`` (module ``conv`` where ``attn`` stands in the other
layers; the taps through ``ops/ssm.py`` ``causal_conv1d``).  The bias is a
variable of the ``batch_stats`` collection, no parameter: ``training.Module``
carries it in its ``TrainState`` beside the parameters (through ``fit``,
checkpoints and a joiner's bootstrap), no gradient reaches it and the
optimizer holds nothing for it; ``parallel/moe.py`` ``RoutedExperts`` reads it
and, in a training step, writes it moved (once a step: a rematerialised
block's second forward does not move it again), and counts the picks it moved
under ``("counters", "moe_bias")``.

Module names and ``jax.named_scope``s tell the parts apart in an operation's
scope path: ``block3/conv/in_proj``, ``block3/conv/gate_in`` (``B * u``),
``block3/conv/conv1d`` (the taps), ``block3/conv/gate_out`` (``C * c``),
``block3/conv/out_proj``; ``block3/attn/q_proj``, ``block3/attn/rope``,
``block3/attn/indexer`` (the three projections, the index key's norm and the
chunked scores), ``block3/attn/select`` (each row's k-th largest score and
the bitmaps), ``block3/attn/indexer_kl``,
``block3/moe/route`` (``dispatch``, ``experts``, ``combine``, ``shared``),
``embed``, ``lm_head``; in a decoder whose layers differ, the kind of a
layer's attention (``block3/attn/window/...``, ``block0/attn/full/...``: no
metric has to name a layer by its number), ``.../gate`` inside either, and
``block0/mlp`` for a dense layer.

**Heads of whole lane tiles** (``head_dim % 128 == 0`` under
``attention="flash"``: the three published decoders above; PERF.md section 6,
PR 45; ``attention.lane_tiled`` is the one place that says so).  The layer
then hands ``flash_attention`` q, k, v as the projections' ``(B, S, H * D)``
arrays (rank 3, ``heads=H``), the kernels read them and write their output
where those lie, and the layer's own work stays there too: the heads' norm,
the rotary turn and the gate go over ``attention.by_head``'s view ``(B, S /
8, H, 8, D)``, the same bytes under the TPU's (8, 128) tiling, where a ``(B,
S, H, D)`` array is other bytes and every ``reshape`` to it a pass over the
array.
The turn is ``turn_heads``: each lane times its cosine plus its pair's lane
times its signed sine, the pairs brought by a product with a permutation
(``_pairs``; exact) and not by slices of the lanes, which XLA cuts out as
arrays of their own; its backward pass is the turn by the opposite angle,
written out (on the chip the step is 3.3% slower with that pass left to
autodiff; the gate's, tried the same way, bought nothing and is autodiff's).
Same arithmetic, float32 inside; at any other head size, and without the
kernels, the layer holds ``(B, S, H, D)`` arrays as before.

With ``remat`` each block is rematerialised: it keeps its input and the
values named in ``SAVED`` (the flash kernel's output among them, so the
kernel runs once a layer) and computes the rest again in the backward pass.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, Optional

import flax.linen as linen
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from dt_tpu.models.hybrid_lm import GatedMLP, RMSNorm
from dt_tpu.ops import sparse_index, ssm
from dt_tpu.ops.pallas.attention import (BlockDiffusionMask, DEFAULT_BLOCK,
                                         NEG_INF, SelectedKeysMask,
                                         WindowMask, backward_tiles,
                                         by_head, flash_attention,
                                         forward_tiles, from_heads,
                                         lane_tiled,
                                         unpack_selection)
from dt_tpu.parallel.moe import RoutedExperts

F32 = jnp.float32


def _turn(x, angle, scale: float = 1.0):
    """``x`` (B, S, H, D) with the pair ``(x_i, x_{i + D/2})`` turned by
    ``angle`` (S, D/2), in float32; the cosine and sine times ``scale``."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    v = x.astype(F32)
    a, b = v[..., :half], v[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _turn_part(x, angle, scale: float = 1.0):
    """``_turn`` on the first ``2 angle.shape[1]`` of ``x``'s last axis; the
    rest of the head passes through."""
    part = 2 * angle.shape[1]
    if part == x.shape[-1]:
        return _turn(x, angle, scale)
    return jnp.concatenate([_turn(x[..., :part], angle, scale),
                            x[..., part:]], axis=-1)


def _rope_freq(half: int, theta: float):
    """The plain rotary schedule over ``half`` pairs: ``theta^(-i / half)``."""
    return theta ** (-jnp.arange(half, dtype=F32) / half)


def _angle(positions, freq):
    """``positions`` (S,) times each pair's frequency: the angles (S,
    len(freq)), float32, that every rotary rule here turns by (``mrope``'s
    are ``_mrope_angle``'s)."""
    return positions.astype(F32)[:, None] * jnp.asarray(freq, F32)[None, :]


def rope(x, positions, theta: float):
    """Rotary positions on ``x`` (B, S, H, D) at ``positions`` (S,): the
    pair ``(x_i, x_{i + D/2})`` turned by ``pos * theta^(-2i/D)``, in
    float32."""
    return _turn(x, _angle(positions, _rope_freq(x.shape[-1] // 2, theta)))


def yarn_frequencies(rotary_dim: int, theta: float, factor: float,
                     original_max_position_embeddings: int,
                     beta_fast: float = 32.0, beta_slow: float = 1.0):
    """The YaRN schedule (arXiv:2309.00071, as its reference implementation
    has it) over the ``rotary_dim / 2`` frequency pairs: ``f_i = theta^(-2i
    / rotary_dim)`` is kept where pair ``i`` turns at least ``beta_fast``
    times inside the original context, divided by ``factor`` where it turns
    at most ``beta_slow`` times, and blended along a linear ramp over the
    pairs between the two (their indices rounded outwards).  A float32
    numpy vector: made at trace time, a constant of the program."""
    half = rotary_dim // 2

    def pair_of(turns):     # the (fractional) pair that makes ``turns``
        return rotary_dim * math.log(original_max_position_embeddings
                                     / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    kept = 1.0 - np.clip((np.arange(half, dtype=np.float32) - low)
                         / (high - low), 0.0, 1.0)
    freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    return (freq / factor * (1.0 - kept) + freq * kept).astype(np.float32)


def rope_part(x, positions, freq, scale: float = 1.0):
    """Rotary positions on the first ``2 len(freq)`` of ``x``'s (B, S, H, D)
    last axis, at ``positions`` (S,): inside that part the pair ``(x_i, x_{i
    + len(freq)})`` is turned by ``pos * freq_i`` (``_turn``'s convention,
    the cosine and sine times ``scale``), and the rest of the head passes
    through.  ``rope`` where the part is the whole head, ``freq`` is
    ``theta^(-i / (D/2))`` and ``scale`` 1."""
    return _turn_part(x, _angle(positions, freq), scale)


def mrope(x, positions, theta: float, sections):
    """Three-part rotary positions on ``x`` (B, S, H, D) at ``positions``
    (3, S): the ``D/2`` frequency pairs are cut into ``sections`` (their
    sum), and pair ``i`` of section ``r`` turns by ``positions[r] *
    theta^(-2i/D)``: ``rope`` where the three rows are equal."""
    return _turn(x, _mrope_angle(positions, theta, sections,
                                 x.shape[-1] // 2))


def _mrope_angle(positions, theta: float, sections, half: int):
    """``mrope``'s angles (S, D/2) at ``positions`` (3, S)."""
    if len(sections) != 3 or sum(sections) != half:
        raise ValueError(f"sections {sections} do not cut {half} pairs in "
                         f"three")
    row = np.repeat(np.arange(3), sections)               # (D/2,)
    return positions.astype(F32)[row, :].T * _rope_freq(half, theta)[None, :]


# -- heads of whole lane tiles: the layer's elementwise work where the
# -- kernels' operands lie (PERF.md section 6, PR 45) -------------------------

def _pairs(d: int, half: int):
    """The (d, d) 0/1 matrix that brings each turned lane its pair: column
    ``l`` has its one in row ``l + half`` below ``half`` and in row ``l -
    half`` from there to ``2 half``; the lanes past the turned part get
    nothing (their sine is 0)."""
    p = np.zeros((d, d), np.float32)
    lanes = np.arange(half)
    p[lanes + half, lanes] = 1.0
    p[lanes, lanes + half] = 1.0
    return p


def _turn_lanes(x5, cos, sin, half: int):
    """``x5`` (B, S / 8, H, 8, D), ``cos`` and ``sin`` (S / 8, 1, 8, D)
    float32 tables over a head's lanes (``_lane_tables``): each lane times
    its cosine plus its pair's lane times its signed sine, in float32.  The
    pairs come through the matrix unit, ``x5`` times a permutation (exact:
    one term a sum), not through slices of the lanes, which XLA would cut
    out as arrays of their own in another layout."""
    d = x5.shape[-1]
    # as one (rows, D) x (D, D) product over the view's rows as they lie
    pair = jnp.dot(x5.reshape(-1, d), _pairs(d, half).astype(x5.dtype),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=F32).reshape(x5.shape)
    return x5.astype(F32) * cos + pair * sin


def _lane_tables(angle, scale: float, d: int):
    """``angle`` (S, half) -> the cosine and the signed sine of every lane
    of a head of ``d``, (S / 8, 1, 8, d) float32 each, as ``_turn_lanes``
    reads them: ``(cos, cos, 1 ...)`` and ``(-sin, sin, 0 ...)``, the turned
    part's times ``scale`` (``_turn``'s arithmetic, lane by lane)."""
    s, half = angle.shape
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    rest = d - 2 * half
    shape = lambda t: t.reshape(s // 8, 1, 8, d)  # noqa: E731
    return (shape(jnp.concatenate([cos, cos, jnp.ones((s, rest), F32)], -1)),
            shape(jnp.concatenate([-sin, sin, jnp.zeros((s, rest), F32)],
                                  -1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 4))
def turn_heads(x3, heads: int, cos, sin, half: int):
    """The rotary turn of ``x3`` (B, S, H * D) on its by-head view
    (``attention.by_head``): the same shape and type back, float32 inside,
    the tables from ``_lane_tables``.  Its transpose is the turn by the
    opposite angle, written out (``custom_vjp``): the backward pass reads
    the cotangent and the tables, keeps nothing of the forward's, and
    goes through the view the same way round."""
    return from_heads(_turn_lanes(by_head(x3, heads), cos, sin, half).astype(
        x3.dtype))


def _turn_heads_fwd(x3, heads, cos, sin, half):
    return turn_heads(x3, heads, cos, sin, half), (cos, sin)


def _turn_heads_bwd(heads, half, res, dy3):
    cos, sin = res
    return turn_heads(dy3, heads, cos, -sin, half), None, None


turn_heads.defvjp(_turn_heads_fwd, _turn_heads_bwd)


def _gates_by_head(g):
    """(B, S, H) -> (B, S / 8, H, 8, 1): a value a head and position beside
    the by-head view."""
    b, s, h = g.shape
    return g.reshape(b, s // 8, 8, h).transpose(0, 1, 3, 2)[..., None]


#: the columns of the ``counters`` an attention layer with an index sows for
#: each row of the batch: the pairs selected and the causal pairs (``sum
#: min(t + 1, k)`` of ``sum (t + 1)``), the tiles that ran and the tiles
#: ``causal`` alone would run in the forward kernel and in the backward, and
#: the layer's KL term in millionths
DSA_COUNTERS = ("selected_pairs", "causal_pairs", "fwd_tiles_run",
                "fwd_tiles_causal", "bwd_tiles_run", "bwd_tiles_causal",
                "kl_millionths")

#: the columns of the ``counters`` an attention layer under a window sows
#: for each row of the batch, all static (``WindowMask``'s sums): the pairs
#: a head's band needs, the pairs of the tiles the forward kernel and the
#: backward kernel run, and each kernel's tiles run beside the tiles
#: ``causal`` alone would run with the same tiles
WIN_COUNTERS = ("needed_pairs", "fwd_pairs_run", "bwd_pairs_run",
                "fwd_tiles_run", "fwd_tiles_causal", "bwd_tiles_run",
                "bwd_tiles_causal")


class RotaryAttention(linen.Module):
    """Grouped-query attention with a learned RMSNorm on each head's query
    and key, then rotary positions.  Under ``mask``, a
    ``BlockDiffusionMask`` over ``[noisy ; clean]``, both halves sit at
    positions ``0 .. half-1`` unless ``positions`` says otherwise.  Without
    one it is causal attention (``flash_attention(causal=True)``) at
    ``positions`` (S,) or (3, S) (``mrope_section`` cuts the pairs), default
    ``0 .. S-1``; with ``indexer`` (``heads``, ``head_dim``, ``top_k``,
    ``q_chunk``, ``kv_chunk``, ``kl_weight``) each query reads only the keys
    its learned index picks (the module's docstring has the equations).

    What a layer of a decoder with two kinds of attention sets (all off by
    default): ``window`` (each query reads the ``window`` keys up to its
    own: ``WindowMask`` in both flash kernels, and the layer's static counts
    under ``("counters", "win")``, ``WIN_COUNTERS``); ``rotary_dim`` (only
    the head's first ``rotary_dim`` dims turn) and ``yarn``
    (``yarn_frequencies``' arguments beside ``rope_theta``, and
    ``attention_factor`` on the cosine and sine); ``gate`` (``g =
    sigmoid(x Wg)``, one a head, from the layer's normed input ``x``,
    multiplied into the attention's output before ``o_proj``; module
    ``gate_proj`` under the scope ``gate``); ``qk_norm`` False (no norm on
    the heads' queries and keys); ``kind``, a ``jax.named_scope`` around
    the whole layer (``attn/window/...``, ``attn/full/...``), so that an
    operation's path tells the kinds apart."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    mask: Optional[BlockDiffusionMask] = None
    rope_theta: float = 1e6
    eps: float = 1e-6
    attention: Optional[str] = "flash"   # 'flash' (Pallas) | None (plain)
    dtype: Any = F32
    indexer: Any = None                  # a dict, or its items
    mrope_section: Optional[tuple] = None
    window: Optional[int] = None
    rotary_dim: Optional[int] = None     # None: the whole head
    yarn: Any = None                     # a dict, or its items
    gate: bool = False
    qk_norm: bool = True
    kind: Optional[str] = None

    @linen.compact
    def __call__(self, x, positions=None):
        with jax.named_scope(self.kind) if self.kind \
                else contextlib.nullcontext():
            b, s, d = x.shape
            h, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
            dense = lambda n, name: linen.Dense(  # noqa: E731
                n, use_bias=False, dtype=self.dtype, name=name)
            q, k, v = checkpoint_name(
                (dense(h * hd, "q_proj")(x), dense(kv * hd, "k_proj")(x),
                 dense(kv * hd, "v_proj")(x)), "attn_qkv")
            # a head of whole lane tiles: q, k, v and the kernels' output
            # stay the projections' (B, S, H * D) arrays, which the flash
            # kernels read and write as they are, and the norm, the turn and
            # the gate work on their by-head view (``by_head``)
            tiled = self.attention == "flash" and lane_tiled(hd, s)
            if not tiled:
                q = q.reshape(b, s, h, hd)
                k, v = k.reshape(b, s, kv, hd), v.reshape(b, s, kv, hd)
            if self.qk_norm:
                norm = lambda name: RMSNorm(  # noqa: E731
                    self.eps, self.dtype, name=name)
                if tiled:
                    q = from_heads(norm("q_norm")(by_head(q, h)))
                    k = from_heads(norm("k_norm")(by_head(k, kv)))
                else:
                    q, k = norm("q_norm")(q), norm("k_norm")(k)
            mask = self.mask
            if positions is None:
                positions = jnp.arange(s) % (s if mask is None else mask.half)
            with jax.named_scope("rope"):
                if tiled:
                    angle, scale = self._angles(positions)
                    tables = _lane_tables(angle, scale, hd)
                    q, k = (turn_heads(t, n, *tables, angle.shape[1])
                            for t, n in ((q, h), (k, kv)))
                else:
                    q, k = (_turn_part(t, *self._angles(positions))
                            for t in (q, k))
            # k and v go on with their ``kv`` heads: the kernels' index maps
            # read the head that serves a query head (and its gradient comes
            # back summed over them); only ``_plain`` spreads them
            if self.indexer is not None:
                out = self._sparse(x, q, k, v)
            elif self.window is not None:
                out = self._window(q, k, v)
            elif mask is None:
                out = self._causal(q, k, v)
            elif self.attention == "flash":
                out = self._flash(q, k, v)
            else:
                out = self._plain(q, k, v)
            if self.gate:
                with jax.named_scope("gate"):
                    g = jax.nn.sigmoid(dense(h, "gate_proj")(x).astype(F32))
                    if tiled:
                        out = from_heads((by_head(out, h).astype(F32)
                                          * _gates_by_head(g)).astype(
                                              out.dtype))
                    else:
                        out = (out.astype(F32) * g[..., None]).astype(
                            out.dtype)
            return checkpoint_name(
                dense(d, "o_proj")(out.reshape(b, s, h * hd)), "attn_out")

    def _angles(self, positions):
        """(the angle of each turned pair at each position, (S, pairs); the
        scale on cosine and sine): what the layer's rotary rule turns by,
        ``mrope``'s, ``rope``'s or ``rope_part``'s."""
        if positions.ndim == 2:
            return _mrope_angle(positions, self.rope_theta,
                                self.mrope_section, self.head_dim // 2), 1.0
        if self.yarn is None and self.rotary_dim is None:
            return _angle(positions, _rope_freq(self.head_dim // 2,
                                                self.rope_theta)), 1.0
        freq, scale = self._frequencies()
        return _angle(positions, freq), scale

    def _frequencies(self):
        """(the frequency of each turned pair, the scale on cosine and
        sine) of a layer with ``rotary_dim`` or ``yarn``."""
        part = self.rotary_dim or self.head_dim
        if self.yarn is None:
            return self.rope_theta ** (
                -np.arange(part // 2, dtype=np.float32) / (part // 2)), 1.0
        yarn = dict(self.yarn)
        scale = yarn.pop("attention_factor", 1.0)
        return yarn_frequencies(part, self.rope_theta, **yarn), scale

    def _window(self, q, k, v):
        """Causal attention over the ``window`` keys up to the query's own,
        and the rule's static counts."""
        rule, s = WindowMask(self.window), q.shape[1]
        out = self._causal(q, k, v, rule)
        if self.attention != "flash":       # no tiles to count
            return out
        padded = s + (-s) % DEFAULT_BLOCK
        args = (padded, padded, self.head_dim,
                jnp.dtype(self.dtype).itemsize, rule)
        counts, tiles = [rule.pairs(s)], []
        for bq, bk in (forward_tiles(*args), backward_tiles(*args)):
            run = rule.tiles_run(padded, bq, bk)
            counts.append(run * bq * bk)
            tiles += [run, rule.causal_tiles(padded, bq, bk)]
        self.sow("counters", "win", jnp.broadcast_to(
            jnp.asarray(counts + tiles, jnp.int32),
            (q.shape[0], len(WIN_COUNTERS))))
        return out

    def _sparse(self, x, q, k, v):
        """Attention over the keys the index picks, and the index's own
        term and counts."""
        b, s = x.shape[:2]
        if s % DEFAULT_BLOCK:
            raise ValueError(f"{s} positions are not whole tiles of "
                             f"{DEFAULT_BLOCK}")
        ix = dict(self.indexer)
        hi, di = ix["heads"], ix["head_dim"]
        scale = self.head_dim ** -0.5
        with jax.named_scope("indexer"):
            a = jax.lax.stop_gradient(x)
            dense = lambda n, name: linen.Dense(  # noqa: E731
                n, use_bias=False, dtype=self.dtype, name=name)
            q_i = dense(hi * di, "index_q")(a).reshape(b, s, hi, di)
            k_i = linen.LayerNorm(epsilon=self.eps, dtype=self.dtype,
                                  name="index_k_norm")(dense(di, "index_k")(a))
            w = dense(hi, "index_w")(a).astype(F32) * hi ** -0.5
        # its own scopes inside: ``indexer`` (the scores), ``select``
        selection, index_lse, picked = checkpoint_name(
            sparse_index.select_keys(
                q_i, k_i, w, ix["top_k"], q_chunk=ix.get("q_chunk", 512),
                kv_chunk=ix.get("kv_chunk", 512)), "dsa_selection")
        if self.attention == "flash":
            out, lse = flash_attention(
                q, k, v, causal=True, mask=SelectedKeysMask(),
                selection=selection, return_lse=True, heads=self._heads(q))
            # the KL term reads each head's queries and keys
            q = q.reshape(b, s, self.num_heads, self.head_dim)
            k = k.reshape(b, s, self.num_kv_heads, self.head_dim)
        else:
            out, lse = self._plain(
                q, k, v, unpack_selection(selection.by_query, s)
                & jnp.tril(jnp.ones((s, s), bool)), with_lse=True)
        with jax.named_scope("indexer_kl"):
            kl = sparse_index.indexer_kl(
                q_i, k_i, w, q, k, lse, index_lse, selection, scale=scale,
                chunk=ix.get("q_chunk", 512))
            self.sow("aux_loss", "indexer_kl",
                     ix.get("kl_weight", 1.0) * jnp.mean(kl))
        with jax.named_scope("select"):
            itemsize = jnp.dtype(self.dtype).itemsize
            tiles = [n for tile in (forward_tiles, backward_tiles)
                     for n in SelectedKeysMask.tiles(
                         selection.blocks, *tile(s, s, self.head_dim,
                                                 itemsize), causal=True)]
            self.sow("counters", "dsa", jnp.stack(
                [picked, jnp.full((b,), s * (s + 1) // 2, jnp.int32), *tiles,
                 jnp.round(jax.lax.stop_gradient(kl) * 1e6).astype(
                     jnp.int32)], axis=1))
        return out

    def _causal(self, q, k, v, rule=None):
        """Causal attention, under ``rule`` (a ``WindowMask``) where one is
        given."""
        if self.attention != "flash":
            s, pos = q.shape[1], jnp.arange(q.shape[1])
            return self._plain(
                q, k, v, jnp.tril(jnp.ones((s, s), bool)) if rule is None
                else rule.allowed(pos[:, None], pos[None, :]))
        # padded to the tile: a padded key lies after every real query
        s, pad = q.shape[1], (-q.shape[1]) % DEFAULT_BLOCK
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                   for t in (q, k, v))
        return flash_attention(q, k, v, causal=True, mask=rule,
                               heads=self._heads(q))[:, :s]

    def _heads(self, q):
        """``flash_attention``'s ``heads``: the query heads where ``q`` is
        the projection's (B, S, H * D) array, None where it is (B, S, H,
        D)."""
        return self.num_heads if q.ndim == 3 else None

    def _flash(self, q, k, v):
        s, mask = q.shape[1], self.mask
        # each half padded to the tile: a padded key lies in a block after
        # every real query's, so the rule hides it (half is whole blocks)
        pad = (-mask.half) % DEFAULT_BLOCK
        if pad:
            halves = lambda t: jnp.pad(  # noqa: E731
                t.reshape((t.shape[0], 2, mask.half) + t.shape[2:]),
                ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)).reshape(
                    (t.shape[0], 2 * (mask.half + pad)) + t.shape[2:])
            q, k, v = halves(q), halves(k), halves(v)
        out = flash_attention(
            q, k, v, mask=BlockDiffusionMask(mask.half + pad, mask.block),
            heads=self._heads(q))
        if pad:
            out = out.reshape((out.shape[0], 2, mask.half + pad)
                              + out.shape[2:])[:, :, :mask.half].reshape(
                                  (out.shape[0], s) + out.shape[2:])
        return out

    def _plain(self, q, k, v, allowed=None, with_lse=False):
        """A dense masked softmax in float32: the kernels' oracle, with
        each key-value head spread over the query heads it serves.
        ``allowed`` (S, S) or (B, S, S), the block-diffusion rule's where
        None."""
        s = q.shape[1]
        k, v = (jnp.repeat(t, q.shape[2] // t.shape[2], axis=2)
                for t in (k, v))
        if allowed is None:
            pos = jnp.arange(s)
            allowed = self.mask.allowed(pos[:, None], pos[None, :])
        if allowed.ndim == 3:
            allowed = allowed[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(F32),
                            k.astype(F32)) * self.head_dim ** -0.5
        scores = jnp.where(allowed, scores, NEG_INF)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                         v.astype(F32)).astype(q.dtype)
        return (out, jax.nn.logsumexp(scores, axis=-1)) if with_lse else out


class ShortConv(linen.Module):
    """A gated short convolution where an attention layer would stand (the
    LFM2 family's mixer, ``Lfm2ShortConv``): from the layer's normed input
    ``a`` (B, T, d)::

        [B | C | u] = a Win                        Win d x 3d, three equal parts in this order
        c_t = sum_j w_j * (B * u)_{t - taps + 1 + j}    depthwise over d channels, zero before position 0
        y = (C * c) Wout                           Wout d x d

    No bias, no activation, no positions (``positions`` is taken and
    ignored, so that a block calls either mixer alike).  Modules
    ``in_proj`` and ``out_proj``, the taps ``conv_kernel`` (``taps``, d) with
    ``conv_kernel[taps - 1]`` on the current position; ``jax.named_scope``s
    ``gate_in`` (``B * u``), ``conv1d`` (the taps, ``ops.ssm.causal_conv1d``:
    shifted multiply-adds that XLA fuses) and ``gate_out`` (``C * c``) tell
    the part that is no matrix product from the two that are."""
    taps: int = 3
    dtype: Any = F32

    @linen.compact
    def __call__(self, x, positions=None):
        d = x.shape[-1]
        dense = lambda n, name: linen.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        gate_in, gate_out, u = jnp.split(checkpoint_name(
            dense(3 * d, "in_proj")(x), "conv_in_proj"), 3, axis=-1)
        kernel = self.param("conv_kernel", linen.initializers.normal(
            self.taps ** -0.5), (self.taps, d), F32)
        with jax.named_scope("gate_in"):
            u = gate_in * u
        with jax.named_scope("conv1d"):
            u = ssm.causal_conv1d(u, kernel)
        with jax.named_scope("gate_out"):
            u = gate_out * u
        return dense(d, "out_proj")(u)


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
#:               indices jax derives from those two for the two gathers;
#:               and E x 4, the selection's load over the E router outputs,
#:               where the objective holds the load-balancing term)
#:   moe_up      R x I x 4          float32, as the grouped product returns it
#: and under an index (``RotaryAttention(indexer=...)``), for H_I index heads
#: of D_I:
#:   dsa_selection     2 x T x T / 8 + (T / 128)^2 + T x 4   the two bitmaps
#:               (33.5 MB each at 16,384 positions), the 128 x 128 blocks
#:               that hold a pair, each row's index log-sum-exp
#:   indexer_kl_grads  T x (H_I x D_I + D_I + H_I) x 4   what the KL term's
#:               forward pass computed for its backward (72 MB): with it
#:               held, the probabilities are made once a step
#: and under a band (``RotaryAttention(window=...)``: the kernels' results
#: carry names of their own there):
#:   flash_win_out     T x H x D x c   the windowed kernel's output (268 MB a
#:               layer at 16,384 positions of 64 heads; 8 ms a layer spared)
#:   flash_win_lse     T x H x 4    its log-sum-exp
#: and in a layer whose mixer is a gated short convolution (``ShortConv``):
#:   conv_in_proj      T x 3 d x c    in_proj's output, the three parts the
#:               gates and the taps read (201 MB a layer at 16,384 positions
#:               of width 2,048; PERF.md section 6, PR 43)
#: Named and not kept: shared_gate and shared_up (T x I x c each: the
#: shared expert's two products), mlp_gate and mlp_up (``GatedMLP``'s, in a
#: dense layer: 268 MB each at 16,384 positions of width 8,192 for 3.5 ms),
#: moe_gate (as moe_up: the pair fits the chip with
#: under half a gigabyte to spare) and attn_qkv (T x (H + 2 KV) x D x c,
#: the three projections' outputs: fewest milliseconds a gigabyte).
#: PERF.md section 6, PR 35, has each name's measured milliseconds and
#: bytes, and what the chip has room for.
SAVED = ("flash_out", "flash_lse", "attn_out", "moe_route", "moe_up",
         "dsa_selection", "indexer_kl_grads", "flash_win_out",
         "flash_win_lse", "conv_in_proj")


class RoutedBlock(linen.Module):
    """One layer: attention (module ``attn``) or, where ``conv`` is given,
    a gated short convolution in its place (``ShortConv``, module
    ``conv``), then the routed experts (module ``moe``) or, where ``mlp`` is
    given, a dense gated feed-forward in their place
    (``hybrid_lm.GatedMLP``, module ``mlp``), each on the RMSNorm of the
    stream and added back."""
    attn: Any                 # kwargs of RotaryAttention
    moe: Any                  # kwargs of RoutedExperts
    eps: float = 1e-6
    dtype: Any = F32
    mlp: Any = None           # kwargs of GatedMLP: a dense layer
    conv: Any = None          # kwargs of ShortConv: no attention

    @linen.compact
    def __call__(self, x, positions=None):
        h = RMSNorm(self.eps, self.dtype, name="input_norm")(x)
        if self.conv is not None:
            h = ShortConv(dtype=self.dtype, name="conv",
                          **dict(self.conv))(h, positions)
        else:
            h = RotaryAttention(eps=self.eps, dtype=self.dtype, name="attn",
                                **dict(self.attn))(h, positions)
        x = x + h.astype(x.dtype)
        h = RMSNorm(self.eps, self.dtype, name="post_norm")(x)
        if self.mlp is not None:
            h = GatedMLP(dtype=self.dtype, name="mlp", **dict(self.mlp))(h)
        else:
            h = RoutedExperts(dtype=self.dtype, name="moe",
                              **dict(self.moe))(h)
        return x + h.astype(x.dtype)


def _items(value):
    """A dict (of dicts and lists) as sorted items, hashable: what a
    rematerialised block's attributes have to be."""
    if isinstance(value, dict):
        return tuple(sorted((k, _items(v)) for k, v in value.items()))
    return tuple(value) if isinstance(value, list) else value


class RoutedLM(linen.Module):
    """Under ``objective="block_diffusion"``: ``tokens`` ``[xt ; x0]`` (B,
    2 L) -> float32 logits (B, L, V) of the noisy half, under the mask of
    blocks of ``block_length``.  Under ``"causal"``: ``tokens`` (B, T) ->
    float32 logits (B, T, V) of every position, under the causal mask, at
    ``positions`` (T,) or (3, T) (default ``0 .. T-1``, three equal rows
    where ``mrope_section`` is set); with ``indexer`` (``RotaryAttention``'s)
    each layer attends to the keys its index picks.  The defaults
    are a small model; a published one passes its own ``config.json``'s
    numbers (``benchmark/sdar_drivers.py``, ``benchmark/keye_drivers.py``).
    ``held_experts`` and ``buffer_rows`` are ``RoutedExperts``' ``held`` and
    ``buffer_rows``; ``scoring``, ``routed_scale`` and
    ``shared_intermediate`` its switches of those names.

    **The per-layer record.**  ``layers`` None builds ``num_layers`` layers
    alike from the fields above, as ever.  Else it holds one dict a layer,
    and a layer takes from its own what the dict names and the rest from
    the fields: ``attention`` (``"full"``: causal; ``"window"``: the
    ``window`` keys up to the query's own; the kind is also the layer's
    ``RotaryAttention.kind`` scope; ``"conv"``: no attention, a gated short
    convolution of ``conv_taps`` taps, ``ShortConv``, module ``conv``, which
    takes none of the record's other attention keys), ``num_heads``,
    ``rope`` (a dict:
    ``rope_theta``, and optionally ``rotary_dim`` and ``yarn``, see
    ``RotaryAttention``), ``mlp`` (``"routed"``, or ``"dense"``: a
    ``GatedMLP`` of ``dense_intermediate`` where the experts stand, which
    sows no counters).  ``attn_gate`` and ``qk_norm`` are every layer's
    (``RotaryAttention``'s ``gate`` and ``qk_norm``).  Causal objective
    only.

    ``tie_word_embeddings``: the head is the table (``logits = rms(x_last)
    Table^T``; no parameter ``lm_head``, and the table's gradient sums both
    uses).  ``selection_bias``, ``bias_update_speed`` and ``router_norm_eps``
    are ``RoutedExperts``' ``selection_bias``, ``bias_update_speed`` and
    ``norm_eps``: the routed layers are balanced by a bias on the scores for
    the selection only, a variable of the ``batch_stats`` collection that a
    training step moves, and such a decoder usually sets ``aux_loss_coef``
    0."""
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
    objective: str = "block_diffusion"      # or 'causal'
    indexer: Any = None                     # RotaryAttention's, a dict
    mrope_section: Optional[tuple] = None
    layers: Optional[tuple] = None          # one dict a layer, see above
    window: Optional[int] = None
    dense_intermediate: Optional[int] = None
    attn_gate: bool = False
    qk_norm: bool = True
    scoring: str = "softmax"
    routed_scale: float = 1.0
    shared_intermediate: Optional[int] = None
    conv_taps: int = 3
    tie_word_embeddings: bool = False
    selection_bias: bool = False
    bias_update_speed: float = 0.001
    router_norm_eps: float = 0.0
    saved_names = SAVED     # no field: the policy's list, and the gauge's

    @linen.compact
    def __call__(self, tokens, training: bool = True, positions=None):
        b, s = tokens.shape
        causal = self.objective == "causal"
        if not causal and self.objective != "block_diffusion":
            raise ValueError(f"no objective {self.objective!r}")
        if not causal and (s % 2 or self.indexer is not None
                           or self.layers is not None):
            raise ValueError(f"[xt ; x0] has an even length, not {s}, and "
                             f"no index and no layers that differ")
        if self.layers is not None and len(self.layers) != self.num_layers:
            raise ValueError(f"{len(self.layers)} records for "
                             f"{self.num_layers} layers")
        mask = None if causal else BlockDiffusionMask(s // 2,
                                                      self.block_length)
        if causal and positions is None and self.mrope_section is not None:
            positions = jnp.broadcast_to(jnp.arange(s), (3, s))   # text
        attn = dict(num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                    head_dim=self.head_dim, rope_theta=self.rope_theta,
                    mask=mask, attention=self.attention)
        if causal:
            attn.update(
                mrope_section=self.mrope_section and tuple(self.mrope_section),
                indexer=self.indexer and tuple(sorted(
                    dict(self.indexer).items())))
        # the switches enter a layer's arguments only where they are set:
        # a decoder without them builds the blocks it always built
        if self.attn_gate:
            attn["gate"] = True
        if not self.qk_norm:
            attn["qk_norm"] = False
        moe = dict(num_experts=self.num_experts,
                   top_k=self.num_experts_per_tok,
                   intermediate=self.moe_intermediate,
                   held=self.held_experts, buffer_rows=self.buffer_rows,
                   aux_weight=self.aux_loss_coef)
        if self.scoring != "softmax":
            moe["scoring"] = self.scoring
        if self.routed_scale != 1.0:
            moe["routed_scale"] = self.routed_scale
        if self.shared_intermediate:
            moe["shared_intermediate"] = self.shared_intermediate
        if self.router_norm_eps:
            moe["norm_eps"] = self.router_norm_eps
        if self.selection_bias:
            moe.update(selection_bias=True,
                       bias_update_speed=self.bias_update_speed)
        init = linen.initializers.normal(0.02)
        table = self.param("embedding", init,
                           (self.vocab_size, self.embed_dim), F32)
        with jax.named_scope("embed"):
            x = jnp.take(table, tokens, axis=0).astype(self.dtype)
        block_cls = linen.remat(
            RoutedBlock, policy=jax.checkpoint_policies.save_only_these_names(
                *self.saved_names)) if self.remat else RoutedBlock
        for i in range(self.num_layers):
            mine, mlp = attn, None
            if self.layers is not None:
                mine, mlp = self._layer(dict(self.layers[i]), attn)
            # no attention's arguments: the layer's mixer is the convolution
            conv = _items({"taps": self.conv_taps}) if mine is None else None
            x = block_cls(_items(mine), _items(moe), self.rms_norm_eps,
                          self.dtype, mlp, conv, name=f"block{i}")(
                              x, positions)
        if not causal:
            x = x[:, :mask.half]        # the head over the noisy half only
        x = RMSNorm(self.rms_norm_eps, self.dtype, name="final_norm")(x)
        head = table if self.tie_word_embeddings else self.param(
            "lm_head", init, (self.vocab_size, self.embed_dim), F32)
        with jax.named_scope("lm_head"):
            return jnp.einsum("bsd,vd->bsv", x, head.astype(self.dtype),
                              preferred_element_type=F32)

    def _layer(self, record, attn):
        """One layer's record over the decoder's own attention arguments ->
        (that layer's, or None where its mixer is no attention; its dense
        feed-forward's or None)."""
        kind = record.pop("attention", None)
        if kind not in (None, "full", "window", "conv"):
            raise ValueError(f"no attention {kind!r}")
        mlp = record.pop("mlp", "routed")
        if mlp not in ("routed", "dense"):
            raise ValueError(f"no feed-forward {mlp!r}")
        mlp = _items({"intermediate": self.dense_intermediate}) \
            if mlp == "dense" else None
        if kind == "conv":
            if record:
                raise ValueError(f"a conv layer's record has no "
                                 f"{sorted(record)}")
            return None, mlp
        mine = {**attn, **record.pop("rope", {})}
        if "num_heads" in record:
            mine["num_heads"] = record.pop("num_heads")
        if record:
            raise ValueError(f"a layer's record has no {sorted(record)}")
        if kind is not None:
            mine["kind"] = kind
        if kind == "window":
            mine["window"] = self.window
        return mine, mlp
