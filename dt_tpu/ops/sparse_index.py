"""A learned index over keys: the scores, the exact top-k selection and the
term that trains it (DeepSeek-V3.2-Exp's sparse attention: lightning
indexer, token-level top-k, the sparse-stage objective).

Beyond the reference's long-context ceiling (the cuDNN fused RNN,
``src/operator/cudnn_rnn-inl.h:1``; SURVEY.md §5.7: no attention anywhere in
the 2018 tree): an attention layer that reads only the ``top_k`` keys a small
scorer picks for each query.  With ``a~`` the stop-gradient of the layer's normed input, ``H_I``
index heads of ``D_I`` over one index key a position::

    qI = (a~ WqI) as [T, H_I, D_I] ;  kI = layer_norm(a~ WkI) as [T, D_I]
    w  = a~ Ww * H_I^-0.5          as [T, H_I]
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(D_I)      s <= t
    S_t = { s <= t : I[t, s] >= the top_k-th largest of row t }     (every
          s <= t while t < top_k; keys tied with the top_k-th are all kept)
    KL_t = sum_{s in S_t} pbar[t, s] (log pbar[t, s] - log softmax_S I[t, .])
    pbar[t, s] = mean_h p_h[t, s]    the main attention's own probabilities

Three stages, each under a scope of its own in the model
(``models/routed_lm.py``: ``indexer``, ``select``, ``indexer_kl``):

``index_scores`` is the chunk's scores, operands as stored (bfloat16 in the
benchmark's cells) with float32 accumulation, the weighted sum in float32.

``select_keys`` never holds a ``T x T`` float array: for ``q_chunk`` queries
at a time it scores the keys up to the chunk's last position ``kv_chunk`` at
a time, finds each row's ``top_k``-th largest score exactly by 32 counting
passes over the bits of the floats' order-preserving integer keys (a fixed
count: the cost does not follow the data, where a loop to convergence or a
partial sort would), and packs the chosen pairs into the ``Selection`` the
flash kernels read (``ops/pallas/attention.py``), bits over keys and bits
over queries, with each row's log-sum-exp of its chosen scores.

``indexer_kl`` is the mean over positions of ``KL_t``, tile by tile over the
causal pairs: the main attention's probabilities from ``q``, ``k`` and the
flash kernel's saved log-sum-exp, summed over heads on the selected pairs,
meet the index scores in the same tile, and only a row's KL and what its
gradient needs leave it.  The value reads ``q``, ``k`` and the two
log-sum-exps as constants, so its derivative is ``softmax_S I - pbar`` with
respect to the scores and nothing else; the forward pass computes the
gradients for ``qI``, ``kI`` and ``w`` beside the value (a ``custom_vjp``
whose residuals are those three, named ``indexer_kl_grads``), so the
probabilities are made once a step and no loop has to be differentiated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from dt_tpu.ops.pallas.attention import (DEFAULT_BLOCK, SEL_GROUP, Selection,
                                         pack_columns, pack_rows,
                                         selected_blocks, unpack_tile)

F32 = jnp.float32


def index_scores(q_i, k_i, w, with_products: bool = False):
    """``q_i`` (R, H_I, D_I), ``k_i`` (N, D_I), ``w`` (R, H_I) -> ``I`` (R, N)
    float32: ``sum_j w[:, j] relu(q_i[:, j] . k_i) / sqrt(D_I)``; with
    ``with_products`` also the products ``z`` (H_I, R, N) before the relu."""
    z = jnp.einsum("rjd,nd->jrn", q_i, k_i, preferred_element_type=F32)
    scores = jnp.einsum("jrn,rj->rn", jax.nn.relu(z), w.astype(F32)) \
        * q_i.shape[-1] ** -0.5
    return (scores, z) if with_products else scores


def sortable_key(x):
    """float32 -> uint32 whose unsigned order is the floats' (``-0.0`` as
    ``0.0``)."""
    u = lax.bitcast_convert_type(x.astype(F32) + 0.0, jnp.uint32)
    return jnp.where(u >> 31 != 0, ~u, u | jnp.uint32(1 << 31))


def kth_largest_key(keys, k: int):
    """``keys`` (R, N) uint32 -> (R,) uint32: each row's ``k``-th largest,
    or 0 where the row has fewer than ``k``: the largest value that ``k``
    of the row's keys reach, found a bit at a time from the top in 32
    counting passes."""
    def narrow(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        reach = jnp.sum(keys >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(reach >= k, cand, prefix)
    return lax.fori_loop(0, 32, narrow,
                         jnp.zeros((keys.shape[0],), jnp.uint32))


def _select_one(q_i, k_i, w, top_k: int, causal: bool, q_chunk: int,
                kv_chunk: int):
    """One sequence: ``q_i`` (T, H_I, D_I), ``k_i`` (T, D_I), ``w`` (T, H_I)
    -> (by_query (G, T, 128), by_key (G, T, 128), index_lse (T,), selected
    pairs)."""
    t = q_i.shape[0]
    groups = -(-t // SEL_GROUP)
    by_query, lses, picked = [], [], 0
    by_key = jnp.zeros((groups, t, DEFAULT_BLOCK), jnp.int32)
    for first in range(0, t, q_chunk):
        # the keys up to the chunk's last query, kv_chunk at a time
        n = first + q_chunk if causal else t
        rows = slice(first, first + q_chunk)
        with jax.named_scope("indexer"):
            scores = lax.map(
                lambda kc: index_scores(q_i[rows], kc, w[rows]),
                k_i[:n].reshape(n // kv_chunk, kv_chunk, -1))
            scores = jnp.moveaxis(scores, 0, 1).reshape(q_chunk, n)
        with jax.named_scope("select"):
            valid = jnp.ones((q_chunk, n), bool) if not causal else (
                jnp.arange(n)[None, :]
                <= first + jnp.arange(q_chunk)[:, None])
            scores = jnp.where(valid, scores, -jnp.inf)
            keys = sortable_key(scores)
            chosen = valid & (keys >= kth_largest_key(keys, top_k)[:, None])
            lses.append(jax.nn.logsumexp(
                jnp.where(chosen, scores, -jnp.inf), axis=1))
            picked = picked + jnp.sum(chosen, dtype=jnp.int32)
            words = pack_rows(chosen)                   # (G_n, q_chunk, 128)
            by_query.append(jnp.pad(
                words, ((0, groups - words.shape[0]), (0, 0), (0, 0))))
            g = first // SEL_GROUP
            by_key = by_key.at[g, :n].set(
                by_key[g, :n] | pack_columns(chosen.T, first))
    return jnp.concatenate(by_query, axis=1), by_key, \
        jnp.concatenate(lses), picked


def select_keys(q_i, k_i, w, top_k: int, *, causal: bool = True,
                q_chunk: int = 512, kv_chunk: int = 512):
    """``q_i`` (B, T, H_I, D_I), ``k_i`` (B, T, D_I), ``w`` (B, T, H_I) ->
    (``Selection``, index_lse (B, T) float32, selected pairs (B,) int32):
    for each query the ``top_k`` keys (at or before it under ``causal``) of
    largest index score, exactly, as the kernels' bitmaps; each row's
    log-sum-exp of its chosen scores.  ``T`` is whole chunks, the chunks
    whole 128s, and a chunk of queries lies inside one group of 4,096.  No
    gradient passes (the inputs are taken as constants)."""
    t = q_i.shape[1]
    q_chunk, kv_chunk = min(q_chunk, t), min(kv_chunk, t)
    if t % q_chunk or q_chunk % kv_chunk or kv_chunk % DEFAULT_BLOCK \
            or SEL_GROUP % q_chunk:
        raise ValueError(f"{t} positions are not whole chunks of {q_chunk} "
                         f"queries and {kv_chunk} keys, those whole 128s")
    q_i, k_i, w = (lax.stop_gradient(x) for x in (q_i, k_i, w))
    # a sequence at a time, not under vmap: the scopes keep their names in
    # an operation's path (``select/``, not ``vmap(select)/``)
    by_query, by_key, index_lse, picked = (jnp.stack(x) for x in zip(*(
        _select_one(q_i[b], k_i[b], w[b], top_k, causal, q_chunk, kv_chunk)
        for b in range(q_i.shape[0]))))
    with jax.named_scope("select"):
        blocks = selected_blocks(by_query)
    return Selection(by_query, by_key, blocks), index_lse, picked


# ---------------------------------------------------------------------------
# the term that trains the index
# ---------------------------------------------------------------------------

def _kl_one(q_i, k_i, w, q, k, lse, index_lse, by_query, *, scale: float,
            chunk: int):
    """One sequence -> (mean KL, its gradient for q_i, k_i and w).  ``q``
    (T, H, D), ``k`` (T, KV, D): the main attention's, after the rotary
    turn; ``lse`` (H, T) its log-sum-exp over the selected keys."""
    t, heads, d = q.shape
    kv = k.shape[1]
    hi, di = q_i.shape[1:]
    n = t // chunk
    dtype = q_i.dtype

    def rows(x, i, axis=0):
        return lax.dynamic_slice_in_dim(x, i * chunk, chunk, axis)

    def tile(j, carry, i):
        kl, dq_c, dw_c, dk_all = carry
        qc, kc = rows(q, i).reshape(chunk, kv, heads // kv, d), rows(k, j)
        s = jnp.einsum("qgrd,kgd->grqk", qc, kc,
                       preferred_element_type=F32) * scale
        p = jnp.exp(s - rows(lse, i, 1).reshape(kv, heads // kv, chunk, 1))
        seen = unpack_tile(rows(by_query[(j * chunk) // SEL_GROUP], i),
                           j * chunk, chunk) != 0
        pbar = jnp.where(seen, jnp.sum(p, axis=(0, 1)) / heads, 0.0)
        qi_c, ki_c, w_c = rows(q_i, i), rows(k_i, j), rows(w, i).astype(F32)
        scores, z = index_scores(qi_c, ki_c, w_c, with_products=True)
        log_pi = scores - rows(index_lse, i)[:, None]
        kl = kl + jnp.sum(jnp.where(
            pbar > 0, pbar * (jnp.log(jnp.where(pbar > 0, pbar, 1.0))
                              - log_pi), 0.0), axis=1)
        d_scores = (jnp.where(seen, jnp.exp(log_pi), 0.0) - pbar) * di ** -0.5
        dw_c = dw_c + jnp.einsum("jqk,qk->qj", jax.nn.relu(z), d_scores)
        dz = jnp.where(z > 0, d_scores[None] * w_c.T[:, :, None],
                       0.0).astype(dtype)
        dq_c = dq_c + jnp.einsum("jqk,kd->qjd", dz, ki_c,
                                 preferred_element_type=F32)
        dk_t = jnp.einsum("jqk,qjd->kd", dz, qi_c, preferred_element_type=F32)
        dk_all = lax.dynamic_update_slice_in_dim(
            dk_all, rows(dk_all, j) + dk_t, j * chunk, 0)
        return kl, dq_c, dw_c, dk_all

    def chunk_rows(i, carry):
        kl_all, dq_all, dw_all, dk_all = carry
        kl, dq_c, dw_c, dk_all = lax.fori_loop(
            0, i + 1, functools.partial(tile, i=i),     # the causal tiles
            (jnp.zeros((chunk,), F32), jnp.zeros((chunk, hi, di), F32),
             jnp.zeros((chunk, hi), F32), dk_all))

        def put(whole, part):
            return lax.dynamic_update_slice_in_dim(whole, part, i * chunk, 0)

        return put(kl_all, kl), put(dq_all, dq_c), put(dw_all, dw_c), dk_all

    kl, dq, dw, dk = lax.fori_loop(
        0, n, chunk_rows,
        (jnp.zeros((t,), F32), jnp.zeros((t, hi, di), F32),
         jnp.zeros((t, hi), F32), jnp.zeros((t, di), F32)))
    return jnp.mean(kl), (dq / t, dk / t, dw / t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _kl(q_i, k_i, w, q, k, lse, index_lse, by_query, scale, chunk):
    return _kl_fwd(q_i, k_i, w, q, k, lse, index_lse, by_query, scale,
                   chunk)[0]


def _kl_fwd(q_i, k_i, w, q, k, lse, index_lse, by_query, scale, chunk):
    value, grads = jax.vmap(functools.partial(
        _kl_one, scale=scale, chunk=chunk))(
            q_i, k_i, w, q, k, lse, index_lse, by_query)
    # what a block's remat policy keeps (models/routed_lm.py SAVED): with
    # them held the backward pass does not make the probabilities again
    return value, checkpoint_name(grads, "indexer_kl_grads")


def _kl_bwd(scale, chunk, grads, ct):
    dq, dk, dw = grads
    return (dq * ct[:, None, None, None], dk * ct[:, None, None],
            dw * ct[:, None, None], None, None, None, None, None)


_kl.defvjp(_kl_fwd, _kl_bwd)


def indexer_kl(q_i, k_i, w, q, k, lse, index_lse, selection: Selection, *,
               scale: float, chunk: int = 512):
    """(B,) float32: for each sequence the mean over its positions of ``KL(
    pbar_t || softmax over S_t of I[t, .])``, of a causal selection.
    ``q_i`` (B, T, H_I, D_I), ``k_i`` (B, T, D_I), ``w`` (B, T, H_I) are the
    index's, and the only
    arguments a gradient reaches; ``q`` (B, T, H, D) and ``k`` (B, T, KV, D)
    the main attention's after the rotary turn, ``lse`` (B, H, T) its
    log-sum-exp over the selected keys (the flash kernel's), ``index_lse``
    and ``selection`` ``select_keys``'; ``scale`` the main attention's."""
    t = q.shape[1]
    chunk = min(chunk, t)
    if t % chunk or chunk % DEFAULT_BLOCK or SEL_GROUP % chunk:
        raise ValueError(f"{t} positions are not whole chunks of {chunk}, "
                         f"those whole 128s")
    dtype = q_i.dtype
    return _kl(q_i, k_i.astype(dtype), w, lax.stop_gradient(q),
               lax.stop_gradient(k), lax.stop_gradient(lse),
               lax.stop_gradient(index_lse), selection.by_query, scale,
               chunk)
