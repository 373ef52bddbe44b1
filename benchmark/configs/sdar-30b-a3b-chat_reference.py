"""Plain reference for the ``sdar-30b-a3b-chat`` configuration.

A decoder of grouped-query attention with rotary positions and a norm on each
head's query and key, and of routed experts (sizes from
``JetLM/SDAR-30B-A3B-Chat`` ``config.json``, ``model_type`` ``sdar_moe``),
trained by diffusion over blocks with Adam; in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``; gradients by autodiff.  Imports
nothing of the program and takes nothing the program made.

One layer, ``x`` (positions, 2048), ``rms(x) = x / sqrt(mean(x^2) + 1e-6) *
scale``::

    a = rms(x) ;  q = rms_128(a Wq) , k = rms_128(a Wk) , v = a Wv
    q, k = rope(q, pos), rope(k, pos)            theta 1e6, pos = p mod L
    h = x + softmax(q k^T / sqrt(128) + M) v Wo  each kv head serves 8 q heads
    r = rms(h) ;  p = softmax(r Wr) over 128 ;  S = top8(p)
    w_e = p_e / sum_S p
    x' = h + sum_{e in S, e held} w_e Wdown_e(silu(r Wgate_e) * (r Wup_e))
    logits = rms(x_last) Whead

``rms_128`` is per head with a learned scale of 128; ``rope`` turns the pair
``(x_i, x_{i+64})`` by ``pos * theta^(-i/64)``.  This chip holds
``num_experts`` (16) of the router's ``published.num_experts`` (128) outputs,
from ``held_experts_first``: the router keeps its width and its 8 a token,
and what the experts that are not held would have added is left out, as in
the program.  The experts are a loop over the 16 held with a mask each;
nothing is dropped (the program's buffer must not overflow).

Training by diffusion over blocks (BD3-LM, SDAR).  The batch is ``[xt ;
x0]``, ``2 L`` positions a sequence, both halves at rotary positions
``0 .. L-1``; with ``b(p) = (p mod L) // block_length`` the mask ``M``
lets a query see a key where

    both noisy (p < L):            b(q) == b(k)
    noisy query, clean key:        b(k) <  b(q)
    clean query, clean key:        b(k) <= b(q)
    clean query, noisy key:        never.

Attention is a dense masked softmax over all ``2 L`` keys, ``Q_CHUNK``
queries and one key-value head (its 8 query heads) at a time so that 8,192
positions fit.  The logits are of the noisy half.  The labels are ``(B, L,
2)``: the target id and the weight (``1 / t_b`` where masked, else 0).

    loss = sum_rows weight * -log p(target) / (B L)
    objective = loss + aux_loss_coef * sum_layers 128 * sum_e f_e P_e

``f_e`` the assignments to expert ``e`` over the batch's positions (a count:
no gradient), ``P_e`` the mean router probability.  ``train`` reports the
loss without the auxiliary term, as the program's metric does, and
differentiates the objective.

``precision`` names the type the operands of every matrix product that the
program computes in bfloat16 are rounded to (accumulation stays float32; the
router is float32 in the program and stays so here): ``float32`` is the
reference; ``float8`` is the control, one step below bfloat16.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from rounding import round_to  # benchmark/rounding.py

F32 = jnp.float32
Q_CHUNK = 1024    # queries in one block of the dense masked softmax


def _sizes(cfg):
    return dict(d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                ff=cfg["moe_intermediate_size"], held=cfg["num_experts"],
                routed=cfg["published"]["num_experts"],
                first=cfg["held_experts_first"],
                k=cfg["num_experts_per_tok"])


def init(key, cfg):
    """From ``key``: matrices and tables normal(``initializer_range``), the
    two matrices that write to the residual stream (``wo``, ``down``)
    normal(``residual_out_initializer_range``), norm scales 1.  (The
    configuration's ``assumed.initial_values`` says why the two differ: at
    one range for all, every position's state is the mask token's value
    vector and each router sends all of them to the same experts.)"""
    z = _sizes(cfg)
    d, hd, ff = z["d"], z["hd"], z["ff"]
    layers = cfg["num_hidden_layers"]
    keys = iter(jax.random.split(key, 2 + 8 * layers))
    out_std = cfg["residual_out_initializer_range"]

    def normal(shape, std=cfg["initializer_range"]):
        return jax.random.normal(next(keys), shape, F32) * std

    params = {"embed": normal((cfg["vocab_size"], d)),
              "head": normal((cfg["vocab_size"], d)),
              "norm_f": jnp.ones((d,), F32), "blocks": []}
    for _ in range(layers):
        params["blocks"].append({
            "norm": jnp.ones((d,), F32), "norm2": jnp.ones((d,), F32),
            "q_norm": jnp.ones((hd,), F32), "k_norm": jnp.ones((hd,), F32),
            "wq": normal((d, z["heads"] * hd)), "wk": normal((d, z["kv"] * hd)),
            "wv": normal((d, z["kv"] * hd)),
            "wo": normal((z["heads"] * hd, d), out_std),
            "router": normal((d, z["routed"])),
            "gate": normal((z["held"], d, ff)), "up": normal((z["held"], d, ff)),
            "down": normal((z["held"], ff, d), out_std)})
    return params


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale


def _rope(x, pos, theta):
    """``x`` (S, H, 128) at positions ``pos`` (S,)."""
    half = x.shape[-1] // 2
    angle = pos.astype(F32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=F32) / half)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def visible(q_pos, k_pos, length, block):
    """The block-diffusion mask over ``[noisy ; clean]``, element by
    element."""
    q_noisy, k_noisy = q_pos < length, k_pos < length
    qb, kb = (q_pos % length) // block, (k_pos % length) // block
    return (q_noisy & k_noisy & (qb == kb)) | (q_noisy & ~k_noisy & (kb < qb)) \
        | (~q_noisy & ~k_noisy & (kb <= qb))


def attention(x, blk, cfg, rnd):
    """One sequence ``x`` (2 L, D) through the attention."""
    z = _sizes(cfg)
    s = x.shape[0]
    length, rep, hd = s // 2, z["heads"] // z["kv"], z["hd"]
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(s) % length
    q = (rnd(x) @ rnd(blk["wq"])).reshape(s, z["heads"], hd)
    k = (rnd(x) @ rnd(blk["wk"])).reshape(s, z["kv"], hd)
    v = (rnd(x) @ rnd(blk["wv"])).reshape(s, z["kv"], hd)
    q = _rope(_rms(q, blk["q_norm"], eps), pos, cfg["rope_theta"])
    k = _rope(_rms(k, blk["k_norm"], eps), pos, cfg["rope_theta"])
    chunk = Q_CHUNK if s % Q_CHUNK == 0 else s
    k_pos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        q_c, first, k_g, v_g = args            # (C, rep, hd), (), (S, hd) x 2
        seen = visible((first + jnp.arange(chunk))[:, None], k_pos[None, :],
                       length, cfg["block_length"])
        scores = jnp.einsum("qrd,kd->rqk", rnd(q_c), rnd(k_g)) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->qrd", rnd(probs), rnd(v_g))

    n = s // chunk
    # (kv, chunks, C, rep, hd): a key-value head and a block of queries a call
    q_g = jnp.moveaxis(q.reshape(n, chunk, z["kv"], rep, hd), 2, 0)
    firsts = jnp.broadcast_to(jnp.arange(n) * chunk, (z["kv"], n))
    k_g = jnp.broadcast_to(jnp.moveaxis(k, 1, 0)[:, None], (z["kv"], n, s, hd))
    v_g = jnp.broadcast_to(jnp.moveaxis(v, 1, 0)[:, None], (z["kv"], n, s, hd))
    flat = lambda t: t.reshape((z["kv"] * n,) + t.shape[2:])  # noqa: E731
    out = lax.map(one, (flat(q_g), flat(firsts), flat(k_g), flat(v_g)))
    out = jnp.moveaxis(out.reshape(z["kv"], n, chunk, rep, hd), 0, 2)
    return rnd(out.reshape(s, z["heads"] * hd)) @ rnd(blk["wo"])


def experts(r, blk, cfg, rnd):
    """All the batch's positions ``r`` (T, D) through the router and the
    experts held -> (their part of the layer's result, the layer's
    load-balancing term)."""
    z = _sizes(cfg)
    probs = jax.nn.softmax(r @ blk["router"], axis=-1)           # float32
    top, chosen = lax.top_k(probs, z["k"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    load = jnp.sum(jax.nn.one_hot(chosen, z["routed"], dtype=F32),
                   axis=(0, 1)) / r.shape[0]
    aux = z["routed"] * jnp.sum(lax.stop_gradient(load)
                                * jnp.mean(probs, axis=0))

    @jax.checkpoint
    def one(e, gate, up, down):
        w = jnp.sum(jnp.where(chosen == z["first"] + e, top, 0.0), axis=-1)
        h = jax.nn.silu(rnd(r) @ rnd(gate)) * (rnd(r) @ rnd(up))
        return w[:, None] * (rnd(h) @ rnd(down))

    y = jnp.zeros_like(r)
    for e in range(z["held"]):      # a loop: a scan would keep every sum
        y = y + one(e, blk["gate"][e], blk["up"][e], blk["down"][e])
    return y, aux


def _block(x, blk, cfg, rnd):
    """The batch ``x`` (B, 2 L, D) through one layer -> (x', aux)."""
    eps = cfg["rms_norm_eps"]
    a = _rms(x, blk["norm"], eps)
    x = x + jax.vmap(lambda t: attention(t, blk, cfg, rnd))(a)
    r = _rms(x, blk["norm2"], eps)
    y, aux = experts(r.reshape(-1, r.shape[-1]), blk, cfg, rnd)
    return x + y.reshape(x.shape), aux


def loss_fn(params, tokens, labels, cfg, precision="float32"):
    """``tokens`` (B, 2 L) ``[xt ; x0]``, ``labels`` (B, L, 2) -> (the
    objective, the loss without the auxiliary term)."""
    rnd = round_to(precision)
    length = tokens.shape[1] // 2
    x = params["embed"][tokens]
    aux = 0.0
    for blk in params["blocks"]:
        # one block's activations live at a time in the backward pass
        x, a = jax.checkpoint(functools.partial(_block, cfg=cfg, rnd=rnd))(
            x, blk)
        aux = aux + a
    x = _rms(x[:, :length], params["norm_f"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(rnd(x) @ rnd(params["head"]).T, axis=-1)
    nll = -jnp.take_along_axis(
        logp, labels[..., 0].astype(jnp.int32)[..., None], axis=-1)[..., 0]
    loss = jnp.sum(labels[..., 1] * nll) / nll.size
    return loss + cfg["aux_loss_coef"] * aux, loss


def train(key, batches, cfg, steps, precision="float32"):
    """Follow the first ``steps`` Adam steps from ``init(key)`` on
    ``batches`` (a list of (tokens, labels), each with a leading axis of one
    shard; cycled).  Returns each step's loss (without the auxiliary term),
    the first gradient of the objective (on the host) and the parameters'
    change after the last step (on the host).

    Adam's two moments wait on the host while a gradient is computed, the
    update is applied in place, and the initial parameters are drawn again
    at the end rather than kept: the parameters and their gradient, 2.58 GB
    each, beside the batch's float32 activations are what the chip holds.
    None of this changes a number."""
    opt = cfg["optimizer"]
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])
    tmap = jax.tree_util.tree_map

    @jax.jit
    def gradient(params, tokens, labels):
        (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens[0], labels[0], cfg, precision)
        return loss, grads

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(params, m, v, grads, t):
        lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        m = tmap(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = tmap(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        new = tmap(lambda w, a, c: w - lr_t * a / (jnp.sqrt(c) + eps),
                   params, m, v)
        return new, m, v

    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: init(k, cfg))(key)
        zeros = jax.jit(lambda p: tmap(jnp.zeros_like, p))
        moments = None                      # on the host between steps
        losses, first = [], None
        for i in range(steps):
            tokens, labels = batches[i % len(batches)]
            loss, grads = gradient(params, jnp.asarray(tokens),
                                   jnp.asarray(labels))
            losses.append(float(loss))
            if i == 0:   # to the host: compared leaf by leaf, element by element
                first = jax.device_get(grads)
            m, v = (zeros(params), zeros(params)) if moments is None else \
                tmap(jnp.asarray, moments)
            params, m, v = update(params, m, v, grads,
                                  jnp.asarray(i + 1, F32))
            del grads
            moments = jax.device_get((m, v)) if i + 1 < steps else None
            del m, v
        change = jax.device_get(jax.jit(
            lambda a, k: tmap(jnp.subtract, a, init(k, cfg)),
            donate_argnums=0)(params, key))
    return {"losses": losses, "first_gradient": first, "param_change": change}
