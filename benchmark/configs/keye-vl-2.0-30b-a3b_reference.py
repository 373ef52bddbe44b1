"""Plain reference for the ``keye-vl-2.0-30b-a3b`` configuration.

The language model of ``Kwai-Keye/Keye-VL-2.0-30B-A3B`` (``config.json``,
``model_type`` ``KeyeVL2``): a causal decoder of grouped-query attention with
three-part rotary positions and a norm on each head's query and key, whose
every layer carries a learned index (``sa_config``: DeepSeek-V3.2-Exp's
sparse attention) and attends only to the ``topk`` keys it picks, and of
routed experts; trained as a next-token model over all positions with Adam;
in ``jax.numpy`` and float32 with ``jax.default_matmul_precision("highest")``;
gradients by autodiff.  Imports nothing of the program and takes nothing the
program made.

One layer, ``x`` (T, 2048), ``rms(x) = x / sqrt(mean(x^2) + 1e-6) * scale``,
``H`` = 32, ``D`` = 128, ``H_I`` = 16, ``D_I`` = 64, ``k`` = 2,048::

    a = rms(x) ;  q = rms_h(a Wq) , k = rms_h(a Wk) , v = a Wv
    q, k = mrope(q, pos[3, T]), mrope(k, pos[3, T])      sections 16/24/24 of the 64 frequency pairs, theta 1e7
    qI = (a~ WqI) as [T, H_I, D_I] ;  kI = layer_norm(a~ WkI) as [T, D_I]
    w = a~ Ww * H_I^-0.5 as [T, H_I]                     a~ = stop_gradient(a)
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(D_I)        s <= t
    S_t = the k keys s <= t of largest I[t, s]           (every s <= t while t < k)
    h = x + [softmax over s in S_t of q_t . k_s / sqrt(D)] v Wo       each kv head serves 8 q heads
    r = rms(h) ;  p = softmax(r Wr) over 128 ;  E = top8(p) ;  w_e = p_e / sum_E p
    x' = h + sum_{e in E, e held} w_e Wdown_e(silu(r Wgate_e) * (r Wup_e))
    logits = rms(x_last) Whead

    loss = CE(next token, all T positions)
    objective = loss + aux_loss_coef * sum_layers 128 * sum_e f_e P_e
                + indexer_kl_weight * sum_layers mean_t KL(pbar_t || softmax over S_t of I[t, .])
    pbar_t[s] = stop_gradient(sum_h p_h[t, s] / H) ,  s in S_t

``p_h`` are the main attention's own probabilities; ``mrope`` turns the pair
``(x_i, x_{i+64})`` by ``pos[r(i)] * theta^(-i/64)``, ``r(i)`` the section
pair ``i`` lies in; the batch is text, whose three position rows are equal
(``0 .. T-1``).  ``layer_norm`` has a learned scale and bias of 64.  ``S_t`` is
``jax.lax.top_k``'s ``k``-th value of row ``t`` of the dense masked scores as
a threshold (keys tied with it are all kept, as in the program), ``Q_CHUNK``
queries at a time so that 16,384 positions fit; attention is a dense softmax
over the selected set.  The index learns from the KL term alone (its inputs
are ``a~`` and the term reads ``pbar`` as a constant) and the rest from the
cross-entropy alone (the selection passes no gradient).

This chip holds ``num_experts`` (16) of the router's
``published.num_experts`` (128) outputs, from ``held_experts_first``: the
router keeps its width and its 8 a token, and what the experts that are not
held would have added is left out, as in the program.  The experts are a
loop over the 16 held with a mask each; nothing is dropped (the program's
buffer must not overflow).  ``f_e`` the assignments to expert ``e`` over the
batch's positions (a count: no gradient), ``P_e`` the mean router
probability.  ``train`` reports the loss without the two auxiliary terms, as
the program's metric does, and differentiates the objective.

``precision`` names the type the operands of every matrix product that the
program computes in bfloat16 are rounded to (accumulation stays float32; the
router is float32 in the program and stays so here): ``float32`` is the
reference; ``float8`` is the control, one step below bfloat16.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rounding import round_to  # benchmark/rounding.py

F32 = jnp.float32
Q_CHUNK = 512     # queries in one block of the dense masked scores


def _sizes(cfg):
    sa = cfg["sa_config"]
    return dict(d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                ff=cfg["moe_intermediate_size"], held=cfg["num_experts"],
                routed=cfg["published"]["num_experts"],
                first=cfg["held_experts_first"],
                k=cfg["num_experts_per_tok"], hi=sa["indexer_num_heads"],
                di=sa["indexer_head_dim"], topk=sa["topk"])


def init(key, cfg):
    """From ``key``: matrices and tables normal(``initializer_range``), the
    two matrices that write to the residual stream (``wo``, ``down``)
    normal(``residual_out_initializer_range``), norm scales 1, the index
    norm's bias 0 (the recipe of ``sdar-30b-a3b-chat``, whose
    ``assumed.initial_values`` says why the two ranges differ)."""
    z = _sizes(cfg)
    d, hd, ff = z["d"], z["hd"], z["ff"]
    layers = cfg["num_hidden_layers"]
    keys = iter(jax.random.split(key, 2 + 11 * layers))
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
            "wq_i": normal((d, z["hi"] * z["di"])),
            "wk_i": normal((d, z["di"])),
            "ww_i": normal((d, z["hi"])),
            "k_norm_i": jnp.ones((z["di"],), F32),
            "k_bias_i": jnp.zeros((z["di"],), F32),
            "router": normal((d, z["routed"])),
            "gate": normal((z["held"], d, ff)), "up": normal((z["held"], d, ff)),
            "down": normal((z["held"], ff, d), out_std)})
    return params


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def mrope(x, pos, theta, sections):
    """``x`` (T, H, 128) at three-part positions ``pos`` (3, T): pair ``i``
    of the 64 turns by the row of ``pos`` its section names."""
    half = x.shape[-1] // 2
    row = np.repeat(np.arange(3), sections)
    angle = pos.astype(F32)[row, :].T \
        * theta ** (-jnp.arange(half, dtype=F32) / half)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def index_scores(q_i, k_i, w, rnd):
    """``q_i`` (C, H_I, D_I), ``k_i`` (T, D_I), ``w`` (C, H_I) -> (C, T)."""
    z = jnp.einsum("cjd,td->jct", rnd(q_i), rnd(k_i))
    return jnp.einsum("jct,cj->ct", jax.nn.relu(z), w) * q_i.shape[-1] ** -0.5


def attention(x, blk, cfg, rnd, pos=None):
    """One sequence ``x`` (T, D), normed, through the attention -> (its
    result (T, D), the mean over positions of the index's KL term)."""
    z = _sizes(cfg)
    t = x.shape[0]
    rep, hd, eps = z["heads"] // z["kv"], z["hd"], cfg["rms_norm_eps"]
    sections = cfg["rope_scaling"]["mrope_section"]
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(t), (3, t))      # text
    q = (rnd(x) @ rnd(blk["wq"])).reshape(t, z["heads"], hd)
    k = (rnd(x) @ rnd(blk["wk"])).reshape(t, z["kv"], hd)
    v = (rnd(x) @ rnd(blk["wv"])).reshape(t, z["kv"], hd)
    q = mrope(_rms(q, blk["q_norm"], eps), pos, cfg["rope_theta"], sections)
    k = mrope(_rms(k, blk["k_norm"], eps), pos, cfg["rope_theta"], sections)
    a = lax.stop_gradient(x)
    q_i = (rnd(a) @ rnd(blk["wq_i"])).reshape(t, z["hi"], z["di"])
    k_i = _layer_norm(rnd(a) @ rnd(blk["wk_i"]), blk["k_norm_i"],
                      blk["k_bias_i"], eps)
    w = (rnd(a) @ rnd(blk["ww_i"])) * z["hi"] ** -0.5
    chunk = Q_CHUNK if t % Q_CHUNK == 0 else t
    k_pos = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        q_c, qi_c, w_c, first = args    # (C, heads, hd), (C, hi, di), (C, hi)
        causal = k_pos[None, :] <= (first + jnp.arange(chunk))[:, None]
        scores_i = jnp.where(causal, index_scores(qi_c, k_i, w_c, rnd),
                             -jnp.inf)
        kth = lax.top_k(lax.stop_gradient(scores_i),
                        min(z["topk"], t))[0][:, -1]
        chosen = causal & (scores_i >= kth[:, None])
        # the main attention over the chosen keys, a key-value head at a time
        out, p_sum = [], 0.0
        for g in range(z["kv"]):
            s = jnp.einsum("qrd,kd->rqk", rnd(q_c[:, g * rep:(g + 1) * rep]),
                           rnd(k[:, g])) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(chosen, s, -jnp.inf), axis=-1)
            out.append(jnp.einsum("rqk,kd->qrd", rnd(p), rnd(v[:, g])))
            p_sum = p_sum + jnp.sum(p, axis=0)
        pbar = lax.stop_gradient(p_sum / z["heads"])
        log_pi = jax.nn.log_softmax(jnp.where(chosen, scores_i, -jnp.inf),
                                    axis=-1)
        seen = chosen & (pbar > 0)
        kl = jnp.sum(jnp.where(seen, pbar * (
            jnp.log(jnp.where(seen, pbar, 1.0))
            - jnp.where(seen, log_pi, 0.0)), 0.0), axis=-1)
        return jnp.concatenate(out, axis=1), kl

    n = t // chunk
    split = lambda u: u.reshape((n, chunk) + u.shape[1:])  # noqa: E731
    out, kl = lax.map(one, (split(q), split(q_i), split(w),
                            jnp.arange(n) * chunk))
    return rnd(out.reshape(t, z["heads"] * hd)) @ rnd(blk["wo"]), \
        jnp.mean(kl)

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
    """The batch ``x`` (B, T, D) through one layer -> (x', aux, kl)."""
    eps = cfg["rms_norm_eps"]
    a = _rms(x, blk["norm"], eps)
    y, kl = jax.vmap(lambda t: attention(t, blk, cfg, rnd))(a)
    x = x + y
    r = _rms(x, blk["norm2"], eps)
    y, aux = experts(r.reshape(-1, r.shape[-1]), blk, cfg, rnd)
    return x + y.reshape(x.shape), aux, jnp.mean(kl)


def loss_fn(params, tokens, labels, cfg, precision="float32"):
    """``tokens`` (B, T), ``labels`` (B, T) the next token of each -> (the
    objective, (the loss without the auxiliary terms, the KL term summed
    over the layers))."""
    rnd = round_to(precision)
    x = params["embed"][tokens]
    aux = kl = 0.0
    for blk in params["blocks"]:
        # one block's activations live at a time in the backward pass
        x, a, k = jax.checkpoint(functools.partial(_block, cfg=cfg, rnd=rnd))(
            x, blk)
        aux, kl = aux + a, kl + k
    x = _rms(x, params["norm_f"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(rnd(x) @ rnd(params["head"]).T, axis=-1)
    loss = -jnp.mean(jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1))
    return loss + cfg["aux_loss_coef"] * aux \
        + cfg["indexer_kl_weight"] * kl, (loss, kl)


def train(key, batches, cfg, steps, precision="float32"):
    """Follow the first ``steps`` Adam steps from ``init(key)`` on
    ``batches`` (a list of (tokens, labels), each with a leading axis of one
    shard; cycled).  Returns each step's loss (without the auxiliary term),
    the first gradient of the objective (on the host) and the parameters'
    change after the last step (on the host).

    Adam's two moments wait on the host while a gradient is computed, the
    update is applied in place, and the initial parameters are drawn again
    at the end rather than kept: the parameters and their gradient, 2.64 GB
    each, beside the batch's float32 activations are what the chip holds.
    None of this changes a number."""
    opt = cfg["optimizer"]
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])
    tmap = jax.tree_util.tree_map

    @jax.jit
    def gradient(params, tokens, labels):
        (_, (loss, _)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
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
            if i == 0:  # to the host: compared leaf by leaf
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
