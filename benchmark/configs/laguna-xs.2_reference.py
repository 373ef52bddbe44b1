"""Plain reference for the ``laguna-xs.2`` configuration.

The decoder of ``poolside/Laguna-XS.2`` (``config.json``, ``model_type``
``laguna``): grouped-query attention whose layers alternate between a window
of 512 keys (64 query heads, plain rotary positions) and every earlier key
(48 query heads, YaRN-scaled rotary positions over half of each head), a
sigmoid gate a head on attention's output, a dense gated feed-forward in the
first layer and, in the others, 256 routed experts of width 512 scored by a
sigmoid, 8 a token, scaled by 2.5, beside a shared expert; trained as a
next-token model over all positions with Adam; in ``jax.numpy`` and float32
with ``jax.default_matmul_precision("highest")``; gradients by autodiff.
Imports nothing of the program and takes nothing the program made.

One layer ``l``, ``x`` (T, 2048), ``rms(x) = x / sqrt(mean(x^2) + 1e-6) *
scale``, ``D`` = 128, ``W`` = 512, ``E`` = 256, ``k`` = 8; ``H_l`` and the
layer's kinds are ``num_attention_heads_per_layer[l]``, ``layer_types[l]``,
``mlp_layer_types[l]``::

    a = rms(x)
    q = (a Wq) as [T, H_l, D] ;  k = (a Wk) as [T, 8, D] ;  v = (a Wv) as [T, 8, D]      H_l = 48 (full layer), 64 (sliding layer)
    full layer:     q, k = yarn_rope(q, pos), yarn_rope(k, pos)      the first 64 of 128 dims turned, the rest passed through
    sliding layer:  q, k = rope(q, pos, theta 1e4), rope(k, pos, 1e4)    all 128 dims
    full:     o_t = softmax over s <= t           of q_t . k_s / sqrt(D)  v
    sliding:  o_t = softmax over t - W < s <= t   of q_t . k_s / sqrt(D)  v          512 keys, the query's own among them
    g = sigmoid(a Wg) as [T, H_l]                                                     one gate a head
    h = x + ((g[..., None] * o) as [T, H_l D]) Wo
    layer 0:        x' = h + Wdown(silu(b Wgate) * (b Wup)) ,  b = rms(h)             width 8,192
    layers 1..:     s = sigmoid_f32(b Wr) over E ;  S = top_k(s) ;  w_e = 2.5 * s_e / sum_S s
                    x' = h + shared(b) + sum_{e in S, e held} w_e expert_e(b)         shared and routed experts gated SiLU of width 512
    logits = rms(x_last) Whead   (float32) ;  loss = CE(next token, all T) + aux_loss_coef * load_balance

``yarn_rope`` (arXiv:2309.00071, as its reference implementation has it, with
``rope_parameters.full_attention``): over the 32 frequency pairs of the
turned 64 dims ``f_i = 500000^(-i/32)``; pair ``i`` turns ``4096 f_i / 2 pi``
times inside ``original_max_position_embeddings``; the pairs that turn at
least ``beta_fast`` = 64 times keep ``f_i``, those that turn at most
``beta_slow`` = 1 time get ``f_i / 64``, and a linear ramp over the pairs
between them (their indices rounded outwards) blends the two; cosine and sine
are multiplied by ``attention_factor`` = 1.4158883 (= 0.1 ln 64 + 1).  The
turned pair is ``(x_i, x_{i+32})`` inside the turned part (``(x_i,
x_{i+64})`` where the whole head turns).  Each key-value head serves ``H_l /
8`` query heads.

``load_balance = sum_layers E * sum_e f_e P_e`` over the routed layers, ``f_e``
the assignments to expert ``e`` over the batch's positions (a count: no
gradient), ``P_e`` the mean over positions of ``s_e / sum_E s``.  This chip
holds ``num_experts`` (32) of the router's ``published.num_experts`` (256)
outputs, from ``held_experts_first``: the router keeps its width and its 8 a
token, and what the experts that are not held would have added is left out,
as in the program; the shared expert is every chip's.  The experts are a loop
over the 32 held with a mask each; nothing is dropped (the program's buffer
must not overflow).  Attention is a dense masked softmax over all ``T`` keys,
``Q_CHUNK`` queries and one key-value head (its query heads) at a time, and
the dense layer and the head with its loss take ``ROW_CHUNK`` positions at a
time, so that 8,192 positions fit beside what the program's loaded step
keeps reserved.  ``train`` reports the loss without the auxiliary
term, as the program's metric does, and differentiates the objective.

``precision`` names the type the operands of every matrix product that the
program computes in bfloat16 are rounded to (accumulation stays float32; the
router is float32 in the program and stays so here): ``float32`` is the
reference; ``float8`` is the control, one step below bfloat16.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from rounding import round_to  # benchmark/rounding.py

F32 = jnp.float32
Q_CHUNK = 1024    # queries in one block of the dense masked softmax
ROW_CHUNK = 4096  # positions in one block of the dense layer and of the head


def _layers(cfg):
    """[(attention kind, query heads, feed-forward kind)] a layer, read from
    the configuration's three lists."""
    n = cfg["num_hidden_layers"]
    kinds, heads, mlps = (cfg["layer_types"],
                          cfg["num_attention_heads_per_layer"],
                          cfg["mlp_layer_types"])
    assert len(kinds) == len(heads) == len(mlps) == n
    return list(zip(kinds, heads, mlps))


def init(key, cfg):
    """From ``key``: matrices and tables normal(``initializer_range``), the
    matrices that write to the residual stream (``wo``, every ``down``)
    normal(``residual_out_initializer_range``), norm scales 1."""
    d, hd, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    ff, dense_ff = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    shared_ff = cfg["shared_expert_intermediate_size"]
    held, routed = cfg["num_experts"], cfg["published"]["num_experts"]
    layers = _layers(cfg)
    keys = iter(jax.random.split(key, 2 + 12 * len(layers)))
    out_std = cfg["residual_out_initializer_range"]

    def normal(shape, std=cfg["initializer_range"]):
        return jax.random.normal(next(keys), shape, F32) * std

    params = {"embed": normal((cfg["vocab_size"], d)),
              "head": normal((cfg["vocab_size"], d)),
              "norm_f": jnp.ones((d,), F32), "blocks": []}
    for _, heads, mlp in layers:
        blk = {"norm": jnp.ones((d,), F32), "norm2": jnp.ones((d,), F32),
               "wq": normal((d, heads * hd)), "wk": normal((d, kv * hd)),
               "wv": normal((d, kv * hd)), "wg": normal((d, heads)),
               "wo": normal((heads * hd, d), out_std)}
        if mlp == "dense":
            blk.update(gate=normal((d, dense_ff)), up=normal((d, dense_ff)),
                       down=normal((dense_ff, d), out_std))
        else:
            blk.update(router=normal((d, routed)),
                       gate=normal((held, d, ff)), up=normal((held, d, ff)),
                       down=normal((held, ff, d), out_std),
                       shared_gate=normal((d, shared_ff)),
                       shared_up=normal((d, shared_ff)),
                       shared_down=normal((shared_ff, d), out_std))
        params["blocks"].append(blk)
    return params


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale


def yarn_frequencies(rope):
    """The frequency of each turned pair and the factor on cosine and sine,
    from one entry of ``rope_parameters`` and the head's size."""
    part = int(rope["head_dim"] * rope.get("partial_rotary_factor", 1))
    half = part // 2
    theta = rope["rope_theta"]
    freq = [theta ** (-i / half) for i in range(half)]
    if rope["rope_type"] == "default":
        return freq, 1.0
    assert rope["rope_type"] == "yarn"
    original = rope["original_max_position_embeddings"]

    def pair_that_turns(times):
        return part * math.log(original / (times * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_that_turns(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(rope["beta_slow"])), part - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(freq):
        interpolated = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / rope["factor"] * interpolated
                   + f * (1.0 - interpolated))
    return out, rope["attention_factor"]


def rotary(x, pos, rope):
    """``x`` (S, H, D) at positions ``pos`` (S,): the first ``2 len(freq)``
    dims turned, pair ``(x_i, x_{i + len(freq)})`` by ``pos * freq_i``, the
    rest passed through."""
    freq, factor = yarn_frequencies({**rope, "head_dim": x.shape[-1]})
    half = len(freq)
    angle = pos.astype(F32)[:, None] * jnp.asarray(freq, F32)[None, :]
    cos = jnp.cos(angle)[:, None, :] * factor
    sin = jnp.sin(angle)[:, None, :] * factor
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def visible(q_pos, k_pos, kind, window):
    """Which keys a query sees, element by element."""
    seen = k_pos <= q_pos
    if kind == "sliding_attention":
        seen = seen & (k_pos > q_pos - window)
    return seen


def attention(x, blk, kind, heads, cfg, rnd):
    """One sequence ``x`` (T, D), normed, through one layer's attention and
    its gate."""
    s = x.shape[0]
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    rep = heads // kv
    pos = jnp.arange(s)
    rope = cfg["rope_parameters"][kind]
    q = (rnd(x) @ rnd(blk["wq"])).reshape(s, heads, hd)
    k = (rnd(x) @ rnd(blk["wk"])).reshape(s, kv, hd)
    v = (rnd(x) @ rnd(blk["wv"])).reshape(s, kv, hd)
    q, k = rotary(q, pos, rope), rotary(k, pos, rope)
    chunk = Q_CHUNK if s % Q_CHUNK == 0 else s
    by_head = (jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0))   # (kv, S, hd)

    @jax.checkpoint
    def one(args):
        q_c, first, g = args                   # (C, rep, hd), (), ()
        k_g, v_g = by_head[0][g], by_head[1][g]                # (S, hd) x 2
        seen = visible((first + jnp.arange(chunk))[:, None], pos[None, :],
                       kind, cfg["sliding_window"])
        scores = jnp.einsum("qrd,kd->rqk", rnd(q_c), rnd(k_g)) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->qrd", rnd(probs), rnd(v_g))

    n = s // chunk
    # (kv x chunks, C, rep, hd): a key-value head and a block of queries a
    # call; the head's keys and values are picked inside it, not copied out
    q_g = jnp.moveaxis(q.reshape(n, chunk, kv, rep, hd), 2, 0).reshape(
        kv * n, chunk, rep, hd)
    out = lax.map(one, (q_g, jnp.tile(jnp.arange(n) * chunk, kv),
                        jnp.repeat(jnp.arange(kv), n)))
    out = jnp.moveaxis(out.reshape(kv, n, chunk, rep, hd), 0, 2)
    out = out.reshape(s, heads, hd)
    gate = jax.nn.sigmoid(rnd(x) @ rnd(blk["wg"]))              # (T, H_l)
    return rnd((gate[..., None] * out).reshape(s, heads * hd)) @ rnd(blk["wo"])


def gated(r, gate, up, down, rnd):
    """A gated-SiLU feed-forward."""
    return rnd(jax.nn.silu(rnd(r) @ rnd(gate)) * (rnd(r) @ rnd(up))) \
        @ rnd(down)


def _in_row_chunks(fn, *rows):
    """``fn`` over arrays of ``T`` rows each, ``ROW_CHUNK`` rows at a time,
    each chunk's intermediates made again in the backward pass (a Python
    loop: the weights ``fn`` closes over are operands, not copies)."""
    t = rows[0].shape[0]
    chunk = ROW_CHUNK if t % ROW_CHUNK == 0 else t
    return jnp.concatenate([
        jax.checkpoint(fn)(*(a[i:i + chunk] for a in rows))
        for i in range(0, t, chunk)])


def experts(r, blk, cfg, rnd):
    """All the batch's positions ``r`` (T, D) through the router, the experts
    held and the shared expert -> (their part of the layer's result, the
    layer's load-balancing term)."""
    held, routed = cfg["num_experts"], cfg["published"]["num_experts"]
    first, k = cfg["held_experts_first"], cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(r @ blk["router"])                   # float32
    top, chosen = lax.top_k(scores, k)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * cfg["moe_routed_scaling_factor"]
    load = jnp.sum(jax.nn.one_hot(chosen, routed, dtype=F32),
                   axis=(0, 1)) / r.shape[0]
    share = scores / jnp.sum(scores, axis=-1, keepdims=True)
    aux = routed * jnp.sum(lax.stop_gradient(load) * jnp.mean(share, axis=0))

    @jax.checkpoint
    def one(e, gate, up, down):
        w = jnp.sum(jnp.where(chosen == first + e, top, 0.0), axis=-1)
        return w[:, None] * gated(r, gate, up, down, rnd)

    y = gated(r, blk["shared_gate"], blk["shared_up"], blk["shared_down"],
              rnd)
    for e in range(held):           # a loop: a scan would keep every sum
        y = y + one(e, blk["gate"][e], blk["up"][e], blk["down"][e])
    return y, aux


def _block(x, blk, layer, cfg, rnd):
    """The batch ``x`` (B, T, D) through one layer -> (x', aux)."""
    kind, heads, mlp = layer
    eps = cfg["rms_norm_eps"]
    a = _rms(x, blk["norm"], eps)
    # a sequence at a time: one sequence's scores live at once
    x = x + jnp.stack([attention(t, blk, kind, heads, cfg, rnd) for t in a])
    r = _rms(x, blk["norm2"], eps)
    flat = r.reshape(-1, r.shape[-1])
    if mlp == "dense":
        y, aux = _in_row_chunks(lambda rows: gated(
            rows, blk["gate"], blk["up"], blk["down"], rnd), flat), 0.0
    else:
        y, aux = experts(flat, blk, cfg, rnd)
    return x + y.reshape(x.shape), aux


def loss_fn(params, tokens, labels, cfg, precision="float32"):
    """``tokens`` (B, T), ``labels`` (B, T) the next tokens -> (the
    objective, the loss without the auxiliary term)."""
    rnd = round_to(precision)
    x = params["embed"][tokens]
    aux = 0.0
    for blk, layer in zip(params["blocks"], _layers(cfg)):
        # one block's activations live at a time in the backward pass
        x, a = jax.checkpoint(functools.partial(
            _block, layer=layer, cfg=cfg, rnd=rnd))(x, blk)
        aux = aux + a
    x = _rms(x, params["norm_f"], cfg["rms_norm_eps"])

    def nll(rows, wanted):
        logp = jax.nn.log_softmax(rnd(rows) @ rnd(params["head"]).T, axis=-1)
        return -jnp.take_along_axis(logp, wanted[:, None], axis=-1)
    loss = jnp.mean(_in_row_chunks(nll, x.reshape(-1, x.shape[-1]),
                                   labels.reshape(-1).astype(jnp.int32)))
    return loss + cfg["aux_loss_coef"] * aux, loss


def train(key, batches, cfg, steps, precision="float32"):
    """Follow the first ``steps`` Adam steps from ``init(key)`` on
    ``batches`` (a list of (tokens, labels), each with a leading axis of one
    shard; cycled).  Returns each step's loss (without the auxiliary term),
    the first gradient of the objective (on the host) and the parameters'
    change after the last step (on the host).

    Adam's two moments wait on the host while a gradient is computed, the
    update is applied in place, and the initial parameters are drawn again
    at the end rather than kept: the parameters and their gradient, 2.77 GB
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
