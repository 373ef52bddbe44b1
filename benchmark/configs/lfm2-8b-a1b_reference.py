"""Plain reference for the ``lfm2-8b-a1b`` configuration.

The decoder of ``LiquidAI/LFM2-8B-A1B`` (``config.json``, ``model_type``
``lfm2_moe``): gated short convolutions among grouped-query attention layers
(``layer_types``), a dense gated feed-forward in the leading
``num_dense_layers`` layers and, in the others, 32 routed experts of width
1,792 scored by a sigmoid and chosen, 4 a token, under a selection bias that
a training step moves against the load (no auxiliary loss), a head tied to
the table; trained as a next-token model over all positions with Adam; in
``jax.numpy`` and float32 with ``jax.default_matmul_precision("highest")``;
gradients by autodiff.  The mixer, the attention layer, the block and the
final norm follow the family's public implementation (``transformers``
``models/lfm2/modeling_lfm2.py``: ``Lfm2ShortConv.slow_forward``,
``Lfm2Attention``, ``Lfm2DecoderLayer``, ``embedding_norm``); the routed
layer follows the configuration's keys and what its file lists under
``assumed``.  Imports nothing of the program and takes nothing the program
made.

One layer, ``x`` (T, 2048), ``rms(x) = x / sqrt(mean(x^2) + 1e-5) * scale``,
``E`` = 32, ``k`` = 4::

    a = rms(x)                                                          operator_norm
    conv layer:       [B | C | u] = a Win                               Win 2048 x 6144, no bias; three equal parts, in this order
                      c_t = sum_{j=0..2} w_j * (B * u)_{t-2+j}          depthwise over 2,048 channels, 3 taps, zero before position 0, no bias, no activation
                      h = x + (C * c) Wout                              Wout 2048 x 2048
    attention layer:  q = rms_h(a Wq) as [T, 32, 64] ;  k = rms_h(a Wk) as [T, 8, 64] ;  v = (a Wv) as [T, 8, 64]
                      q, k = rope(q, pos, 1e6), rope(k, pos, 1e6)       the whole head, pairs (x_i, x_{i+32})
                      h = x + [softmax over s <= t of q_t . k_s / 8] v Wo
    b = rms(h)                                                          ffn_norm
    dense layer:      x' = h + W2(silu(b W1) * (b W3))                  width 7,168
    routed layer:     s = sigmoid_f32(b Wr) over E
                      S = top_k(s + bias)                               bias (E,) float32: the selection only
                      w_e = routed_scaling_factor * s_e / (sum_{S} s + 1e-6) ,  e in S      from s, not from s + bias
                      x' = h + sum_{e in S, e held} w_e W2_e(silu(b W1_e) * (b W3_e))       width 1,792, no shared expert
    logits = rms(x_last) Table^T     (float32; embedding_norm)
    loss = CE(next token, all T positions)                              no auxiliary term
    after a training step, each routed layer:  load_e = its assignments to e in the step, all E, all tokens
                      bias_e <- bias_e + u * sign(mean_e(load) - load_e)

This chip holds ``num_experts`` (8) of the router's ``published.num_experts``
(32) outputs, from ``held_experts_first``: the router and the bias keep their
width and the 4 a token, and what the experts that are not held would have
added is left out, as in the program.  The bias is no parameter: no gradient
reaches it (the selection passes none) and Adam holds nothing for it; it is
carried beside the parameters, moved after each step by the rule above from
the step's own selection (which used the bias from before the step), and
returned with the parameters' change (its first "gradient" is zeros).  The
experts are a loop over the 8 held with a mask each; nothing is dropped (the
program's buffer must not overflow).  The convolution is three shifted
multiply-adds.  Attention is a dense masked softmax over all ``T`` keys,
``Q_CHUNK`` queries and one key-value head (its four query heads) at a time,
and the dense layer and the head with its loss take ``ROW_CHUNK`` positions
at a time, so that 16,384 positions fit.

``precision`` names the type the operands of every matrix product that the
program computes in bfloat16 are rounded to (accumulation stays float32; the
router is float32 in the program and stays so here; the gates and the taps
are no matrix product and are not rounded): ``float32`` is the reference;
``float8`` is the control, one step below bfloat16.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from rounding import round_to  # benchmark/rounding.py

F32 = jnp.float32
Q_CHUNK = 1024    # queries in one block of the dense masked softmax
ROW_CHUNK = 4096  # positions in one block of the dense layer and of the head


def _layers(cfg):
    """[(mixer kind, feed-forward kind)] a layer: ``layer_types``, and dense
    in the leading ``num_dense_layers`` layers."""
    kinds = cfg["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"]
    return [(kind, "dense" if i < cfg["num_dense_layers"] else "routed")
            for i, kind in enumerate(kinds)]


def init(key, cfg):
    """From ``key``: matrices normal(``initializer_range``), the table
    normal(``embedding_initializer_range``) (the head is the table: a
    position's logit for its own token is ``hidden_size`` times this range),
    the matrices that write to the residual stream (``wout``, ``wo``, every
    ``down``) normal(``residual_out_initializer_range``), the taps uniform in
    ``+-conv_L_cache^-0.5`` (torch's ``Conv1d`` default, as the family's
    implementation builds them), norm scales 1, each routed layer's
    selection bias normal(``expert_bias_initial_std``)."""
    d = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    ff, dense_ff = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    held, routed = cfg["num_experts"], cfg["published"]["num_experts"]
    taps = cfg["conv_L_cache"]
    layers = _layers(cfg)
    keys = iter(jax.random.split(key, 1 + 12 * len(layers)))
    out_std = cfg["residual_out_initializer_range"]

    def normal(shape, std=cfg["initializer_range"]):
        return jax.random.normal(next(keys), shape, F32) * std

    params = {"embed": normal((cfg["vocab_size"], d),
                              cfg["embedding_initializer_range"]),
              "norm_f": jnp.ones((d,), F32), "blocks": []}
    for kind, mlp in layers:
        blk = {"norm": jnp.ones((d,), F32), "norm2": jnp.ones((d,), F32)}
        if kind == "conv":
            blk.update(win=normal((d, 3 * d)),
                       taps=jax.random.uniform(
                           next(keys), (taps, d), F32, -taps ** -0.5,
                           taps ** -0.5),
                       wout=normal((d, d), out_std))
        else:
            blk.update(wq=normal((d, heads * hd)), wk=normal((d, kv * hd)),
                       wv=normal((d, kv * hd)),
                       q_norm=jnp.ones((hd,), F32),
                       k_norm=jnp.ones((hd,), F32),
                       wo=normal((heads * hd, d), out_std))
        if mlp == "dense":
            blk.update(gate=normal((d, dense_ff)), up=normal((d, dense_ff)),
                       down=normal((dense_ff, d), out_std))
        else:
            blk.update(router=normal((d, routed)),
                       bias=normal((routed,), cfg["expert_bias_initial_std"]),
                       gate=normal((held, d, ff)), up=normal((held, d, ff)),
                       down=normal((held, ff, d), out_std))
        params["blocks"].append(blk)
    return params


def split_bias(tree):
    """``init``'s tree -> (the parameters, each block's selection bias or
    None): the bias is state, not a parameter."""
    blocks = [{k: v for k, v in blk.items() if k != "bias"}
              for blk in tree["blocks"]]
    return {**tree, "blocks": blocks}, [blk.get("bias")
                                        for blk in tree["blocks"]]


def join_bias(params, biases):
    """``split_bias`` undone: a tree of ``init``'s shape."""
    return {**params, "blocks": [
        blk if bias is None else {**blk, "bias": bias}
        for blk, bias in zip(params["blocks"], biases)]}


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale


def rotary(x, pos, theta):
    """``x`` (S, H, D) at positions ``pos`` (S,): the pair ``(x_i, x_{i +
    D/2})`` turned by ``pos * theta^(-2i/D)``."""
    half = x.shape[-1] // 2
    freq = jnp.asarray([theta ** (-i / half) for i in range(half)], F32)
    angle = pos.astype(F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def short_conv(x, blk, cfg, rnd):
    """One sequence ``x`` (T, D), normed, through the gated short
    convolution: ``Lfm2ShortConv.slow_forward`` without a cache."""
    t = x.shape[0]
    gate_in, gate_out, u = jnp.split(rnd(x) @ rnd(blk["win"]), 3, axis=-1)
    u = gate_in * u
    taps = cfg["conv_L_cache"]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), F32), u])
    conv = sum(blk["taps"][j] * padded[j:j + t] for j in range(taps))
    return rnd(gate_out * conv) @ rnd(blk["wout"])


def attention(x, blk, cfg, rnd):
    """One sequence ``x`` (T, D), normed, through the attention layer:
    ``Lfm2Attention``."""
    s = x.shape[0]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, rep, eps = cfg["hidden_size"] // heads, heads // kv, cfg["norm_eps"]
    pos = jnp.arange(s)
    q = _rms((rnd(x) @ rnd(blk["wq"])).reshape(s, heads, hd), blk["q_norm"],
             eps)
    k = _rms((rnd(x) @ rnd(blk["wk"])).reshape(s, kv, hd), blk["k_norm"], eps)
    v = (rnd(x) @ rnd(blk["wv"])).reshape(s, kv, hd)
    q, k = rotary(q, pos, cfg["rope_theta"]), rotary(k, pos, cfg["rope_theta"])
    chunk = Q_CHUNK if s % Q_CHUNK == 0 else s
    by_head = (jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0))   # (kv, S, hd)

    @jax.checkpoint
    def one(args):
        q_c, first, g = args                   # (C, rep, hd), (), ()
        k_g, v_g = by_head[0][g], by_head[1][g]                # (S, hd) x 2
        seen = pos[None, :] <= (first + jnp.arange(chunk))[:, None]
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
    return rnd(out.reshape(s, heads * hd)) @ rnd(blk["wo"])


def gated(r, gate, up, down, rnd):
    """A gated-SiLU feed-forward: ``W2(silu(r W1) * (r W3))``."""
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


def select(r, blk, bias, cfg):
    """All the batch's positions ``r`` (T, D) through the router -> (chosen
    (T, k), their weights (T, k)): the ``k`` experts of largest ``s +
    bias``, weighted by their unbiased scores."""
    scores = jax.nn.sigmoid(r @ blk["router"])                   # float32
    _, chosen = lax.top_k(scores + lax.stop_gradient(bias),
                          cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True)
                     + cfg["norm_topk_eps"])
    return chosen, top * cfg["routed_scaling_factor"]


def moved_bias(bias, chosen, cfg):
    """The bias after a step that chose ``chosen`` (T, k): each expert's
    moved by ``expert_bias_update_speed`` towards the mean load."""
    load = jnp.sum(jax.nn.one_hot(chosen, bias.shape[0], dtype=F32),
                   axis=(0, 1))
    return bias + cfg["expert_bias_update_speed"] * jnp.sign(
        jnp.mean(load) - load)


def experts(r, blk, bias, cfg, rnd):
    """All the batch's positions ``r`` (T, D) through the router and the
    experts held -> (their part of the layer's result, the moved bias)."""
    first = cfg["held_experts_first"]
    chosen, top = select(r, blk, bias, cfg)

    @jax.checkpoint
    def one(e, gate, up, down):
        w = jnp.sum(jnp.where(chosen == first + e, top, 0.0), axis=-1)
        return w[:, None] * gated(r, gate, up, down, rnd)

    y = jnp.zeros_like(r)
    for e in range(cfg["num_experts"]):  # a loop: a scan would keep every sum
        y = y + one(e, blk["gate"][e], blk["up"][e], blk["down"][e])
    return y, lax.stop_gradient(moved_bias(bias, chosen, cfg))


def _block(x, blk, bias, layer, cfg, rnd):
    """The batch ``x`` (B, T, D) through one layer -> (x', the layer's moved
    bias or None): ``Lfm2DecoderLayer``."""
    kind, mlp = layer
    eps = cfg["norm_eps"]
    a = _rms(x, blk["norm"], eps)
    mixer = short_conv if kind == "conv" else attention
    # a sequence at a time: one sequence's scores live at once
    x = x + jnp.stack([mixer(t, blk, cfg, rnd) for t in a])
    r = _rms(x, blk["norm2"], eps)
    flat = r.reshape(-1, r.shape[-1])
    if mlp == "dense":
        y, moved = _in_row_chunks(lambda rows: gated(
            rows, blk["gate"], blk["up"], blk["down"], rnd), flat), None
    else:
        y, moved = experts(flat, blk, bias, cfg, rnd)
    return x + y.reshape(x.shape), moved


def loss_fn(params, biases, tokens, labels, cfg, precision="float32"):
    """``tokens`` (B, T), ``labels`` (B, T) the next tokens, ``biases`` each
    block's selection bias or None -> (the loss, the biases after the
    step)."""
    rnd = round_to(precision)
    x = params["embed"][tokens]
    moved = []
    for blk, bias, layer in zip(params["blocks"], biases, _layers(cfg)):
        # one block's activations live at a time in the backward pass
        x, new = jax.checkpoint(functools.partial(
            _block, layer=layer, cfg=cfg, rnd=rnd))(x, blk, bias)
        moved.append(new)
    x = _rms(x, params["norm_f"], cfg["norm_eps"])

    def nll(rows, wanted):        # the head is the table
        logp = jax.nn.log_softmax(rnd(rows) @ rnd(params["embed"]).T, axis=-1)
        return -jnp.take_along_axis(logp, wanted[:, None], axis=-1)
    loss = jnp.mean(_in_row_chunks(nll, x.reshape(-1, x.shape[-1]),
                                   labels.reshape(-1).astype(jnp.int32)))
    return loss, moved


def train(key, batches, cfg, steps, precision="float32"):
    """Follow the first ``steps`` Adam steps from ``init(key)`` on
    ``batches`` (a list of (tokens, labels), each with a leading axis of one
    shard; cycled), the selection biases carried through them.  Returns each
    step's loss, the first gradient (on the host; zeros for each bias, which
    has none) and the change of the parameters and of the biases after the
    last step (on the host), each a tree of ``init``'s shape; beside them
    ``biases``, each routed layer's bias after every step (for the tests).

    Adam's two moments wait on the host while a gradient is computed, the
    update is applied in place, and the initial values are drawn again at
    the end rather than kept.  None of this changes a number."""
    opt = cfg["optimizer"]
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])
    tmap = jax.tree_util.tree_map

    @jax.jit
    def gradient(params, biases, tokens, labels):
        (loss, moved), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, biases, tokens[0], labels[0], cfg, precision)
        return loss, moved, grads

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(params, m, v, grads, t):
        lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        m = tmap(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = tmap(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        new = tmap(lambda w, a, c: w - lr_t * a / (jnp.sqrt(c) + eps),
                   params, m, v)
        return new, m, v

    with jax.default_matmul_precision("highest"):
        params, biases = jax.jit(lambda k: split_bias(init(k, cfg)))(key)
        zeros = jax.jit(lambda p: tmap(jnp.zeros_like, p))
        moments = None                      # on the host between steps
        losses, first, after = [], None, []
        for i in range(steps):
            tokens, labels = batches[i % len(batches)]
            loss, moved, grads = gradient(params, biases, jnp.asarray(tokens),
                                          jnp.asarray(labels))
            losses.append(float(loss))
            if i == 0:  # to the host: compared leaf by leaf
                first = jax.device_get(join_bias(grads, zeros(biases)))
            biases = moved
            after.append(jax.device_get([b for b in moved if b is not None]))
            m, v = (zeros(params), zeros(params)) if moments is None else \
                tmap(jnp.asarray, moments)
            params, m, v = update(params, m, v, grads,
                                  jnp.asarray(i + 1, F32))
            del grads
            moments = jax.device_get((m, v)) if i + 1 < steps else None
            del m, v
        change = jax.device_get(jax.jit(
            lambda a, k: tmap(jnp.subtract, a, init(k, cfg)),
            donate_argnums=0)(join_bias(params, biases), key))
    return {"losses": losses, "first_gradient": first, "param_change": change,
            "biases": after}
