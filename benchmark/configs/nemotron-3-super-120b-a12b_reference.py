"""Plain reference for the ``nemotron-3-super-120b-a12b`` configuration.

The decoder of ``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``
(``config.json``, ``model_type`` ``nemotron_h``): 88 blocks, each one part
alone, named by a letter of ``hybrid_override_pattern``: a Mamba-2 mixer
(``M``), grouped-query attention (``*``) or 512 squared-ReLU experts that
work in a 1,024-wide latent, 22 a token, beside a shared expert (``E``);
trained as a next-token model over all positions with Adam; in ``jax.numpy``
and float32 with ``jax.default_matmul_precision("highest")``; gradients by
autodiff.  Imports nothing of the program and takes nothing the program made.

Every block is ``x <- x + part(rms(x) * g)``, ``rms(x) = x / sqrt(mean(x^2)
+ 1e-5)`` (``layer_norm_epsilon``), no biases but the convolution's; ``a`` is
the normed stream.  Published sizes first, then what this chip holds::

    M  Mamba-2:   H = 128 heads of P = 64 (d_inner 8192), G = 8 groups, N = 128, 4 taps, chunk 128
        [z | xBC | dt] = a Win                       Win 4096 x (8192 | 8192 + 2*8*128 | 128) = 4096 x 18,560
        xBC = silu(conv4(xBC) + b)                   depthwise, causal, zero before position 0, use_conv_bias true
        [x | B | C] = xBC                            x as [T, 128, 64] ; B, C as [T, 8, 128] ; head h reads group h // 16
        D_t = softplus(dt_t + dt_bias) ;  A = -exp(A_log)                       per head, float32
        s_t = exp(D_t A) s_{t-1} + D_t x_t B_t^T ;  y_t = s_t C_t + Dskip x_t    state 64 x 128 a head
        u = y * silu(z) ;  n = u * rsqrt(mean over its group's 1,024 channels of u^2 + eps) * w      the norm comes after the gate, one group at a time
        part = n Wout                                Wout 8192 x 4096
      held: heads 0..15 and group 0: Win's columns [z 0:1024 | x 0:1024 | B_0 | C_0 | dt 0:16] (4096 x 2,320), Wout's rows 0:1024

    *  attention: q = a Wq as [T, 32, 128] ; k = a Wk, v = a Wv as [T, 2, 128] ; query head h reads key-value head h // 16
        o = softmax_causal(q k^T / sqrt(128)) v ;  part = o Wo                  no positions (see the file's ``assumed``)
      held: query heads 0..3 and key-value head 0: Wq 4096 x 512, Wk, Wv 4096 x 128, Wo 512 x 4096

    E  experts in a latent: E = 512, k = 22, latent 1,024, width 2,688, shared 5,376
        s = sigmoid_f32(a Wr)                        Wr 4096 x 512, float32
        S = the 22 of largest s + b                  b: e_score_correction_bias, selection only ; n_group 1, topk_group 1: no groups
        w_e = 5 * s_e / (sum_S s + 1e-20)            norm_topk_prob true, routed_scaling_factor 5, weights from the unbiased s
        l = a Wlin                                   Wlin 4096 x 1024
        r = sum_{e in S, e held} w_e * (relu(l Wup_e)^2) Wdown_e                Wup_e 1024 x 2688, Wdown_e 2688 x 1024 ; not gated
        part = r Wlout + (relu(a Wsu)^2) Wsd         Wlout 1024 x 4096 ; Wsu 4096 x 5376, Wsd 5376 x 4096
      held: experts 0..7 of 512 ; Wr, b, Wlin, Wlout and the shared expert whole

    logits = (rms(x) * g) Whead over the 16,384 rows held (untied)
    loss = CE(next token, all T positions) + aux_loss_coef * sum over E blocks of E * sum_e f_e P_e
    after a training step, each E block:  load_e = its assignments to e in the step, all 512, all tokens
        b_e <- b_e + u * sign(mean_e(load) - load_e)

``f_e`` is the share of the tokens that chose expert ``e`` (a count: no
gradient) and ``P_e`` the mean over the tokens of ``s_e / sum_E s``.

**The share.**  The configuration's keys count what this chip holds
(``mamba_num_heads`` 16 heads in ``n_groups`` 1 group, ``num_attention_heads``
4 over ``num_key_value_heads`` 1, ``n_routed_experts`` 8 from
``held_experts_first``, ``vocab_size`` rows) and ``published`` what the whole
model has; the router and the bias keep the published width (512) and the 22
a token.  ``init`` draws the held heads' columns and rows and the held
experts' matrices, and every function here computes *their part* of a
block's result: what the absent heads and experts would have added is left
out, as in the program, and the partial result goes on to the next block.
Nothing in a part crosses a head's group (the convolution is depthwise, the
recurrence a head's own, the gated norm a group's own), so the parts of all
the shares add up to the whole block: the same functions given a
configuration that holds everything (no ``published``) compute the uncut
block, which is what the CPU tests sum the program's shares against.

The bias is no parameter: no gradient reaches it (the selection passes none)
and Adam holds nothing for it; it is carried beside the parameters, moved
after each step by the rule above from the step's own selection (which used
the bias from before the step), and returned with the parameters' change
(its first "gradient" is zeros).  The experts are a loop over those held
with a mask each; nothing is dropped (the program's buffer must not
overflow).  The recurrence runs one position at a time under ``lax.scan``
(the system under test computes it in chunks of 128); its backward pass
keeps the state at every ``SEGMENT``-th position and recomputes between
them, which changes no number.  Attention is a dense masked softmax over all
``T`` keys, ``Q_CHUNK`` queries and one key-value head (its query heads) at
a time, and the shared expert and the head with its loss take ``ROW_CHUNK``
positions at a time, so that 8,192 positions fit.

``precision`` names the type the operands of every matrix product, of the
convolution and of the recurrence's two products are rounded to
(accumulation stays float32; the router is float32 in the program and stays
so here): ``float32`` is the reference; ``float8`` is the control, one step
below the bfloat16 the configuration states.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from rounding import round_to  # benchmark/rounding.py

F32 = jnp.float32
SEGMENT = 64      # positions between the states the backward pass keeps
Q_CHUNK = 1024    # queries in one block of the dense masked softmax
ROW_CHUNK = 4096  # positions in one block of the shared expert and the head


def _whole(cfg, key):
    """The whole model's value of ``key``: ``published``'s where the key is
    cut, else the file's own."""
    return cfg.get("published", {}).get(key, cfg[key])


def _sizes(cfg):
    d = cfg["hidden_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return dict(d=d, h=h, p=p, g=g, n=n, d_inner=h * p,
                conv_dim=h * p + 2 * g * n, taps=cfg["conv_kernel"],
                heads=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                held=cfg["n_routed_experts"],
                routed=_whole(cfg, "n_routed_experts"),
                latent=cfg["moe_latent_size"],
                ff=cfg["moe_intermediate_size"],
                shared=cfg["moe_shared_expert_intermediate_size"])


def init(key, cfg):
    """From ``key``: matrices normal(``initializer_range``), the table
    normal(``embedding_initializer_range``); the matrices
    that write to the residual stream (``out_proj``, ``wo``, ``lat_out``,
    ``shared_down``) normal(``residual_out_initializer_range``); norm scales
    1; the convolution's kernel and bias uniform in ``+-conv_kernel^-0.5``
    (torch's default for a depthwise ``Conv1d``); Mamba-2's own ``A``
    uniform in 1..16 (``A_log`` its logarithm), ``dt`` log-uniform in
    ``time_step_min..time_step_max`` and no smaller than ``time_step_floor``
    through the inverse softplus (``dt_bias``), ``D`` = 1; each E block's
    selection bias normal(``expert_bias_initial_std``)."""
    z = _sizes(cfg)
    d = z["d"]
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["num_hidden_layers"]
    keys = iter(jax.random.split(key, 2 + 8 * len(pattern)))
    out_std = cfg["residual_out_initializer_range"]

    def normal(shape, std=cfg["initializer_range"]):
        return jax.random.normal(next(keys), shape, F32) * std

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, F32, lo, hi)

    params = {"embed": normal((cfg["vocab_size"], d),
                              cfg["embedding_initializer_range"]),
              "head": normal((cfg["vocab_size"], d)),
              "norm_f": jnp.ones((d,), F32), "blocks": []}
    for kind in pattern:
        blk = {"norm": jnp.ones((d,), F32)}
        if kind == "M":
            k = z["taps"]
            dt = jnp.maximum(
                jnp.exp(uniform((z["h"],), jnp.log(cfg["time_step_min"]),
                                jnp.log(cfg["time_step_max"]))),
                cfg["time_step_floor"])
            blk.update(
                in_proj=normal((d, z["d_inner"] + z["conv_dim"] + z["h"])),
                conv_w=uniform((k, z["conv_dim"]), -k ** -0.5, k ** -0.5),
                conv_b=uniform((z["conv_dim"],), -k ** -0.5, k ** -0.5),
                # softplus(dt_bias) = dt
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                A_log=jnp.log(uniform((z["h"],), 1.0, 16.0)),
                D=jnp.ones((z["h"],), F32),
                norm_g=jnp.ones((z["d_inner"],), F32),
                out_proj=normal((z["d_inner"], d), out_std))
        elif kind == "*":
            blk.update(wq=normal((d, z["heads"] * z["hd"])),
                       wk=normal((d, z["kv"] * z["hd"])),
                       wv=normal((d, z["kv"] * z["hd"])),
                       wo=normal((z["heads"] * z["hd"], d), out_std))
        elif kind == "E":
            blk.update(
                router=normal((d, z["routed"])),
                bias=normal((z["routed"],), cfg["expert_bias_initial_std"]),
                lat_in=normal((d, z["latent"])),
                up=normal((z["held"], z["latent"], z["ff"])),
                down=normal((z["held"], z["ff"], z["latent"])),
                lat_out=normal((z["latent"], d), out_std),
                shared_up=normal((d, z["shared"])),
                shared_down=normal((z["shared"], d), out_std))
        else:
            raise ValueError(f"no part {kind!r}")
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


def relu2(x):
    return jnp.square(jax.nn.relu(x))


# -- M: the Mamba-2 mixer ----------------------------------------------------

def recurrence(x, dt, a_log, b, c):
    """``s_t = exp(-exp(A_log) dt_t) s_{t-1} + dt_t x_t B_t^T``, ``y_t = s_t
    C_t``, one position at a time: ``x`` (L, H, P), ``dt`` (L, H), ``b``,
    ``c`` (L, G, N), head ``h`` reading group ``h // (H / G)`` -> ``y`` (L,
    H, P)."""
    l, h, p = x.shape
    g, n = b.shape[1:]
    a = jnp.exp(-jnp.exp(a_log) * dt)                          # (L, H)

    def position(state, inp):
        x_t, dt_t, a_t, b_t, c_t = inp
        b_t, c_t = (jnp.repeat(t, h // g, axis=0) for t in (b_t, c_t))
        state = a_t[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def segment(state, inp):
        return lax.scan(position, state, inp)

    seg = SEGMENT if l % SEGMENT == 0 else l
    inputs = tuple(t.reshape((l // seg, seg) + t.shape[1:])
                   for t in (x, dt, a, b, c))
    _, y = lax.scan(segment, jnp.zeros((h, p, n), F32), inputs)
    return y.reshape(l, h, p)


def _conv(x, w, bias):
    """Causal depthwise convolution: ``y_t = sum_k w[k] x[t-(K-1)+k] + b``."""
    k, l = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(xp[i:i + l] * w[i] for i in range(k)) + bias


def mamba_mixer(x, blk, cfg, rnd):
    """One sequence ``x`` (L, D), normed, through the heads and groups held:
    their part of the mixer's result."""
    z = _sizes(cfg)
    l = x.shape[0]
    gate, xbc, dt = jnp.split(
        rnd(x) @ rnd(blk["in_proj"]),
        [z["d_inner"], z["d_inner"] + z["conv_dim"]], axis=-1)
    xbc = jax.nn.silu(_conv(rnd(xbc), rnd(blk["conv_w"]), blk["conv_b"]))
    xs, b, c = jnp.split(xbc, [z["d_inner"], z["d_inner"] + z["g"] * z["n"]],
                         axis=-1)
    xs = xs.reshape(l, z["h"], z["p"])
    y = recurrence(rnd(xs), jax.nn.softplus(dt + blk["dt_bias"]),
                   blk["A_log"], rnd(b.reshape(l, z["g"], z["n"])),
                   rnd(c.reshape(l, z["g"], z["n"])))
    y = (y + blk["D"][:, None] * xs).reshape(l, z["d_inner"])
    u = (y * jax.nn.silu(gate)).reshape(l, z["g"], z["d_inner"] // z["g"])
    # the norm after the gate, each group over its own channels
    u = u * lax.rsqrt(jnp.mean(jnp.square(u), axis=-1, keepdims=True)
                      + cfg["layer_norm_epsilon"])
    return rnd(u.reshape(l, z["d_inner"]) * blk["norm_g"]) \
        @ rnd(blk["out_proj"])


# -- *: grouped-query attention without positions ----------------------------

def attention(x, blk, cfg, rnd):
    """One sequence ``x`` (T, D), normed, through the query heads held and
    the key-value heads they read: their part of the layer's result."""
    z = _sizes(cfg)
    s, heads, kv, hd = x.shape[0], z["heads"], z["kv"], z["hd"]
    rep = heads // kv
    pos = jnp.arange(s)
    q = (rnd(x) @ rnd(blk["wq"])).reshape(s, heads, hd)
    k = (rnd(x) @ rnd(blk["wk"])).reshape(s, kv, hd)
    v = (rnd(x) @ rnd(blk["wv"])).reshape(s, kv, hd)
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


# -- E: experts in a latent ---------------------------------------------------

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
    (T, k), their weights (T, k), the scores (T, E)): the ``k`` experts of
    largest ``s + bias``, weighted by their unbiased scores."""
    scores = jax.nn.sigmoid(r @ blk["router"])                   # float32
    _, chosen = lax.top_k(scores + lax.stop_gradient(bias),
                          cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True)
                     + cfg["norm_topk_eps"])
    return chosen, top * cfg["routed_scaling_factor"], scores


def moved_bias(bias, chosen, cfg):
    """The bias after a step that chose ``chosen`` (T, k): each expert's
    moved by ``expert_bias_update_speed`` towards the mean load."""
    load = jnp.sum(jax.nn.one_hot(chosen, bias.shape[0], dtype=F32),
                   axis=(0, 1))
    return bias + cfg["expert_bias_update_speed"] * jnp.sign(
        jnp.mean(load) - load)


def routed_sum(r, blk, bias, cfg, rnd):
    """All the batch's positions ``r`` (T, D) through the router, the
    projection into the latent and the experts held -> (their part of the
    routed sum in the latent (T, L), the moved bias, the block's
    load-balancing term)."""
    z = _sizes(cfg)
    first = cfg["held_experts_first"]
    chosen, top, scores = select(r, blk, bias, cfg)
    load = jnp.sum(jax.nn.one_hot(chosen, z["routed"], dtype=F32),
                   axis=(0, 1)) / r.shape[0]
    share = scores / jnp.sum(scores, axis=-1, keepdims=True)
    aux = z["routed"] * jnp.sum(lax.stop_gradient(load)
                                * jnp.mean(share, axis=0))
    lat = rnd(r) @ rnd(blk["lat_in"])

    @jax.checkpoint
    def one(e, up, down):
        w = jnp.sum(jnp.where(chosen == first + e, top, 0.0), axis=-1)
        return w[:, None] * (rnd(relu2(rnd(lat) @ rnd(up))) @ rnd(down))

    y = jnp.zeros_like(lat)
    for e in range(z["held"]):      # a loop: a scan would keep every sum
        y = y + one(e, blk["up"][e], blk["down"][e])
    return y, lax.stop_gradient(moved_bias(bias, chosen, cfg)), aux


def shared_expert(r, blk, rnd):
    """The shared expert every token visits, every chip alike."""
    return _in_row_chunks(lambda rows: rnd(relu2(
        rnd(rows) @ rnd(blk["shared_up"]))) @ rnd(blk["shared_down"]), r)


def expert_block(r, blk, bias, cfg, rnd):
    """All the batch's positions ``r`` (T, D), normed -> (this chip's part
    of the block's result: its experts' routed sum through the projection
    out, and the shared expert; the moved bias; the load-balancing term)."""
    y, moved, aux = routed_sum(r, blk, bias, cfg, rnd)
    return rnd(y) @ rnd(blk["lat_out"]) + shared_expert(r, blk, rnd), \
        moved, aux


def _block(x, blk, bias, kind, cfg, rnd):
    """The batch ``x`` (B, T, D) through one block -> (x', the block's moved
    bias or None, its load-balancing term)."""
    a = _rms(x, blk["norm"], cfg["layer_norm_epsilon"])
    if kind == "E":
        y, moved, aux = expert_block(a.reshape(-1, a.shape[-1]), blk, bias,
                                     cfg, rnd)
        return x + y.reshape(x.shape), moved, aux
    mixer = mamba_mixer if kind == "M" else attention
    # a sequence at a time: one sequence's scores and states live at once
    return x + jnp.stack([mixer(t, blk, cfg, rnd) for t in a]), None, 0.0


def loss_fn(params, biases, tokens, labels, cfg, precision="float32"):
    """``tokens`` (B, T), ``labels`` (B, T) the next tokens, ``biases`` each
    block's selection bias or None -> (the objective, (the loss without the
    auxiliary term, the biases after the step))."""
    rnd = round_to(precision)
    x = params["embed"][tokens]
    moved, aux = [], 0.0
    for blk, bias, kind in zip(params["blocks"], biases,
                               cfg["hybrid_override_pattern"]):
        # one block's activations live at a time in the backward pass
        x, new, a = jax.checkpoint(functools.partial(
            _block, kind=kind, cfg=cfg, rnd=rnd))(x, blk, bias)
        moved.append(new)
        aux = aux + a
    x = _rms(x, params["norm_f"], cfg["layer_norm_epsilon"])

    def nll(rows, wanted):
        logp = jax.nn.log_softmax(rnd(rows) @ rnd(params["head"]).T, axis=-1)
        return -jnp.take_along_axis(logp, wanted[:, None], axis=-1)
    loss = jnp.mean(_in_row_chunks(nll, x.reshape(-1, x.shape[-1]),
                                   labels.reshape(-1).astype(jnp.int32)))
    return loss + cfg["aux_loss_coef"] * aux, (loss, moved)


def train(key, batches, cfg, steps, precision="float32"):
    """Follow the first ``steps`` Adam steps from ``init(key)`` on
    ``batches`` (a list of (tokens, labels), each with a leading axis of one
    shard; cycled), the selection biases carried through them.  Returns each
    step's loss (without the auxiliary term), the first gradient of the
    objective (on the host; zeros for each bias, which has none) and the
    change of the parameters and of the biases after the last step (on the
    host), each a tree of ``init``'s shape; beside them ``biases``, each E
    block's bias after every step (for the tests).

    Adam's two moments wait on the host while a gradient is computed, the
    update is applied in place, and the initial values are drawn again at
    the end rather than kept.  None of this changes a number."""
    opt = cfg["optimizer"]
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])
    tmap = jax.tree_util.tree_map

    @jax.jit
    def gradient(params, biases, tokens, labels):
        (_, (loss, moved)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, biases, tokens[0], labels[0], cfg,
                                   precision)
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
