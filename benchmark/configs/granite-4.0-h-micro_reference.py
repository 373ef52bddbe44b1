"""Plain reference for the ``granite-4.0-h-micro`` configuration.

A decoder of state-space (Mamba-2) and grouped-query attention layers (sizes
and the four multipliers from ``ibm-granite/granite-4.0-h-micro``
``config.json``, ``model_type`` ``granitemoehybrid`` with no experts) trained
by Adam; in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``; gradients by autodiff.  Imports
nothing of the program and takes nothing the program made.

The equations, with ``rms(x) = x / sqrt(mean(x^2) + 1e-5) * scale``:

    h = 12 * E[token]                                  (embedding_multiplier)
    every layer l:  h = h + 0.22 * mixer_l(rms(h))     (residual_multiplier)
                    h = h + 0.22 * mlp(rms(h))
    mlp(x) = (silu(x Wg) * (x Wu)) Wd                  2048 -> 8192 -> 2048
    logits = rms(h) E^T / 8                            (logits_scaling; tied)
    loss = mean next-token cross-entropy over the vocabulary rows held

Attention mixer (``layer_types[l] == "attention"``): ``q = x Wq`` (32 heads
of 64), ``k = x Wk``, ``v = x Wv`` (8 heads of 64, each serving 4 consecutive
query heads), no bias, no positional encoding, causal softmax of
``0.015625 * q k^T`` (``attention_multiplier``), output ``Wo``.  Computed a
key-value head (its four query heads) at a time.

Mamba-2 mixer (``"mamba"``): ``[z | xBC | dt] = x W_in`` (4096, 4096 + 2 x
128, 64); ``xBC = silu(conv(xBC))``, a causal depthwise convolution over 4
positions with bias; ``xBC`` splits into ``x`` (64 heads of 64), ``B`` and
``C`` (128 each, shared by all heads); per head ``dt_t = softplus(dt_t +
dt_bias)``, ``a_t = exp(-exp(A_log) dt_t)``,

    S_t = a_t S_{t-1} + dt_t x_t B_t^T   (64 x 128, S_0 = 0),
    y_t = S_t C_t + D x_t,

then ``rms_g(y * silu(z)) W_out`` (the norm over all 4096 channels, after the
gate).  The recurrence runs one position at a time under ``lax.scan`` (the
system under test computes it in chunks of 256); its backward pass keeps the
state at every ``SEGMENT``-th position and recomputes between them, which
changes no number.

``init`` draws what ``config.json`` does not give: matrices normal(0.02),
norm scales 1, the convolution uniform in +-1/sqrt(4), and Mamba-2's own
``A`` uniform in 1..16, ``dt`` log-uniform in 0.001..0.1 through the inverse
softplus, ``D`` = 1.

The gradient of a batch is accumulated one sequence at a time
(``loss_and_grad``), so that one sequence's activations are live beside
Adam's state; ``loss_fn`` is the same loss over the whole batch at once.

``precision`` names the type the operands of every matrix product, of the
convolution and of the recurrence's two products are rounded to (accumulation
stays float32): ``float32`` is the reference; ``float8`` is the control, one
step below the bfloat16 the configuration states.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from rounding import round_to  # benchmark/rounding.py

F32 = jnp.float32
SEGMENT = 64    # positions between the states the backward pass keeps


def _sizes(cfg):
    d = cfg["hidden_size"]
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return dict(d=d, h=h, p=p, g=g, n=n, d_inner=h * p,
                conv_dim=h * p + 2 * g * n, heads=heads, kv=kv,
                hd=cfg.get("head_dim") or d // heads,
                ff=cfg["shared_intermediate_size"])


def init(key, cfg):
    z = _sizes(cfg)
    d, ff = z["d"], z["ff"]
    kinds = cfg["layer_types"]
    keys = iter(jax.random.split(key, 1 + 9 * len(kinds)))

    def normal(shape, std=0.02):
        return jax.random.normal(next(keys), shape, F32) * std

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, F32, lo, hi)

    params = {"embed": normal((cfg["vocab_size"], d)), "blocks": [],
              "norm_f": jnp.ones((d,), F32)}
    for kind in kinds:
        blk = {"norm": jnp.ones((d,), F32), "norm2": jnp.ones((d,), F32),
               "gate": normal((d, ff)), "up": normal((d, ff)),
               "down": normal((ff, d))}
        if kind == "mamba":
            k = cfg["mamba_d_conv"]
            dt = jnp.exp(uniform((z["h"],), jnp.log(1e-3), jnp.log(0.1)))
            blk.update(
                in_proj=normal((d, z["d_inner"] + z["conv_dim"] + z["h"])),
                conv_w=uniform((k, z["conv_dim"]), -k ** -0.5, k ** -0.5),
                conv_b=uniform((z["conv_dim"],), -k ** -0.5, k ** -0.5),
                # softplus(dt_bias) = dt
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                A_log=jnp.log(uniform((z["h"],), 1.0, 16.0)),
                D=jnp.ones((z["h"],), F32),
                norm_g=jnp.ones((z["d_inner"],), F32),
                out_proj=normal((z["d_inner"], d)))
        else:
            blk.update(wq=normal((d, z["heads"] * z["hd"])),
                       wk=normal((d, z["kv"] * z["hd"])),
                       wv=normal((d, z["kv"] * z["hd"])),
                       wo=normal((z["heads"] * z["hd"], d)))
        params["blocks"].append(blk)
    return params


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale


def recurrence(x, dt, a_log, b, c):
    """``S_t = exp(-exp(A_log) dt_t) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t``, one position at a time: ``x`` (L, H, P), ``dt``
    (L, H), ``b``, ``c`` (L, G, N) -> ``y`` (L, H, P)."""
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
    """One sequence ``x`` (L, D) through the Mamba-2 mixer."""
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
    y = _rms(y * jax.nn.silu(gate), blk["norm_g"], cfg["rms_norm_eps"])
    return rnd(y) @ rnd(blk["out_proj"])


def attention_mixer(x, blk, cfg, rnd):
    """One sequence ``x`` (L, D) through grouped-query attention, a
    key-value head with its query heads at a time."""
    z = _sizes(cfg)
    l = x.shape[0]
    rep = z["heads"] // z["kv"]
    q = (rnd(x) @ rnd(blk["wq"])).reshape(l, z["kv"], rep, z["hd"])
    k = (rnd(x) @ rnd(blk["wk"])).reshape(l, z["kv"], z["hd"])
    v = (rnd(x) @ rnd(blk["wv"])).reshape(l, z["kv"], z["hd"])
    causal = jnp.tril(jnp.ones((l, l), bool))

    @jax.checkpoint
    def one(qkv):
        q_g, k_g, v_g = qkv                      # (L, rep, hd), (L, hd) x 2
        scores = jnp.einsum("qrd,kd->rqk", rnd(q_g), rnd(k_g)) \
            * cfg["attention_multiplier"]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->qrd", rnd(probs), rnd(v_g))

    out = lax.map(one, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                        jnp.moveaxis(v, 1, 0)))        # (kv, L, rep, hd)
    out = jnp.moveaxis(out, 0, 1).reshape(l, z["heads"] * z["hd"])
    return rnd(out) @ rnd(blk["wo"])


def _block(x, blk, kind, cfg, rnd):
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = mamba_mixer if kind == "mamba" else attention_mixer
    x = x + res * mixer(_rms(x, blk["norm"], eps), blk, cfg, rnd)
    h = rnd(_rms(x, blk["norm2"], eps))
    h = jax.nn.silu(h @ rnd(blk["gate"])) * (h @ rnd(blk["up"]))
    return x + res * (rnd(h) @ rnd(blk["down"]))


def forward(params, tokens, cfg, precision="float32"):
    """Logits (S, V) of one sequence ``tokens`` (S,)."""
    rnd = round_to(precision)
    x = cfg["embedding_multiplier"] * params["embed"][tokens]
    for blk, kind in zip(params["blocks"], cfg["layer_types"]):
        # one block's activations live at a time in the backward pass
        x = jax.checkpoint(functools.partial(
            _block, kind=kind, cfg=cfg, rnd=rnd))(x, blk)
    x = _rms(x, params["norm_f"], cfg["rms_norm_eps"])
    return rnd(x) @ rnd(params["embed"]).T / cfg["logits_scaling"]


def sequence_loss(params, tokens, labels, cfg, precision="float32"):
    logp = jax.nn.log_softmax(forward(params, tokens, cfg, precision),
                              axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def loss_fn(params, tokens, labels, cfg, precision="float32"):
    """Mean cross-entropy of the batch ``tokens`` (B, S), all at once."""
    return jnp.mean(jax.vmap(
        lambda t, lb: sequence_loss(params, t, lb, cfg, precision))(
            tokens, labels))


def loss_and_grad(params, tokens, labels, cfg, precision="float32"):
    """The same loss and its gradient, accumulated one sequence at a
    time."""
    def one(carry, tl):
        loss, grads = jax.value_and_grad(sequence_loss)(
            params, tl[0], tl[1], cfg, precision)
        return (carry[0] + loss,
                jax.tree_util.tree_map(jnp.add, carry[1], grads)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    (loss, grads), _ = lax.scan(one, (jnp.zeros((), F32), zero),
                                (tokens, labels))
    n = tokens.shape[0]
    return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads)


def train(key, batches, cfg, steps, precision="float32"):
    """Follow the first ``steps`` Adam steps from ``init(key)`` on
    ``batches`` (a list of (tokens, labels), each with a leading axis of one
    shard; cycled).  Returns each step's loss, the first gradient (on
    the host) and the parameters' change after the last step (on the host).

    Five trees of the model's size do not fit the chip beside a sequence's
    activations (parameters, the gradient being accumulated, a sequence's
    gradient, Adam's two moments: 15.7 GB compiled for the v5e's 16.9), so
    the second moment waits on the host while a gradient is computed, the
    update is applied in place, and the initial parameters are drawn again
    at the end rather than kept.  None of this changes a number."""
    opt = cfg["optimizer"]
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])
    tmap = jax.tree_util.tree_map

    @jax.jit
    def gradient(params, tokens, labels):
        return loss_and_grad(params, tokens[0], labels[0], cfg, precision)

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
        m, second = zeros(params), None     # v: on the host between steps
        losses, first = [], None
        for i in range(steps):
            tokens, labels = batches[i % len(batches)]
            loss, grads = gradient(params, jnp.asarray(tokens),
                                   jnp.asarray(labels))
            losses.append(float(loss))
            if i == 0:   # to the host: compared leaf by leaf, element by element
                first = jax.device_get(grads)
            v = zeros(params) if second is None else tmap(jnp.asarray, second)
            params, m, v = update(params, m, v, grads,
                                  jnp.asarray(i + 1, F32))
            del grads
            second = jax.device_get(v) if i + 1 < steps else None
            del v
        del m
        change = jax.device_get(jax.jit(
            lambda a, k: tmap(jnp.subtract, a, init(k, cfg)),
            donate_argnums=0)(params, key))
    return {"losses": losses, "first_gradient": first, "param_change": change}
