"""Plain reference for the ``gpt2-medium`` configuration.

The GPT-2 decoder (Radford et al. 2019; sizes from
``openai-community/gpt2-medium`` ``config.json``) trained by Adam: learned
token and position embeddings, pre-norm blocks of causal multi-head attention
and a GELU (tanh form, ``gelu_new``) feed-forward, a final LayerNorm and a
linear head; mean next-token cross-entropy; gradients by autodiff; in
``jax.numpy`` and float32 with ``jax.default_matmul_precision("highest")``.
Imports nothing of the program and takes nothing the program made.

Departures from the published model, each because the system under test
computes it so: the head is a matrix of its own (not tied to the token
embedding); the attention projections (``c_attn``, ``c_proj``) carry no bias;
LayerNorm's epsilon is 1e-6 (published 1e-5); no dropout.

``precision`` names the type the operands of every matrix product are
rounded to before the product (accumulation stays float32): ``float32`` is
the reference; ``float8`` is the control, one step below the bfloat16 the
configuration states (e4m3 forward, e5m2 backward, scaled).
"""

import functools

import jax
import jax.numpy as jnp

from rounding import round_to  # benchmark/rounding.py

F32 = jnp.float32
LN_EPS = 1e-6



def init(key, cfg):
    d, v, ff = cfg["n_embd"], cfg["vocab_size"], cfg["n_inner"]
    n_layer = cfg["n_layer"]
    keys = iter(jax.random.split(key, 3 + 4 * n_layer))

    def normal(shape, std=0.02):
        return jax.random.normal(next(keys), shape, F32) * std

    def ln():
        return {"g": jnp.ones((d,), F32), "b": jnp.zeros((d,), F32)}

    params = {"wte": normal((v, d)), "wpe": normal((cfg["n_positions"], d)),
              "blocks": [], "ln_f": ln()}
    for _ in range(n_layer):
        params["blocks"].append({
            "ln_1": ln(), "qkv": normal((d, 3 * d)),
            # GPT-2 scales the residual projections by 1/sqrt(2 n_layer)
            "proj": normal((d, d), 0.02 / (2 * n_layer) ** 0.5),
            "ln_2": ln(), "fc": normal((d, ff)),
            "fc_b": jnp.zeros((ff,), F32),
            "out": normal((ff, d), 0.02 / (2 * n_layer) ** 0.5),
            "out_b": jnp.zeros((d,), F32)})
    params["head"] = normal((d, v))
    return params


def _ln(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["g"] + p["b"]


def _block(x, blk, n_head, rnd):
    b, s, d = x.shape
    h = _ln(x, blk["ln_1"])
    qkv = rnd(h) @ rnd(blk["qkv"])
    q, k, v = (t.reshape(b, s, n_head, d // n_head)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", rnd(q), rnd(k)) \
        / (d // n_head) ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", rnd(probs), rnd(v)).reshape(b, s, d)
    x = x + rnd(att) @ rnd(blk["proj"])
    h = _ln(x, blk["ln_2"])
    h = jax.nn.gelu(rnd(h) @ rnd(blk["fc"]) + blk["fc_b"], approximate=True)
    return x + rnd(h) @ rnd(blk["out"]) + blk["out_b"]


def forward(params, tokens, cfg, precision="float32"):
    """Logits (B, S, V) of ``tokens`` (B, S)."""
    rnd = round_to(precision)
    s = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][None, :s]
    for blk in params["blocks"]:
        # one block's activations live at a time in the backward pass
        x = jax.checkpoint(functools.partial(
            _block, n_head=cfg["n_head"], rnd=rnd))(x, blk)
    return rnd(_ln(x, params["ln_f"])) @ rnd(params["head"])


def loss_fn(params, tokens, labels, cfg, precision="float32"):
    """Mean cross-entropy, a sequence at a time so that the (S, V) logits of
    one sequence, not of the batch, are live."""
    def one(tl):
        t, lb = tl
        logp = jax.nn.log_softmax(
            forward(params, t[None], cfg, precision)[0], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, lb[:, None], axis=-1))
    return jnp.mean(jax.lax.map(jax.checkpoint(one), (tokens, labels)))


def train(key, batches, cfg, steps, precision="float32"):
    """Follow the first ``steps`` Adam steps from ``init(key)`` on
    ``batches`` (a list of (tokens, labels), each with a leading axis of one
    shard; cycled).  Returns each step's loss, the first gradient (on
    the host) and the parameters' change after the last step (on the host)."""
    opt = cfg["optimizer"]
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])

    @jax.jit
    def step(params, m, v, t, tokens, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens[0],
                                                  labels[0], cfg, precision)
        t = t + 1
        lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g,
                                   m, grads)
        v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g,
                                   v, grads)
        new = jax.tree_util.tree_map(
            lambda w, a, c: w - lr_t * a / (jnp.sqrt(c) + eps), params, m, v)
        return new, m, v, t, loss, grads

    with jax.default_matmul_precision("highest"):
        params0 = jax.jit(lambda k: init(k, cfg))(key)
        params = params0
        m = jax.tree_util.tree_map(jnp.zeros_like, params0)
        v = jax.tree_util.tree_map(jnp.zeros_like, params0)
        t = jnp.zeros((), F32)
        losses, first = [], None
        for i in range(steps):
            tokens, labels = batches[i % len(batches)]
            params, m, v, t, loss, grads = step(params, m, v, t,
                                                jnp.asarray(tokens),
                                                jnp.asarray(labels))
            losses.append(float(loss))
            if i == 0:   # to the host: compared leaf by leaf, element by element
                first = jax.device_get(grads)
            del grads
        del m, v
        change = jax.device_get(jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.subtract, a, b))(params, params0))
    return {"losses": losses, "first_gradient": first, "param_change": change}
