"""Plain reference for the ``resnet50`` configuration.

ResNet (He et al. 2015, arXiv:1512.03385, table 1, the 50-layer column)
trained by SGD with momentum: forward pass, mean softmax cross-entropy,
gradients by autodiff and the update rule, in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``.  Imports nothing of the program
and takes nothing the program made: ``init`` makes the weights from a key.

Departures from the paper, each because the system under test computes it so
(the reference has to compute the same function to be compared):

* the stride of a down-sampling bottleneck sits on its 3x3 convolution, not
  on the first 1x1 ("v1.5", as the MXNet/Gluon and torchvision zoos do);
* a 3x3 stride-2 convolution pads (0, 1) ("SAME"), not (1, 1);
* batch-norm running statistics are not followed: in training mode they do
  not enter the loss, the gradient or the update.

``precision`` names the type the operands of every convolution and matrix
product are rounded to before the product (accumulation stays float32):
``float32`` is the reference; ``float8`` is the control, one step below the
bfloat16 the configuration states (e4m3 forward, e5m2 backward, scaled).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from rounding import round_to  # benchmark/rounding.py

F32 = jnp.float32
BN_EPS = 1e-5



def _he(key, shape):
    fan_in = shape[0] * shape[1] * shape[2]
    return jax.random.normal(key, shape, F32) * (2.0 / fan_in) ** 0.5


def init(key, cfg):
    """Weights from ``key``: He-normal convolutions (HWIO), batch-norm scale
    1 and shift 0, a normal(0.01) classifier with zero bias.  With
    ``zero_init_residual`` the last batch norm of every block starts at
    scale 0 (Goyal et al. 2017, section 5.1), so that a block starts as the
    identity."""
    last_scale = jnp.zeros if cfg.get("zero_init_residual") else jnp.ones
    widths, stages = cfg["stage_widths"], cfg["stage_blocks"]
    n_keys = 2 + 4 * sum(stages)
    keys = iter(jax.random.split(key, n_keys))

    def bn(c):
        return {"g": jnp.ones((c,), F32), "b": jnp.zeros((c,), F32)}

    stem_c = cfg["stem_width"]
    params = {"stem": {"w": _he(next(keys), (7, 7, cfg["image_shape"][2],
                                             stem_c)), "bn": bn(stem_c)},
              "blocks": []}
    c_in = stem_c
    for w, n in zip(widths, stages):
        for i in range(n):
            c_out = w * cfg["expansion"]
            blk = {"w1": _he(next(keys), (1, 1, c_in, w)), "bn1": bn(w),
                   "w2": _he(next(keys), (3, 3, w, w)), "bn2": bn(w),
                   "w3": _he(next(keys), (1, 1, w, c_out)),
                   "bn3": {**bn(c_out), "g": last_scale((c_out,), F32)}}
            k_down = next(keys)
            if i == 0:  # every stage's first block projects its shortcut
                blk["wd"] = _he(k_down, (1, 1, c_in, c_out))
                blk["bnd"] = bn(c_out)
            params["blocks"].append(blk)
            c_in = c_out
    params["fc"] = {"w": jax.random.normal(next(keys),
                                           (c_in, cfg["num_classes"]),
                                           F32) * 0.01,
                    "b": jnp.zeros((cfg["num_classes"],), F32)}
    return params


def _conv(x, w, stride, padding, rnd):
    return lax.conv_general_dilated(
        rnd(x), rnd(w), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    return (x - mean) * lax.rsqrt(var + BN_EPS) * p["g"] + p["b"]


def _block(x, blk, stride, rnd):
    y = jax.nn.relu(_bn(_conv(x, blk["w1"], 1, "VALID", rnd), blk["bn1"]))
    y = jax.nn.relu(_bn(_conv(y, blk["w2"], stride, "SAME", rnd), blk["bn2"]))
    y = _bn(_conv(y, blk["w3"], 1, "VALID", rnd), blk["bn3"])
    if "wd" in blk:
        x = _bn(_conv(x, blk["wd"], stride, "VALID", rnd), blk["bnd"])
    return jax.nn.relu(y + x)


def forward(params, x, cfg, precision="float32"):
    """Training-mode logits of a batch ``x`` (N, H, W, 3)."""
    rnd = round_to(precision)
    x = x.astype(F32)
    x = _conv(x, params["stem"]["w"], 2, [(3, 3), (3, 3)], rnd)
    x = jax.nn.relu(_bn(x, params["stem"]["bn"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    i = 0
    for s, n in enumerate(cfg["stage_blocks"]):
        for j in range(n):
            stride = 2 if (j == 0 and s > 0) else 1
            # one block's activations live at a time in the backward pass,
            # so that float32 at the timed batch fits the chip
            x = jax.checkpoint(functools.partial(
                _block, stride=stride, rnd=rnd))(x, params["blocks"][i])
            i += 1
    x = jnp.mean(x, axis=(1, 2))
    return rnd(x) @ rnd(params["fc"]["w"]) + params["fc"]["b"]


def loss_fn(params, x, labels, cfg, precision="float32"):
    logits = forward(params, x, cfg, precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def train(key, batches, cfg, steps, precision="float32"):
    """Follow the first ``steps`` steps from ``init(key)`` on ``batches`` (a
    list of (data, labels), cycled).  A batch may come as several shards
    (a leading axis on data and labels of more than one): synchronous data
    parallelism averages the shards' gradients, and batch norm sees one
    shard at a time.  Returns each step's loss, the first gradient (on
    the host) and the parameters' change after the last step (on the host)."""
    opt = cfg["optimizer"]
    lr, mom, wd = opt["learning_rate"], opt["momentum"], opt["weight_decay"]

    @jax.jit
    def step(params, vel, data, labels):
        def shard(d, lb):
            return jax.value_and_grad(loss_fn)(params, d, lb, cfg, precision)
        losses, grads = lax.map(lambda dl: shard(*dl), (data, labels))
        loss = jnp.mean(losses)
        grads = jax.tree_util.tree_map(lambda g: jnp.mean(g, axis=0), grads)
        # mom = momentum*mom - lr*(g + wd*w); w += mom
        vel = jax.tree_util.tree_map(
            lambda v, g, w: mom * v - lr * (g + wd * w), vel, grads, params)
        new = jax.tree_util.tree_map(jnp.add, params, vel)
        return new, vel, loss, grads

    with jax.default_matmul_precision("highest"):
        params0 = jax.jit(lambda k: init(k, cfg))(key)
        params, vel = params0, jax.tree_util.tree_map(jnp.zeros_like, params0)
        losses, first = [], None
        for i in range(steps):
            data, labels = batches[i % len(batches)]
            params, vel, loss, grads = step(params, vel, jnp.asarray(data),
                                            jnp.asarray(labels))
            losses.append(float(loss))
            if i == 0:   # to the host: compared leaf by leaf, element by element
                first = jax.device_get(grads)
            del grads
        change = jax.device_get(jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.subtract, a, b))(params, params0))
    return {"losses": losses, "first_gradient": first, "param_change": change}
