"""Training-throughput scaling sweep over data-parallel mesh sizes.

Reference: ``example/image-classification/benchmark.py`` — multi-node
training sweeps (1 -> N GPUs, doubling) behind the published scaling
tables (``README.md:300-320``).  TPU-native: instead of launching ssh
jobs per point, each sweep point jits the SAME full training step over a
k-device ``jax.sharding.Mesh`` (batch sharded over ``data``, params
replicated, gradient psum by GSPMD) and measures img/s — the framework's
actual scaling mechanism.

On real hardware run as-is; without a pod, sweep the virtual CPU mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 DT_FORCE_CPU=1 \
        python examples/benchmark.py --network resnet18 --image-size 64

Prints one JSON line per point: devices, imgs/sec, scaling efficiency.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser("benchmark")
    ap.add_argument("--network", default="resnet50")
    ap.add_argument("--batch-per-device", type=int, default=32)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--max-devices", type=int, default=0,
                    help="cap the sweep (default: all devices)")
    args = ap.parse_args()

    from dt_tpu.config import maybe_force_cpu
    maybe_force_cpu()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from dt_tpu import models, optim
    from dt_tpu.ops import losses
    from dt_tpu.training.train_state import TrainState

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    devices = jax.devices()
    if args.max_devices:
        devices = devices[:args.max_devices]
    sizes = []
    k = 1
    while k <= len(devices):
        sizes.append(k)
        k *= 2

    model = models.create(args.network, num_classes=args.num_classes,
                          dtype=dtype)
    size = args.image_size
    base = None
    for n in sizes:
        mesh = Mesh(np.array(devices[:n]), ("data",))
        batch = args.batch_per_device * n
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.uniform(-1, 1, (batch, size, size, 3)), dtype)
        y = jnp.asarray(rng.randint(0, args.num_classes, (batch,)))
        xsh = NamedSharding(mesh, P("data"))
        x = jax.device_put(x, xsh)
        y = jax.device_put(y, NamedSharding(mesh, P("data")))

        init_fn = jax.jit(
            lambda kk: model.init({"params": kk}, x, training=False))
        variables = init_fn(jax.random.PRNGKey(0))
        tx = optim.create("sgd", learning_rate=0.1, momentum=0.9)
        state = TrainState.create(model.apply, variables["params"], tx,
                                  variables.get("batch_stats"))
        state = jax.device_put(state, NamedSharding(mesh, P()))

        def train_step(state, x, y):
            def loss_of(p):
                out, mut = model.apply(
                    {"params": p, "batch_stats": state.batch_stats},
                    x, training=True, mutable=["batch_stats"])
                return losses.softmax_cross_entropy(out, y), \
                    mut["batch_stats"]
            (loss, bs), g = jax.value_and_grad(loss_of, has_aux=True)(
                state.params)
            return state.apply_gradients(g).replace(batch_stats=bs), loss

        step = jax.jit(train_step,
                       out_shardings=(NamedSharding(mesh, P()),
                                      NamedSharding(mesh, P())))
        state, loss = step(state, x, y)   # compile + warmup
        jax.block_until_ready((state, loss))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            state, loss = step(state, x, y)
        # block on the FULL output state: the window ends when every
        # output of the last step exists, not just its loss
        jax.block_until_ready((state, loss))
        dt = time.perf_counter() - t0
        ips = batch * args.iters / dt
        if base is None:
            base = ips
        print(json.dumps({
            "network": args.network, "devices": n, "global_batch": batch,
            "imgs_per_sec": round(ips, 1),
            "speedup": round(ips / base, 2),
            "scaling_efficiency": round(ips / (base * n), 3),
        }))


if __name__ == "__main__":
    main()
