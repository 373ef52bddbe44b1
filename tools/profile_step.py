"""Profile one training step on the flagship bench model (judge item 2).

Captures a jax.profiler trace of the steady-state ResNet-152 b32 train
step (same step as bench.py), then summarizes where the time goes from
the trace's event table so the MFU number has a committed explanation.

Outputs:
- ``profile_output/r04_trace/``  — the raw trace (perfetto-compatible)
- ``PROFILE_r04.json``           — op-category time breakdown + step time

Usage: python tools/profile_step.py [--model resnet152] [--batch 32]
       (DT_FORCE_CPU=1 for a CPU smoke run)
"""

import argparse
import glob
import gzip
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_step(net, batch, size):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dt_tpu import models, optim
    from dt_tpu.ops import losses
    from dt_tpu.training.train_state import TrainState

    model = models.create(net, num_classes=1000, dtype=jnp.bfloat16)
    x = jnp.asarray(np.random.RandomState(0)
                    .uniform(-1, 1, (batch, size, size, 3)), jnp.bfloat16)
    y = jnp.asarray(np.random.RandomState(1).randint(0, 1000, (batch,)))
    init_fn = jax.jit(
        lambda k: model.init({"params": k, "dropout": k}, x,
                             training=False))
    variables = init_fn(jax.random.PRNGKey(0))
    tx = optim.create("sgd", learning_rate=0.1, momentum=0.9,
                      weight_decay=1e-4)
    state = TrainState.create(model.apply, variables["params"], tx,
                              variables.get("batch_stats", {}))

    def train_step(state, x, y):
        def loss_of(params):
            out, mutated = model.apply(
                {"params": params, "batch_stats": state.batch_stats}, x,
                training=True, mutable=["batch_stats"],
                rngs={"dropout": jax.random.PRNGKey(2)})
            return losses.softmax_cross_entropy(out, y), \
                mutated["batch_stats"]
        (loss, stats), grads = jax.value_and_grad(
            loss_of, has_aux=True)(state.params)
        return state.apply_gradients(grads).replace(batch_stats=stats), loss

    # donation segfaults on XLA CPU with multi-device collectives
    # (CLAUDE.md gotcha; DT_FORCE_CPU runs land here too)
    donate = (0,) if jax.default_backend() != "cpu" else ()
    step = jax.jit(train_step, donate_argnums=donate)
    return step, state, x, y


def summarize_trace(outdir):
    """Best-effort xplane/trace.json.gz summary: bucket device-op self
    time by op-name family."""
    events = []
    for path in glob.glob(os.path.join(outdir, "**", "*.trace.json.gz"),
                          recursive=True):
        with gzip.open(path, "rt") as f:
            doc = json.load(f)
        events.extend(doc.get("traceEvents", []))
    buckets = {}
    device_total = 0.0
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        # device lanes carry compiled op names; host lanes python frames
        name = e.get("name", "")
        cat = None
        low = name.lower()
        for key, tag in (("conv", "conv"), ("dot", "matmul"),
                         ("fusion", "fusion"), ("all-reduce", "collective"),
                         ("copy", "copy"), ("reduce", "reduce"),
                         ("transpose", "transpose"), ("scatter", "scatter")):
            if key in low:
                cat = tag
                break
        if cat is None:
            continue
        buckets[cat] = buckets.get(cat, 0.0) + e["dur"] / 1e3
        device_total += e["dur"] / 1e3
    return {"categories_ms": {k: round(v, 2)
                              for k, v in sorted(buckets.items(),
                                                 key=lambda kv: -kv[1])},
            "categorized_total_ms": round(device_total, 2)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet152")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    from dt_tpu.config import maybe_force_cpu, enable_compilation_cache
    maybe_force_cpu()
    enable_compilation_cache()
    # r16 flight recorder: a wedged profile attempt leaves a bundle
    # (thread stacks pin the blocking call) instead of a bare rc —
    # no-op unless DT_BLACKBOX=1
    from dt_tpu.obs import blackbox
    blackbox.install(host="profile_step")
    # beats are per-stage and a healthy resnet152 compile alone runs
    # minutes: floor the deadman above the training-loop default
    dog = blackbox.Watchdog(host="profile_step",
                            hang_seconds=max(blackbox.hang_s(), 1800.0)) \
        if blackbox.enabled() else None
    import jax

    step, state, x, y = build_step(args.model, args.batch, args.size)
    if dog is not None:
        dog.beat()  # build+trace armed; compile is next
    state, loss = step(state, x, y)  # compile + warm
    jax.block_until_ready((state, loss))

    outdir = os.path.join(REPO, "profile_output", "r04_trace")
    os.makedirs(outdir, exist_ok=True)
    jax.profiler.start_trace(outdir)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, loss = step(state, x, y)
    jax.block_until_ready((state, loss))
    dt = (time.perf_counter() - t0) / args.steps
    jax.profiler.stop_trace()

    summary = {
        "model": args.model, "batch": args.batch, "size": args.size,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "step_ms": round(dt * 1e3, 2),
        "imgs_per_sec": round(args.batch / dt, 2),
        "trace_dir": os.path.relpath(outdir, REPO),
        **summarize_trace(outdir),
    }
    with open(os.path.join(REPO, "PROFILE_r04.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    if dog is not None:
        dog.stop()


if __name__ == "__main__":
    main()
