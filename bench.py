"""Benchmark: ResNet-152 ImageNet training throughput on one TPU chip.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline"}.

Baseline: the reference's published single-GPU number for the same model and
batch size — ResNet-152, batch 32, 20.08 img/s (BASELINE.md row 1,
reference ``example/image-classification/README.md:300-320``).
``vs_baseline`` = our imgs/sec / 20.08.

Full training step (fwd + bwd + SGD-momentum update + BN stats), bf16
compute, synthetic input (the reference's ``--benchmark 1`` mode) so input
IO can't mask compute throughput.

One process, run through the chip tool.  It refuses a backend that is not
``tpu`` (``DT_FORCE_CPU=1``, set by hand, smoke-runs the measurement path
on the CPU), exits non-zero when any tier fails, and treats a
``device_kind`` missing from the peak table as an error.  ROADMAP S1
replaces the tier functions with cells through ``Module.fit``.
"""

import json
import os
import sys
import time

BASELINE_IMGS_PER_SEC = 20.08  # reference ResNet-152 1-GPU img/s, batch 32


def _arm_blackbox(tag):
    """r16 flight recorder: register the crash-bundle hooks + a hang
    watchdog in this process, so a wedged run leaves a bundle with the
    blocking frame instead of a bare rc (no-op unless ``DT_BLACKBOX=1``).
    Returns the watchdog (or None) — beat it at stage boundaries."""
    from dt_tpu.obs import blackbox
    if not blackbox.enabled():
        return None
    blackbox.install(host=tag)
    blackbox.note("bench.stage", tag=tag, stage="start")
    # beats land only at tier boundaries and a HEALTHY tier runs many
    # minutes (compile + measurement) — floor the deadman well above
    # the training-loop default or every clean run dumps phantom hang
    # bundles
    return blackbox.Watchdog(host=tag,
                             hang_seconds=max(blackbox.hang_s(), 1800.0))


def main():
    from dt_tpu.config import maybe_force_cpu, enable_compilation_cache
    forced_cpu = maybe_force_cpu()
    enable_compilation_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not forced_cpu:
        print(f"bench.py measures a TPU; found platform {dev.platform!r} "
              f"({dev.device_kind}).  DT_FORCE_CPU=1 smoke-runs the path "
              "on the CPU.", file=sys.stderr)
        return 1
    if dev.platform == "tpu":
        _peak_tflops(dev.device_kind)  # unknown kind: fail before compiling
    _bb_dog = _arm_blackbox("bench")

    # overridables exist so the measurement path can be smoke-tested on
    # CPU; the default TIERS: a fast ResNet-18 point first, then the
    # BASELINE row (ResNet-152, batch 32), then the LM.
    batch = int(os.environ.get("DT_BENCH_BATCH", "32"))
    size = int(os.environ.get("DT_BENCH_IMAGE", "224"))
    tiers = ([os.environ["DT_BENCH_MODEL"]]
             if os.environ.get("DT_BENCH_MODEL")
             else ["resnet18", "resnet152", "transformer_lm"])
    # the single reported line is the highest-priority tier (the
    # reference's headline is the ResNet-152 row); the others ride along
    # under "other_tiers"
    priority = ["resnet152", "inception_v3", "alexnet", "resnet50",
                "resnet18", "transformer_lm"]
    completed = {}
    try:
        for net in tiers:
            if _bb_dog is not None:
                _bb_dog.beat()  # tier boundary: progress reached the deadman
            if net == "transformer_lm":
                result = measure_tier_lm()
            else:
                result = measure_tier(net, batch, size)
            if forced_cpu:
                # a CPU number never goes under a device metric's name
                result["metric"] = "cpu_smoke_" + result["metric"]
            completed[net] = result
            print(f"# tier {net} done: {json.dumps(result)}",
                  file=sys.stderr, flush=True)
    finally:
        if _bb_dog is not None:
            _bb_dog.stop()
    head = next(completed[n] for n in priority + tiers if n in completed)
    others = {n: {k: r[k] for k in ("metric", "value", "unit", "mfu",
                                    "step_ms", "sync_agreement",
                                    "steps_per_sec", "final_loss")
                  if k in r}
              for n, r in completed.items() if r is not head}
    print(json.dumps(dict(head, **({"other_tiers": others}
                                   if others else {}))))
    return 0


# per-img fwd GFLOP (train step ~ 3x fwd) + the image size that figure
# (and the baseline) is calibrated at; baselines from the reference's
# published single-GPU table where a row exists.  When the run's
# DT_BENCH_IMAGE differs from the calibrated size, flops/MFU/vs_baseline
# are suppressed rather than silently mis-scaled.
# {net: (fwd GFLOP/img, baseline img/s or None, calib size, calib batch
# or None=any)}; the reference's baseline rows are batch-specific
_TIER_INFO = {
    "resnet152": (11.56e9, BASELINE_IMGS_PER_SEC, 224, 32),
    "resnet50": (4.1e9, None, 224, None),
    "resnet18": (1.8e9, None, 224, None),
    # other reference 1-GPU table rows (BASELINE.md): inception-v3 b32 at
    # 299px, alexnet b512 (run via DT_BENCH_MODEL/_IMAGE/_BATCH)
    "inception_v3": (5.73e9, 30.4, 299, 32),
    "alexnet": (0.72e9, 457.07, 224, 512),
}

# published peak bf16 TFLOP/s per chip, keyed by device_kind substring —
# used for the MFU estimate (VERDICT round-1 item 2)
_PEAK_TFLOPS = (("v6e", 918.0), ("v6", 918.0), ("v5p", 459.0),
                ("v5e", 197.0), ("v5lite", 197.0), ("v4", 275.0),
                ("v3", 123.0), ("v2", 45.0))


def _peak_tflops(device_kind: str) -> float:
    kind = device_kind.lower().replace(" ", "")
    for key, peak in _PEAK_TFLOPS:
        if key in kind:
            return peak
    raise ValueError(f"device_kind {device_kind!r} is not in bench.py's "
                     "peak table (_PEAK_TFLOPS); add it with its source")


def _bench_health(tier, dt_step, loss):
    """r15: per-tier training-health gauges for the committed BENCH
    jsonl — step rate and final loss land next to ``sync_agreement``,
    so the evidence trajectory carries health series from the first
    successful TPU tier onward (ISSUE 12 satellite; the live
    time-series plane belongs to training jobs — a bench child has no
    heartbeat export or scraper, so the row fields ARE the surface)."""
    import jax
    del tier  # rows are already per-tier; kept for call-site clarity
    return {"steps_per_sec": round(1.0 / dt_step, 3),
            "final_loss": round(float(jax.device_get(loss)), 5)}


def measure_tier(net, batch, size):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dt_tpu import models, optim
    from dt_tpu.ops import losses
    from dt_tpu.training.train_state import TrainState

    def phase(msg):
        print(f"# [{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
              flush=True)

    model = models.create(net, num_classes=1000, dtype=jnp.bfloat16)
    x = jnp.asarray(np.random.RandomState(0)
                    .uniform(-1, 1, (batch, size, size, 3)), jnp.bfloat16)
    y = jnp.asarray(np.random.RandomState(1).randint(0, 1000, (batch,)))

    # init must be jitted: eager init compiles and dispatches hundreds of
    # tiny ops individually; one compiled program pays the cost once
    phase(f"compiling init ({net}, batch {batch})")
    init_fn = jax.jit(
        lambda k: model.init({"params": k, "dropout": k}, x,
                             training=False))
    variables = init_fn(jax.random.PRNGKey(0))
    jax.block_until_ready(variables)
    phase("init done")
    tx = optim.create("sgd", learning_rate=0.1, momentum=0.9,
                      weight_decay=1e-4)
    state = TrainState.create(model.apply, variables["params"], tx,
                              variables.get("batch_stats", {}))

    def train_step(state, x, y):
        def loss_of(params):
            # BN-less tiers (alexnet) have no batch_stats collection
            variables = {"params": params}
            mutable = []
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
                mutable = ["batch_stats"]
            out, mutated = model.apply(
                variables, x, training=True, mutable=mutable,
                rngs={"dropout": jax.random.fold_in(
                    jax.random.PRNGKey(2), state.step)})
            return losses.softmax_cross_entropy(out, y), \
                mutated.get("batch_stats", state.batch_stats)
        (loss, stats), grads = jax.value_and_grad(loss_of, has_aux=True)(
            state.params)
        return state.apply_gradients(grads).replace(batch_stats=stats), loss

    donate = (0,) if jax.default_backend() != "cpu" else ()
    step = jax.jit(train_step, donate_argnums=donate)

    # AOT compile: cost_analysis must read the program BEFORE the first
    # donating call deletes the input buffers, and AOT avoids lowering
    # twice
    phase("compiling train step")
    from dt_tpu.obs import device as obs_device
    from dt_tpu.obs import trace as obs_trace
    cache = obs_device.cache_probe()
    t_compile = time.perf_counter()
    _tr = obs_trace.tracer()
    _tc0 = _tr.begin("compile.bench_step")
    compiled = step.lower(state, x, y).compile()
    _tr.complete_span("compile.bench_step", _tc0, {"tier": net})
    step_flops = _compiled_flops(compiled)
    step = compiled
    state, loss = step(state, x, y)
    jax.block_until_ready((state, loss))
    t_compile = time.perf_counter() - t_compile
    phase(f"train step compiled in {t_compile:.0f}s; measuring")

    # Block on the FULL output state, not just the scalar loss: the
    # window ends when every output of the last step exists.  Two
    # timings — queued (async dispatch, drain at the end) and per-step
    # synced (pays a host round trip each step) — can each be
    # pessimistic in different regimes (queued donation chains build HBM
    # pressure; sync adds the round trip), so take the better of the two
    # completed-work measurements.
    iters = int(os.environ.get("DT_BENCH_ITERS", "20"))
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = step(state, x, y)
    jax.block_until_ready((state, loss))
    queued = (time.perf_counter() - t0) / iters

    sync_iters = min(iters, 10)
    t0 = time.perf_counter()
    for _ in range(sync_iters):
        state, loss = step(state, x, y)
        jax.block_until_ready((state, loss))
    synced = (time.perf_counter() - t0) / sync_iters
    dt_step = min(queued, synced)

    imgs_per_sec = batch / dt_step
    step_ms = dt_step * 1e3
    # this step is a single-device jit, so value IS the per-chip
    # number; num_chips documents the divisor and sync_agreement is the
    # queued-drain vs per-step-sync ratio (within 10% = the two
    # completed-work timings agree)
    num_chips = 1
    sync_agreement = round(min(queued, synced) / max(queued, synced), 3)
    fwd_flops, baseline, calib_size, calib_batch = _TIER_INFO.get(
        net, (0.0, None, None, None))
    if calib_size is not None and size != calib_size:
        fwd_flops, baseline = 0.0, None  # config != calibration: no claims
    if calib_batch is not None and batch != calib_batch:
        baseline = None  # the reference row is batch-specific
    # FLOPs: the COMPILER's count of the whole train step is primary
    # (survives model edits — VERDICT r4 next 10); the hand-calibrated
    # table (3x fwd heuristic) is the fallback and cross-check
    if step_flops:
        flops_per_img = step_flops / batch
        flops_source = "compiler"
    else:
        flops_per_img = 3 * fwd_flops
        flops_source = "table"
    model_tflops = imgs_per_sec * flops_per_img / 1e12
    kind = jax.devices()[0].device_kind
    peak = _peak_tflops(kind) if jax.default_backend() == "tpu" else None
    return {
        "metric": f"{net}_train_imgs_per_sec_per_chip",
        "value": round(imgs_per_sec, 2),
        "unit": "imgs/sec",
        # vs_baseline compares like-for-like only: the reference's table
        # has a ResNet-152/b32 row (20.08); other tiers report 0.0
        "vs_baseline": round(imgs_per_sec / baseline, 2) if baseline
        else 0.0,
        "step_ms": round(step_ms, 2),
        "step_ms_queued": round(queued * 1e3, 2),
        "step_ms_synced": round(synced * 1e3, 2),
        "sync_agreement": sync_agreement,
        "num_chips": num_chips,
        "value_per_chip": round(imgs_per_sec / num_chips, 2),
        # whether the persistent cache served the compile is part of
        # the row (set-up time is reported apart from the window)
        "compile_time_s": round(t_compile, 1),
        "cache_hits": int(cache.outcome() == "hit"),
        "cache_misses": int(cache.outcome() == "miss"),
        "compile_cache": cache.outcome(),
        "model_tflops_per_sec": round(model_tflops, 2) if flops_per_img
        else None,
        "flops_source": flops_source,
        "device_kind": kind,
        # MFU vs the chip's published bf16 peak (null on the CPU smoke)
        "mfu": round(model_tflops / peak, 3) if peak and flops_per_img
        else None,
        "backend": jax.default_backend(),
        **_bench_health(net, dt_step, loss),
    }


def _compiled_flops(compiled):
    """Whole-train-step FLOPs from XLA's own cost model
    (``Compiled.cost_analysis()``); None when the backend doesn't report
    it."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        f = ca.get("flops", 0.0)
        return float(f) or None
    except Exception:
        return None


def measure_tier_lm():
    """Transformer-LM tokens/sec tier (VERDICT r4 next 9): the
    long-context stack gets a number next to the CNN tiers.  bf16
    GPT-small-ish config (512 dim x 6 layers, seq 2048); attention
    defaults to the Pallas flash kernel on TPU (``DT_BENCH_LM_ATTN``
    overrides; plain attention on CPU smoke where interpret-mode Pallas
    would dominate).  No reference baseline exists — the reference's LM
    ceiling was RNNs (SURVEY §5.7) — so ``vs_baseline`` is 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dt_tpu import models, optim
    from dt_tpu.ops import losses
    from dt_tpu.training.train_state import TrainState

    def phase(msg):
        print(f"# [{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
              flush=True)

    batch = int(os.environ.get("DT_BENCH_LM_BATCH", "8"))
    seq = int(os.environ.get("DT_BENCH_LM_SEQ", "2048"))
    vocab = int(os.environ.get("DT_BENCH_LM_VOCAB", "8192"))
    attn = os.environ.get("DT_BENCH_LM_ATTN")
    if attn is None:
        attn = "flash" if jax.default_backend() not in ("cpu",) else "none"
    attn = None if attn in ("none", "") else attn
    model = models.TransformerLM(
        vocab_size=vocab, embed_dim=512, num_layers=6, num_heads=8,
        max_len=seq, seq_parallel=attn, dtype=jnp.bfloat16)
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, vocab, (batch, seq)), jnp.int32)

    phase(f"compiling LM init (seq {seq}, attn {attn or 'full'})")
    init_fn = jax.jit(
        lambda k: model.init({"params": k}, toks, training=False))
    variables = init_fn(jax.random.PRNGKey(0))
    jax.block_until_ready(variables)
    tx = optim.create("sgd", learning_rate=0.1, momentum=0.9)
    state = TrainState.create(model.apply, variables["params"], tx, {})

    def train_step(state, toks):
        def loss_of(params):
            logits = model.apply({"params": params}, toks, training=True)
            return losses.softmax_cross_entropy(
                logits[:, :-1].reshape(-1, vocab),
                toks[:, 1:].reshape(-1))
        loss, grads = jax.value_and_grad(loss_of)(state.params)
        return state.apply_gradients(grads), loss

    donate = (0,) if jax.default_backend() != "cpu" else ()
    step = jax.jit(train_step, donate_argnums=donate)
    phase("compiling LM train step")
    from dt_tpu.obs import device as obs_device
    from dt_tpu.obs import trace as obs_trace
    cache = obs_device.cache_probe()
    t_compile = time.perf_counter()
    _tr = obs_trace.tracer()
    _tc0 = _tr.begin("compile.bench_step")
    compiled = step.lower(state, toks).compile()
    _tr.complete_span("compile.bench_step", _tc0, {"tier": "lm"})
    step_flops = _compiled_flops(compiled)
    state, loss = compiled(state, toks)
    jax.block_until_ready((state, loss))
    t_compile = time.perf_counter() - t_compile
    phase(f"LM step compiled in {t_compile:.0f}s; measuring")

    iters = int(os.environ.get("DT_BENCH_ITERS", "20"))
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = compiled(state, toks)
    jax.block_until_ready((state, loss))
    queued = (time.perf_counter() - t0) / iters
    sync_iters = min(iters, 10)
    t0 = time.perf_counter()
    for _ in range(sync_iters):
        state, loss = compiled(state, toks)
        jax.block_until_ready((state, loss))
    synced = (time.perf_counter() - t0) / sync_iters
    dt_step = min(queued, synced)

    tokens_per_sec = batch * seq / dt_step
    model_tflops = (tokens_per_sec * step_flops / (batch * seq) / 1e12
                    if step_flops else None)
    kind = jax.devices()[0].device_kind
    peak = _peak_tflops(kind) if jax.default_backend() == "tpu" else None
    num_chips = 1  # single-device jit (see measure_tier's note)
    return {
        "metric": "transformer_lm_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": 0.0,  # beyond reference: no LM row in its table
        "seq_len": seq, "batch": batch, "attention": attn or "full",
        "step_ms": round(dt_step * 1e3, 2),
        "step_ms_queued": round(queued * 1e3, 2),
        "step_ms_synced": round(synced * 1e3, 2),
        "sync_agreement": round(min(queued, synced)
                                / max(queued, synced), 3),
        "num_chips": num_chips,
        "tokens_per_sec_per_chip": round(tokens_per_sec / num_chips, 1),
        "compile_time_s": round(t_compile, 1),
        "cache_hits": int(cache.outcome() == "hit"),
        "cache_misses": int(cache.outcome() == "miss"),
        "compile_cache": cache.outcome(),
        "model_tflops_per_sec": round(model_tflops, 2)
        if model_tflops else None,
        "flops_source": "compiler" if step_flops else None,
        "device_kind": kind,
        "mfu": round(model_tflops / peak, 3)
        if peak and model_tflops else None,
        "backend": jax.default_backend(),
        **_bench_health("transformer_lm", dt_step, loss),
    }


if __name__ == "__main__":
    sys.exit(main())
