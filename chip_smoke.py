"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run from the root of a checkout, on a machine with a TPU::

    python chip_smoke.py

Three stages, each a child process that exits before the next starts (a chip
belongs to one process at a time; this parent never touches JAX):

A. the trainer — ``examples/common.py`` (``make_data``, ``make_module``,
   ``fit``) -> ``Module.fit``, ResNet-50 / 224x224x3 / 1000 classes /
   bfloat16 / synthetic input / batch 128 per chip, ten steps on all local
   chips in one process;
B. every Pallas kernel a supported model can select, compiled by Mosaic
   (``interpret=False``) forward and backward against its ``jnp`` oracle
   (the cases of ``tools/pallas_drive.py``) at the shapes the models feed
   it, then ten steps of ``TransformerLM(seq_parallel="flash")`` through
   ``Module.fit``;
C. the elastic entry — ``python -m dt_tpu.launcher.launch`` running
   ``examples/train_elastic.py``, one worker process per chip; with two or
   more chips the last chip is kept for a worker that joins mid-run.

Any stage's failure is this script's non-zero exit.  It refuses anything but
a TPU: each child checks ``jax.devices()[0].platform`` before it does
anything else, and ``DT_FORCE_CPU`` is dropped from the children's
environment.  On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; the line before
it carries each stage's counts and set-up times.  No rate and no utilization
is measured here.

The stage functions take a ``size`` (``"full"`` | ``"toy"``) so that
``tests/test_smoke_chip.py`` rehearses A and C on the CPU mesh.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: wall budget per stage child (seconds, compilation included); together
#: under the 1200 s the chip check allows
STAGE_TIMEOUT_S = {"a": 420, "b": 450, "c": 300}


def _device():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


class _Builds:
    """What jax itself reports of this stage's builds, read from the
    program's own build account (``dt_tpu/obs/trace.py``: one row for
    each trace, lowering and backend compile, written by listeners of
    ``jax.monitoring`` that ``dt_tpu.training`` registers at import):
    backend compiles (a persistent-cache retrieval counts as one, and its
    seconds are the retrieval's), those the cache was asked for and those
    it served, and the seconds in tracing and in lowering, which no cache
    saves."""

    def __init__(self):
        from dt_tpu.obs import trace
        self._trace = trace
        self._mark = trace.tracer().builds()

    def rows(self):
        return self._trace.tracer().build_rows(since=self._mark)

    @property
    def compiles(self):
        return sum(r[2] == "backend" for r in self.rows())

    def report(self):
        rows = self.rows()
        cache = [r[self._trace.BUILD_ROW_FIELDS.index("cache")]
                 for r in rows if r[2] == "backend"]
        seconds = {stage: round(self._trace.stage_ns(rows, stage) / 1e9, 1)
                   for stage in ("trace", "lower", "backend")}
        return {"backend_compiles": len(cache),
                "compile_s": seconds["backend"],
                "trace_s": seconds["trace"], "lower_s": seconds["lower"],
                "cache_requests": sum(c != "off" for c in cache),
                "cache_hits": sum(c == "hit" for c in cache)}


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# Stage A — the trainer
# ---------------------------------------------------------------------------

_A_SIZES = {
    "full": dict(network="resnet50", image="224,224,3", classes=1000,
                 per_chip=128, steps=10),
    "toy": dict(network="resnet20", image="32,32,3", classes=10,
                per_chip=2, steps=3),
}


def stage_a(size="full"):
    """Ten steps of the ImageNet example's own functions on every local
    device; returns the counts it checked."""
    import jax
    import numpy as np
    sys.path.insert(0, os.path.join(REPO, "examples"))
    import common
    from dt_tpu import parallel

    counter = _Builds()
    cfg = _A_SIZES[size]
    devices = jax.local_devices()
    batch = cfg["per_chip"] * len(devices)
    t_start = time.monotonic()
    # what examples/train_imagenet.py main() does, flag for flag
    args = common.base_parser("chip_smoke stage A").parse_args([
        "--network", cfg["network"], "--num-classes", str(cfg["classes"]),
        "--image-shape", cfg["image"], "--batch-size", str(batch),
        "--num-examples", str(batch * cfg["steps"]), "--benchmark", "1",
        "--dtype", "bfloat16", "--num-epochs", "1", "--disp-batches", "1"])
    image_shape = common.setup(args)
    kv = parallel.create(args.kv_store)
    train, val = common.make_data(args, image_shape, kv)
    mod = common.make_module(args, train.steps_per_epoch or 1, kv)

    sample = train.next()
    train.reset()
    t0 = time.monotonic()
    init_state = mod.init_params(sample.data)
    jax.block_until_ready(init_state)
    init_s = time.monotonic() - t0
    init_compiles = counter.compiles
    before = jax.device_get(init_state.params)

    per_step = []  # the running [accuracy, cross-entropy] after each step
    common.fit(args, mod, train, val, batch_end_callback=[
        lambda p: per_step.append(dict(p.eval_metric.get_name_value()))])
    jax.block_until_ready(mod.state)
    wall_s = time.monotonic() - t_start

    steps = int(mod.state.step)
    _check(steps == cfg["steps"], f"took {steps} steps, asked {cfg['steps']}")
    _check(len(per_step) == cfg["steps"],
           f"{len(per_step)} per-step metric reports for {steps} steps")
    for i, m in enumerate(per_step):
        _check(all(np.isfinite(v) for v in m.values()),
               f"step {i}: training metric not finite: {m}")
    after = jax.device_get(mod.state.params)
    leaves_b, leaves_a = (jax.tree_util.tree_leaves(t) for t in (before, after))
    _check(all(np.isfinite(np.asarray(x, np.float32)).all() for x in leaves_a),
           "a parameter is not finite after training")
    _check(any(not np.array_equal(b, a) for b, a in zip(leaves_b, leaves_a)),
           "no parameter changed")
    donated = all(x.is_deleted()
                  for x in jax.tree_util.tree_leaves(init_state))
    if devices[0].platform != "cpu":  # Module donates off the CPU only
        _check(donated, "the initial state was not donated")
    # every device: a replica of each parameter, a shard of the batch
    want = set(devices)
    for leaf in jax.tree_util.tree_leaves(mod.state.params):
        _check({s.device for s in leaf.addressable_shards} == want
               and all(s.data.shape == leaf.shape
                       for s in leaf.addressable_shards),
               f"parameter {leaf.shape} is not replicated on {want}")
    placed = mod._place(sample.data)
    _check({s.device for s in placed.addressable_shards} == want
           and all(s.data.shape[0] == cfg["per_chip"]
                   for s in placed.addressable_shards),
           f"batch shards: {[(s.device, s.data.shape) for s in placed.addressable_shards]}")
    peak = {}
    for d in devices:
        stats = d.memory_stats()
        if d.platform == "tpu":  # the CPU backend reports no memory stats
            _check(stats and stats.get("peak_bytes_in_use", 0) > 0,
                   f"{d}: no peak_bytes_in_use in memory_stats(): {stats}")
        if stats:
            peak[str(d.id)] = int(stats["peak_bytes_in_use"])
    train_compiles = mod._train_step._cache_size()
    _check(train_compiles == 1,
           f"train_step compiled {train_compiles} times in a fixed-shape run")
    return {"model": cfg["network"], "image": cfg["image"],
            "batch_per_chip": cfg["per_chip"], "steps": steps,
            "final_metric": per_step[-1], "donated": donated,
            "train_step_compiles": train_compiles,
            "init_compiles": init_compiles, "init_s": round(init_s, 1),
            "peak_bytes_in_use": peak, "wall_s": round(wall_s, 1),
            **counter.report()}


# ---------------------------------------------------------------------------
# Stage B — kernels that compile
# ---------------------------------------------------------------------------

_B_SIZES = {
    # flash: the stage's own LM shape below (8 heads of 64 at sequence
    # 2048), at the derived tiles of either pass (flash_case passes none); BN:
    # ResNet-50's first and last BN inputs at batch 128; ssd: the hybrid
    # cell's state-space scan (B, L, H, P, G, N, chunk)
    "full": dict(flash=(8, 2048, 8, 64), bn=[(128, 112, 112, 64),
                                             (128, 7, 7, 2048)],
                 ssd=(2, 4096, 64, 64, 1, 128, 256), dtype="bfloat16",
                 lm=dict(vocab=8192, embed=512, layers=6, heads=8, seq=2048,
                         batch=8, steps=10)),
    "toy": dict(flash=(1, 256, 2, 64), bn=[(2, 8, 8, 64)],
                ssd=(1, 200, 2, 64, 1, 128, 128), dtype="float32",
                lm=dict(vocab=64, embed=32, layers=1, heads=2, seq=128,
                        batch=2, steps=2)),
}


def stage_b(size="full", interpret=False):
    """Each Pallas kernel forward and backward against its oracle, then the
    flash-attention LM through ``Module.fit``.  Every kernel is tried before
    the stage fails, so one run names every miss."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import pallas_drive as pd
    from dt_tpu import data, models
    from dt_tpu.ops.pallas import attention
    from dt_tpu.parallel import mesh as mesh_lib
    from dt_tpu.training import Module

    counter = _Builds()
    cfg = _B_SIZES[size]
    dt = jnp.dtype(cfg["dtype"]).type
    # bf16 carries 8 bits of mantissa and the oracles accumulate in another
    # order; f32 differences are reduction order only
    tol = 5e-2 if cfg["dtype"] == "bfloat16" else 1e-3
    rng = np.random.RandomState(0)
    t_start = time.monotonic()
    cases = [("flash_attention_fwd_bwd", cfg["flash"], tol,
              lambda: pd.flash_case(rng, *cfg["flash"], dt, interpret))]
    for shape in cfg["bn"]:
        cases += [("fused_bn_inference", shape, tol,
                   lambda s=shape: pd.bn_inference_case(rng, s, dt, interpret)),
                  ("fused_bn_train_fwd_bwd", shape, tol,
                   lambda s=shape: pd.bn_train_case(rng, s, dt, interpret))]
    # the scan and its five gradients against the XLA body in float32
    cases.append(("ssd_scan_fwd_bwd", cfg["ssd"], tol,
                  lambda: pd.ssd_scan_case(rng, *cfg["ssd"], dt, interpret)))

    # the flash gate runs at the tiles the models get: derived from the shape
    _, seq, _, head = cfg["flash"]
    lengths = (seq, seq, head, jnp.dtype(dt).itemsize)
    extras = {"flash_attention_fwd_bwd": {
        "tiles": list(attention.forward_tiles(*lengths)),
        "bwd_tiles": list(attention.backward_tiles(*lengths))}}
    kernels, failed = [], []
    for name, shape, bound, build in cases:
        row = {"kernel": name, "shape": str(shape), **extras.get(name, {})}
        try:
            oracle, pallas, a = build()
            want, got = oracle(*a), pallas(*a)
            jax.block_until_ready((want, got))
            row["rel_err"] = pd.rel_err(got, want)
            row["ok"] = bool(np.isfinite(row["rel_err"])
                             and row["rel_err"] <= bound)
        except Exception as e:  # noqa: BLE001 — try every kernel, fail after
            row["ok"] = False
            row["error"] = f"{type(e).__name__}: {str(e)[:600]}"
        print(f"# stage b: {json.dumps(row)}", flush=True)
        kernels.append(row)
        if not row["ok"]:
            failed.append(name)

    # the LM the way tests/test_pipeline_transformer.py drives one, on one
    # device: whether Mosaic takes the kernel is a per-chip question (stage A
    # is the all-chips run)
    lm = cfg["lm"]
    model = models.TransformerLM(
        vocab_size=lm["vocab"], embed_dim=lm["embed"],
        num_layers=lm["layers"], num_heads=lm["heads"], max_len=lm["seq"],
        seq_parallel="flash", dtype=dt)
    toks = rng.randint(0, lm["vocab"],
                       (lm["batch"] * lm["steps"], lm["seq"])).astype(np.int32)
    mod = Module(model, optimizer="sgd",
                 optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
                 mesh=mesh_lib.make_mesh(devices=jax.local_devices()[:1]),
                 seed=0)
    per_step = []
    mod.fit(data.NDArrayIter(toks, np.roll(toks, -1, axis=1),
                             batch_size=lm["batch"]),
            eval_metric="ce", num_epoch=1, batch_end_callback=lambda p:
            per_step.append(p.eval_metric.get_name_value()[0][1]))
    jax.block_until_ready(mod.state)
    lm_steps = int(mod.state.step)
    _check(not failed, f"kernels failed: {failed}")
    _check(lm_steps == lm["steps"], f"LM took {lm_steps} of {lm['steps']}")
    _check(len(per_step) == lm_steps and np.isfinite(per_step).all(),
           f"LM cross-entropy per step: {per_step}")
    _check(mod._train_step._cache_size() == 1, "LM train_step recompiled")
    return {"kernels": kernels, "lm_steps": lm_steps,
            "lm_final_ce": round(float(per_step[-1]), 4),
            "wall_s": round(time.monotonic() - t_start, 1),
            **counter.report()}


# ---------------------------------------------------------------------------
# Stage C — the elastic entry (this function never touches JAX)
# ---------------------------------------------------------------------------

_C_SIZES = {
    # README quick start: resnet20 on 32x32x3, global batch 64
    "full": dict(epochs=20, examples=4096),
    "toy": dict(epochs=6, examples=256),
}


def _kill_group(proc):
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    proc.wait()


def stage_c(size="full", chips=1, timeout_s=STAGE_TIMEOUT_S["c"]):
    """The README's elastic quick start under the launcher, one worker
    process per chip.  With two or more chips the last one is kept free and
    a host line appended after the first epoch, so a worker joins at an
    epoch boundary on a chip of its own.  Every worker must exit 0 and
    report the same final state digest."""
    import re
    cfg = _C_SIZES[size]
    joins = chips >= 2
    base = chips - 1 if joins else chips
    hosts = [f"worker-{i}" for i in range(base + joins)]
    work = tempfile.mkdtemp(prefix="chip_smoke_c_")
    hostfile = os.path.join(work, "host_worker")
    log_path = os.path.join(work, "launch.log")
    with open(hostfile, "w") as f:
        f.write("".join(h + "\n" for h in hosts[:base]))
    cmd = [sys.executable, "-m", "dt_tpu.launcher.launch", "-n", str(base),
           "-H", hostfile, "--elastic-training-enabled", "True", "--",
           sys.executable, os.path.join(REPO, "examples", "train_elastic.py"),
           "--network", "resnet20", "--num-classes", "10",
           "--image-shape", "32,32,3", "--batch-size", "64",
           "--num-epochs", str(cfg["epochs"]),
           "--num-examples", str(cfg["examples"])]
    env = {**os.environ,
           "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    t_start = time.monotonic()
    joined_at = None
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                                env=env, cwd=REPO, start_new_session=True)
    try:
        while proc.poll() is None:
            if time.monotonic() - t_start > timeout_s:
                raise TimeoutError(f"stage c: launcher still running after "
                                   f"{timeout_s} s")
            time.sleep(0.2)
            if joins and joined_at is None:
                with open(log_path) as f:
                    if "Epoch[0] Time cost" in f.read():
                        with open(hostfile, "a") as hf:
                            hf.write(hosts[-1] + "\n")
                        joined_at = round(time.monotonic() - t_start, 1)
        with open(log_path) as f:
            out = f.read()
        _check(proc.returncode == 0,
               f"launcher exited {proc.returncode} (a worker failed)")
        done = {host: (dev, digest) for host, dev, digest in re.findall(
            r"worker (\S+) rank \d+ finished: step \d+ on (\S+ \(.*?\)) "
            r"state sha256 ([0-9a-f]{64})", out)}
        _check(sorted(done) == sorted(hosts),
               f"workers that finished: {sorted(done)}, expected {hosts}")
        digests = {d for _, d in done.values()}
        _check(len(digests) == 1,
               f"final state differs across workers: {done}")
        if joins:
            _check(f"launching elastic worker {hosts[-1]}" in out,
                   f"{hosts[-1]} was never launched")
    except BaseException:
        with open(log_path) as f:
            sys.stderr.write("# stage c launcher log (tail):\n"
                             + f.read()[-6000:] + "\n")
        raise
    finally:
        _kill_group(proc)
    return {"workers": sorted(done), "joined_mid_run": hosts[-1] if joins
            else None, "join_requested_at_s": joined_at,
            "worker_devices": sorted({dev for dev, _ in done.values()}),
            "state_sha256": digests.pop(),
            "wall_s": round(time.monotonic() - t_start, 1)}


# ---------------------------------------------------------------------------
# child / parent
# ---------------------------------------------------------------------------


def _child(stage, chips, result_path):
    """One stage at full size.  Stages A and B own the chip and refuse
    anything else; stage C stays off JAX so that its workers can have it."""
    if stage == "c":
        result = stage_c("full", chips)
        _check(all(d.startswith("tpu ") for d in result["worker_devices"]),
               f"stage c workers ran on {result['worker_devices']}")
    else:
        dev = _device()
        print(f"# stage {stage}: platform={dev['platform']} "
              f"device_kind={dev['kind']} count={dev['count']}", flush=True)
        if dev["platform"] != "tpu":
            print(f"chip_smoke: stage {stage} needs a TPU; JAX found platform "
                  f"{dev['platform']!r} ({dev['kind']})", file=sys.stderr)
            return 1
        result = {"device": dev,
                  **{"a": stage_a, "b": stage_b}[stage]("full")}
    with open(result_path, "w") as f:
        json.dump(result, f)
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--stage", choices=["a", "b", "c"],
                    help="(internal) run one stage in this process")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--result")
    args = ap.parse_args()
    if args.stage:
        return _child(args.stage, args.chips, args.result)

    env = {k: v for k, v in os.environ.items() if k != "DT_FORCE_CPU"}
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    t_start = time.monotonic()
    report = {}
    for stage in ("a", "b", "c"):
        result_path = os.path.join(work, f"stage_{stage}.json")
        chips = report["a"]["device"]["count"] if stage == "c" else 1
        print(f"# chip_smoke: stage {stage}", flush=True)
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--stage", stage,
             "--chips", str(chips), "--result", result_path],
            env=env, cwd=REPO, start_new_session=True)
        try:
            rc = proc.wait(timeout=STAGE_TIMEOUT_S[stage] + 30)
        except subprocess.TimeoutExpired:
            rc = f"no exit within {STAGE_TIMEOUT_S[stage] + 30} s"
        finally:
            _kill_group(proc)
        if rc != 0:
            print(f"chip_smoke: stage {stage} failed ({rc})", file=sys.stderr)
            return 1
        with open(result_path) as f:
            report[stage] = json.load(f)
    report["wall_s"] = round(time.monotonic() - t_start, 1)
    print(json.dumps({"chip_smoke": report}))
    print(json.dumps({"ok": True, "device": report["a"]["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
