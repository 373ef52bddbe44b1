"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that loads, makes weights and inputs from the seed, takes the
cell's first steps through ``Module.fit`` (they warm every program up and are
what ``correct`` compares), measures for ``--seconds`` seconds through the
same object, frees it, follows the same steps with the configuration's plain
reference, and prints one JSON object as its last line.  Everything about a
cell comes from files found by the names in ``BENCHMARK.json``; see
``benchmark/README.md``.  A run that finds no TPU exits 1 (``DT_FORCE_CPU=1``
is the tests' toy-size rehearsal: it names ``cpu`` and measures nothing).
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)
if REPO not in sys.path:
    sys.path.insert(1, REPO)


def log(msg):
    print(f"# {msg}", flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_reference(path):
    spec = importlib.util.spec_from_file_location(
        "reference_" + os.path.basename(path).split(".")[0].replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(manifest_path, workload):
    """The cell's entries and files, by name."""
    root = os.path.dirname(os.path.abspath(manifest_path))
    manifest = load_json(manifest_path)
    cell = next((w for w in manifest["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in {manifest_path}")
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cfg = load_json(os.path.join(root, entry["file"]))
    bench_dir = os.path.join(root, manifest["paths"][0])
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     cell["traffic"] + ".json"))
    reference = os.path.join(root, cfg["reference"])
    return manifest, cell, cfg, traffic, reference, bench_dir


class CompileCounter:
    """What jax itself reports: backend compiles (a persistent-cache
    retrieval counts as one, and its seconds are the retrieval's) and cache
    hits.  A copy of ``chip_smoke.py``'s."""

    def __init__(self):
        from jax import monitoring
        self.compiles, self.compile_s, self.cache_hits = 0, 0.0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def window_stats(times, steps_per_reading):
    """Completion times of the window's steps, the first being its start ->
    all its steps and all its seconds (what a rate is taken over), and
    beside them the block readings (``steps_per_reading`` steps each; steps
    left over after the last whole block are in the window and in no
    reading), their median, and the share of the window that the rate loses
    against the median reading: what stalls cost."""
    steps = len(times) - 1
    window = times[-1] - times[0] if steps > 0 else 0.0
    edges = times[::steps_per_reading]
    readings = [b - a for a, b in zip(edges, edges[1:])]
    median = statistics.median(readings) if readings else None
    lost = None
    if readings and window > 0:
        lost = 100.0 * (1.0 - median / steps_per_reading * steps / window)
    return {"steps": steps, "window_s": window, "readings": readings,
            "median_s": median, "window_vs_median_pct": lost}


def leaf_gaps(got, want):
    """Per leaf, the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero): [(gap, path)],
    widest first.  The median is taken over the leaves the reference gives
    a norm above zero: where a block starts as the identity most leaves'
    first gradient is exactly zero, and a median of zero is no floor."""
    import jax
    g = [float(x) for x in jax.tree_util.tree_leaves(got)]
    paths, w = zip(*[(jax.tree_util.keystr(p), float(x)) for p, x in
                     jax.tree_util.tree_flatten_with_path(want)[0]])
    if len(g) != len(w):
        return [(float("inf"), "the trees differ")]
    floor = statistics.median([x for x in w if x > 0] or [0.0])
    return sorted(((abs(a - b) / max(b, floor, 1e-30), p)
                   for a, b, p in zip(g, w, paths)), reverse=True)


def worst_leaf_gap(got, want):
    return leaf_gaps(got, want)[0][0]


def _sum_squares(a, b=None, chunk=1 << 22):
    """Sum of the squares of ``a`` (of ``a - b``) in float64, a chunk at a
    time: GPT-2 medium's trees are 1.6 GB each, and whole-tree temporaries
    in float64 took as long as the reference itself."""
    import numpy as np
    a = np.asarray(a, np.float32).ravel()
    b = None if b is None else np.asarray(b, np.float32).ravel()
    total = 0.0
    for i in range(0, a.size, chunk):
        c = a[i:i + chunk].astype(np.float64)
        if b is not None:
            c -= b[i:i + chunk]
        total += float(np.dot(c, c))
    return total


def tree_numbers(got, want):
    """Two readings of a tree against the reference's, leaf by leaf on the
    host: the worst leaf's gap in norm, and the norm of the difference over
    the whole tree against the reference's norm.  The first is second order
    in a rounding error (a norm hardly sees a change of direction) and its
    worst leaves are per-channel sums that cancel; the second is first
    order, and is what tells bfloat16 from float8 in ResNet-50 (PERF.md
    section 2)."""
    import jax
    tmap = jax.tree_util.tree_map
    got_sq, want_sq = tmap(_sum_squares, got), tmap(_sum_squares, want)
    worst = worst_leaf_gap(tmap(lambda v: v ** 0.5, got_sq),
                           tmap(lambda v: v ** 0.5, want_sq))
    diff = sum(jax.tree_util.tree_leaves(tmap(_sum_squares, got, want)))
    size = sum(jax.tree_util.tree_leaves(want_sq))
    return worst, (diff / max(size, 1e-60)) ** 0.5


def compare(program, reference, limits, name, every=False):
    """Each number compared beside its limit -> (rows, all within).  The
    numbers are those the configuration gives a limit (``every``: all the
    harness can read, the others beside no limit, for ``control.py``)."""
    found = [(f"loss.step{i + 1}", "loss_rel", abs(a - b) / abs(b))
             for i, (a, b) in enumerate(zip(program["losses"],
                                            reference["losses"]))]
    for tree in ("first_gradient", "param_change"):
        if every or any(k.startswith(tree) for k in limits):
            worst, difference = tree_numbers(program[tree], reference[tree])
            found += [(tree + ".worst_leaf", tree + "_worst_leaf", worst),
                      (tree + ".difference", tree + "_difference", difference)]
    rows = [(what, value, limits.get(key, float("inf")))
            for what, key, value in found if every or key in limits]
    ok = True
    for what, value, limit in rows:
        within = value == value and value <= limit
        ok = ok and within
        log(f"compare config={name} number={what} value={value:.6g} "
            f"limit={limit:.6g} {'ok' if within else 'OVER'}")
    return rows, ok


def quartiles(values):
    if len(values) < 2:
        return [values[0]] * 3 if values else []
    return statistics.quantiles(values, n=4)


def start(manifest_path, workload, seed, say=""):
    """Everything of a run that comes before the seed's inputs: the cell's
    files, the device (a TPU with enough chips, or nothing), the compile
    cache and the job.  Shared with ``control.py``.  Returns None where the
    device will not do."""
    manifest, cell, cfg, traffic, ref_path, bench_dir = load_cell(
        manifest_path, workload)
    chips = int(cell["chips"])
    if bench_dir not in sys.path:   # a copy with files a later PR added
        sys.path.insert(1, bench_dir)
    from dt_tpu import config as dt_config
    forced_cpu = dt_config.maybe_force_cpu()
    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    log(f"platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']} workload={cell['name']} seed={seed}{say}")
    if not forced_cpu and (dev["platform"] != "tpu" or dev["count"] < chips):
        print(f"benchmark: {cell['name']} needs {chips} TPU chip(s); JAX "
              f"found {dev['count']} x {dev['platform']} ({dev['kind']})",
              file=sys.stderr)
        return None
    if not forced_cpu:
        dt_config.enable_compilation_cache()
    counter = CompileCounter()
    t_imported = time.perf_counter()
    import readers
    reference = load_reference(ref_path)
    job = readers.resolve(cfg["driver"])(cfg, traffic, chips, seed)
    return argparse.Namespace(
        manifest=manifest, cell=cell, cfg=cfg, traffic=traffic, chips=chips,
        bench_dir=bench_dir, forced_cpu=forced_cpu, dev=dev, counter=counter,
        reference=reference, job=job, t_imported=t_imported)


def seed_inputs(su, seed):
    """The inputs and the weights of one seed -> (batches, feed, key); the
    job's state is made on the device in one jitted call."""
    import jax
    import traffic as traffic_lib
    from dt_tpu import data as dt_data
    batches = traffic_lib.generate(su.traffic, su.cfg, seed)
    feed = traffic_lib.Feed(batches, dt_data.DataBatch, cast=su.job.cast)
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    jax.block_until_ready(su.job.make_state(su.reference.init, key))
    return batches, feed, key


def follow(su, batches, key, precision="float32"):
    """The plain reference over the same steps, its trees under the
    program's names."""
    shards = [(d[None], lb[None]) for d, lb in batches]
    ref = su.reference.train(key, shards, su.cfg, su.cfg["check"]["steps"],
                             precision=precision)
    for k in ("first_gradient", "param_change"):
        ref[k] = su.job.program_tree(ref[k])
    return ref


def log_memory(job, when):
    stats = job.memory_stats()
    log(f"memory when={when} " + " ".join(
        f"{k}={stats[k]}" for k in sorted(stats) if "bytes" in k))
    return stats


def traced(su, clock, trace):
    """The traced steps' ``.xplane.pb`` -> (its reduction, its path); None
    where the trace holds no device operation."""
    import xplane
    path = xplane.find(trace["dir"])
    span = clock.trace_span
    rows = xplane.load(path) if path else []
    whole = xplane.whole_steps(rows)
    reduced = xplane.reduce_rows(rows, window_ns=whole and whole[0])
    if span is None or (reduced is None and not su.forced_cpu):
        print("benchmark: the traced window holds no device operation "
              "(it closed before the profiler started?)", file=sys.stderr)
        return None
    if reduced is None:  # the CPU rehearsal: its trace has no device
        reduced = {"devices": 1, "busy_s": 0.0, "window_s": 0.0,
                   "op_seconds": {}, "op_events": {}, "scope_seconds": {},
                   "device_ops": [], "idle_gaps": []}
    elif xplane.scopes_missing(reduced):
        log("scopes_missing: under half of the busy time carries a scope "
            "(an executable cached before the program had scopes?); the "
            "split by scope is left out")
    if whole:   # the device's own step edges
        reduced["steps"] = whole[1]
        reduced["host_window_s"] = reduced["window_s"]
    else:
        # under three executions of the step's program in the trace: the
        # host's count of steps, over its clock around them or the span of
        # the device's own events where that is longer (the device finishes
        # the step in flight after the host has asked the profiler to stop)
        log("trace_edges: the trace is not cut to whole steps")
        reduced["steps"] = span[3] - span[2]
        reduced["host_window_s"] = max(span[1] - span[0],
                                       reduced["window_s"])
    reduced["idle_gaps"] = [["unannotated", reduced["host_window_s"]
                             - reduced["busy_s"]]]
    log(f"trace steps={reduced['steps']} busy_s={reduced['busy_s']:.6f} "
        f"device_span_s={reduced['window_s']:.6f} "
        f"host_window_s={reduced['host_window_s']:.6f} "
        f"stop_trace_s={clock.stop_trace_s:.3f} file={path} "
        f"bytes={os.path.getsize(path) if path else 0}")
    return reduced, path


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--manifest", default=os.path.join(REPO, "BENCHMARK.json"),
                    help="(tests) another BENCHMARK.json")
    args = ap.parse_args(argv)

    su = start(args.manifest, args.workload, args.seed,
               f" run_seconds={args.seconds:g} trace={args.trace}")
    if su is None:
        return 1
    import drivers
    import readers
    job, cfg, traffic, counter = su.job, su.cfg, su.traffic, su.counter
    t_import = su.t_imported

    # -- the inputs and the weights, all from the seed ---------------------
    batches, feed, key = seed_inputs(su, args.seed)
    t_init = time.perf_counter()
    log_memory(job, "weights_made")

    # -- the first steps: warm-up, and what `correct` compares ------------
    program = job.first_steps(feed, key, cfg["check"]["steps"])
    setup_compiles, setup_compile_s = counter.compiles, counter.compile_s
    log_memory(job, "first_steps_done")

    # -- the window --------------------------------------------------------
    trace = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="trace_", dir=os.environ.get(
            "TMPDIR") or None)
        trace = {"dir": trace_dir, "last_s": traffic["trace_last_s"]}
    t_window = time.perf_counter()
    clock = job.run_window(feed, args.seconds, trace)
    setup_s = clock.times[0] - T_PROCESS
    spr = traffic["steps_per_reading"]
    stats = window_stats(clock.times, spr)
    steps = stats["steps"]
    in_window = counter.compiles - setup_compiles
    peak = drivers.peak_bytes(log_memory(job, "window_done"))
    log(f"steps={steps} window_s={stats['window_s']:.4f} "
        f"readings={len(stats['readings'])} steps_per_reading={spr} "
        f"quartiles_s={[round(v, 6) for v in quartiles(stats['readings'])]} "
        f"window_vs_median_pct={stats['window_vs_median_pct']} "
        f"compiles_in_window={in_window} setup_s={setup_s:.3f} "
        f"loadavg={os.getloadavg()[0]:.2f} cores={os.cpu_count()}")
    for row in clock.slow_steps():
        log("slow_step step=%d at_s=%.3f wall_s=%.4f thread_cpu_s=%.4f "
            "process_cpu_s=%.4f involuntary_switches=%d gc_s=%.4f "
            "process_stood_still_s=%.4f" % row)

    # -- free the program, then follow the same steps with the reference --
    t_check = time.perf_counter()
    job.release()
    ref = follow(su, batches, key)
    rows, correct = compare(program, ref, cfg["check"]["limits"], cfg["name"])
    check_s = time.perf_counter() - t_check
    if in_window:
        log(f"OVER: {in_window} compile(s) inside the window")
    correct = bool(correct and in_window == 0 and steps > 0)

    # -- the result line -----------------------------------------------------
    per_item = traffic["batch"] * traffic.get("seq_len", 1)
    ctx = {
        "cfg": cfg, "traffic": traffic, "chips": su.chips,
        "items_per_step": per_item, "readings": stats["readings"],
        "median_reading_s": stats["median_s"], "window_s": stats["window_s"],
        "window_vs_median_pct": stats["window_vs_median_pct"],
        "steps": steps, "steps_per_reading": spr,
        "compiles_in_window": in_window, "peak_bytes": peak,
        "device_kind": su.dev["kind"], "bench_dir": su.bench_dir,
        "setup": {"import_s": t_import - T_PROCESS,
                  "init_s": t_init - t_import,
                  "compile_s": setup_compile_s,
                  "warm_s": t_window - t_init, "check_s": check_s},
        "setup_s": setup_s, "rehearsal": su.forced_cpu,
        # for a reader in a module a later PR adds: the job (the program's
        # own counters hang on it), the traced steps' reduction, and the
        # trace's file, kept until every reader has returned
        "job": job, "trace": None, "trace_path": None,
    }
    device = {**su.dev, "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": steps, "failed": 0}
    try:
        if args.trace:
            found = traced(su, clock, trace)
            if found is None:
                return 1
            reduced, ctx["trace_path"] = found
            ctx["trace"] = reduced
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["host_window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        result["metrics"] = readers.collect(su.manifest, su.cell, ctx,
                                            bool(args.trace))
    finally:
        if trace is not None:
            shutil.rmtree(trace["dir"], ignore_errors=True)
    result["device"] = device
    for name, m in result["metrics"].items():
        log(f"metric {name}={m['value']} {m['unit']}")
    # each number compared beside its limit: last in the line, and the last
    # lines on standard error
    result["compared"] = {what: {"value": value, "limit": limit}
                          for what, value, limit in rows}
    result["compared"]["compiles_in_window"] = {"value": in_window,
                                                "limit": 0}
    sys.stdout.flush()
    for what, row in result["compared"].items():
        print(f"compared {what} value={row['value']:.6g} "
              f"limit={row['limit']:.6g}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
