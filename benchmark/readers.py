"""Readers: one small function per kind of metric.

A metric is an entry in ``BENCHMARK.json`` (name, unit, source, layer, what
it moves) and a file ``metrics/<name>.json`` that names its reader here (or
in a module a later PR adds beside this one) as ``module:function``, with the
arguments the reader takes.  Where a quantity is split by cell because the
cells' end-to-end metrics differ (``device.idle_pct.lm``), the split names
share the file of the name before the last dot.  A reader gets the run's
context and the metric's file and returns a number, or None where it finds
nothing to read: the harness then leaves the metric out of the line.
"""

import importlib
import json
import os


def resolve(spec):
    """``module:attribute`` -> the attribute, the module found beside this
    file (a later PR adds modules; it edits none)."""
    module, attr = spec.split(":")
    return getattr(importlib.import_module(module), attr)


def throughput(ctx, m):
    """Items per second per chip: all the steps completed between the
    window's edges over all its seconds, stalls included."""
    if not ctx["steps"]:
        return None
    return ctx["items_per_step"] * ctx["steps"] / ctx["window_s"] \
        / ctx["chips"]


def median_reading_rate(ctx, m):
    """Items per second per chip from the median block reading: the pace
    while nothing stalls.  Beside ``throughput`` it says what stalls cost."""
    if not ctx["readings"]:
        return None
    return ctx["items_per_step"] * ctx["steps_per_reading"] \
        / ctx["median_reading_s"] / ctx["chips"]


def value(ctx, m):
    """A number the runner already holds: ``args.key`` names it in the
    context, dotted for a nested one (``setup.import_s``)."""
    out = ctx
    for part in m["args"]["key"].split("."):
        out = out[part]
    return out


def counter_share_pct(ctx, m):
    """The share (%) that ``args.key`` has of a counter the program keeps on
    the job: ``args.counter`` is the dotted path from ``ctx["job"]`` to a
    dict of counts (``mod.metric_flushes``: which way each step's metric
    went in the window's ``fit`` call)."""
    counts = ctx["job"]
    for part in m["args"]["counter"].split("."):
        counts = getattr(counts, part, None)
    if not isinstance(counts, dict) or not sum(counts.values()):
        return None
    return 100.0 * counts.get(m["args"]["key"], 0) / sum(counts.values())


def peak_hbm_gb(ctx, m):
    return ctx["peak_bytes"] / 1e9 if ctx["peak_bytes"] else None


def _peaks(ctx):
    """The device's row of peaks.json; None in the CPU rehearsal (which has
    no peak and reports no utilization); an unknown device is an error."""
    if ctx["rehearsal"]:
        return None
    with open(os.path.join(ctx["bench_dir"], "peaks.json")) as f:
        peaks = json.load(f)
    if ctx["device_kind"] not in peaks:
        raise KeyError(f"no peak for device kind {ctx['device_kind']!r} in "
                       "peaks.json: add it with its source")
    return peaks[ctx["device_kind"]]


def device_idle_pct(ctx, m):
    tr = ctx["trace"]
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["host_window_s"])


def device_ms_per_step(ctx, m):
    tr = ctx["trace"]
    if not tr or not tr["steps"]:
        return None
    return 1e3 * tr["busy_s"] / tr["steps"]


def mfu_pct(ctx, m):
    """The model step's utilization: operations the mathematics needs for
    the traced steps, over the seconds the device was busy in them and the
    chips' peak.  From the trace, so that it moves with the model step and
    not with the host that feeds it."""
    tr = ctx["trace"]
    peaks = _peaks(ctx)
    if not tr or not tr["steps"] or not tr["busy_s"] or peaks is None:
        return None
    flops = resolve(ctx["cfg"]["train_flops_per_item"])(ctx["cfg"],
                                                        ctx["traffic"])
    return 100.0 * flops * ctx["items_per_step"] * tr["steps"] \
        / (tr["busy_s"] * ctx["chips"]) / peaks["bf16_flops_per_s"]


def scope_ms_per_step(ctx, m):
    """Device milliseconds a traced step in the operations whose scope path
    holds every string of ``args.holds`` and none of ``args.lacks`` (scopes,
    transforms and flax module names are all parts of the path); None where
    the operations carry no scopes (``xplane.scopes_missing``)."""
    import xplane
    tr = ctx["trace"]
    if not tr or not tr["steps"] or not tr["busy_s"] or \
            xplane.scopes_missing(tr):
        return None
    args = m.get("args", {})
    return 1e3 * xplane.scope_seconds(tr, args.get("holds", ()),
                                      args.get("lacks", ())) \
        / tr["steps"] / tr["devices"]


def unscoped_pct(ctx, m):
    """The share (%) of the traced busy time in operations that none of the
    metrics ``args.none_of`` counts (their files say what each holds and
    lacks): what carries another scope, or none."""
    import xplane
    tr = ctx["trace"]
    if not tr or not tr["busy_s"]:
        return None
    splits = []
    for name in m["args"]["none_of"]:
        with open(metric_file(ctx["bench_dir"], name)) as f:
            splits.append(json.load(f).get("args", {}))
    rest = sum(v for k, v in tr["scope_seconds"].items() if not any(
        xplane.scope_matches(k, a.get("holds", ()), a.get("lacks", ()))
        for a in splits))
    return 100.0 * rest / (tr["busy_s"] * tr["devices"])


def _kernel(ctx, m):
    """Seconds and events a traced step of the operations whose name holds
    ``args.op_name_holds``; None where the trace holds none."""
    import xplane
    tr = ctx["trace"]
    if not tr or not tr["steps"]:
        return None
    needle = m["args"]["op_name_holds"]
    seconds, names = xplane.op_seconds(tr, needle)
    if not names:
        return None
    return seconds / tr["steps"], xplane.op_events(tr, needle) / tr["steps"]


def kernel_ms_per_step(ctx, m):
    found = _kernel(ctx, m)
    return None if found is None else 1e3 * found[0]


def kernel_roofline_pct(ctx, m):
    """The least time the chip could take for the kernel's calls of one step
    (the larger of operations over peak and bytes over peak, for each event
    of the kernel the traced steps hold: what a block's recomputation
    repeats or a kept value spares is counted as it ran) over the time they
    took."""
    found = _kernel(ctx, m)
    peaks = _peaks(ctx)
    if not found or not found[0] or peaks is None:
        return None
    per_step, calls = found
    shape = {k: (ctx["traffic"].get(v, ctx["cfg"].get(v))
                 if isinstance(v, str) else v)
             for k, v in m["args"]["shape"].items()}
    ops, nbytes = resolve(m["args"]["ops_bytes"])(**shape)
    least = max(ops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    print(f"# kernel {m['args']['op_name_holds']} events_per_step={calls:g} "
          f"least_ms_per_call={1e3 * least:.4f} "
          f"ms_per_call={1e3 * per_step / calls:.4f}", flush=True)
    return 100.0 * least * calls / per_step


def metric_file(bench_dir, name):
    """``metrics/<name>.json``, or that of the name before the last dot
    (``device.idle_pct.lm`` reads ``device.idle_pct.json``)."""
    while name:
        path = os.path.join(bench_dir, "metrics", name + ".json")
        if os.path.isfile(path):
            return path
        name = name.rpartition(".")[0]
    return None


def collect(manifest, cell, ctx, traced):
    """The cell's metrics for this kind of run, each from its own file."""
    out = {}
    for entry in manifest["per_layer" if traced else "end_to_end"]:
        if "workloads" in entry and cell["name"] not in entry["workloads"]:
            continue
        path = metric_file(ctx["bench_dir"], entry["name"])
        if path is None:
            raise FileNotFoundError(f"no metrics/ file for {entry['name']!r}")
        with open(path) as f:
            m = json.load(f)
        value = resolve(m["reader"])(ctx, m)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out
