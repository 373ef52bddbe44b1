"""clock_offset.py — are the program's spans on the profiler's clock?

    python tools/clock_offset.py [--steps 3] [--out chiprun_out/clock_offset.json]

``Module.fit``'s step account and spans start on ``time.time_ns()``
(``dt_tpu/obs/trace.py``).  A reader that wants to name the device's idle
gaps from them has to lay them over a ``.xplane.pb``, whose events the
profiler stamps with a clock of its own.  This script shows how far apart the
two are instead of assuming it: with ``DT_OBS=1`` every span of the loop also
enters a ``jax.profiler`` annotation of its name, so one profiler session
with the host tracer at level 1 (the lowest that records annotations) holds
the same boundary twice.  It takes ``--steps`` steps of a small model through
``Module.fit`` inside such a session, reads each annotation's start from the
``.xplane.pb`` (``profile_start_time`` of the ``Task Environment`` plane plus
the event's offset) and the same span's start from the ring, and prints the
differences.  It also counts the device operations that carry each of the
step program's ``named_scope``s (``forward``, ``optimizer``), as
``benchmark/xplane.py`` ``event_scopes`` reads them.

It runs on whatever device JAX finds and names it: a time from a CPU run is
a rehearsal of the script, not a reading of the chip.  Reference analog: the
reference's profiler stamped operators and left the loop around them dark
(``src/profiler/profiler.h:256``).
"""

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SCOPES = ("forward", "optimizer")


def annotation_starts(path, names):
    """{name: [absolute start_ns, ...]} of the host events called one of
    ``names``, oldest first, and how many device operations the trace
    holds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    t0 = None
    for plane in data.planes:
        if plane.name == "Task Environment":
            t0 = dict(plane.stats).get("profile_start_time")
    if t0 is None:
        raise RuntimeError("no profile_start_time in the trace: the events' "
                           "offsets cannot be placed on the wall clock")
    starts, device_ops = {}, 0
    for plane in data.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device:
                device_ops += len(list(line.events)) \
                    if line.name == "XLA Ops" else 0
                continue
            for ev in line.events:
                if ev.name in names:
                    starts.setdefault(ev.name, []).append(
                        int(t0) + int(ev.start_ns))
    return {k: sorted(v) for k, v in starts.items()}, device_ops


def scoped_operations(path):
    """({scope: device operations traced under it}, one scoped operation as
    a sample): ``benchmark/xplane.py`` ``event_scopes`` reads an
    operation's scope path from the plane's event metadata."""
    from benchmark.xplane import event_scopes
    found, sample = {s: 0 for s in SCOPES}, None
    for plane, scopes in sorted(event_scopes(path).items()):
        for op, scope in sorted(scopes.items()):
            hits = [s for s in SCOPES if s in scope]
            for s in hits:
                found[s] += 1
            if hits and sample is None:
                sample = {"plane": plane, "name": op, "scope": scope[:300]}
    return found, sample


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out", default=None, help="also write the result here")
    args = ap.parse_args(argv)

    import flax.linen as nn
    import jax
    import numpy as np
    from dt_tpu import config as dt_config, data
    from dt_tpu.obs import trace as obs_trace
    from dt_tpu.training import Module

    dt_config.maybe_force_cpu()
    dev = jax.devices()[0]

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x, training=True):
            x = nn.relu(nn.Dense(1024)(x.reshape((x.shape[0], -1))))
            return nn.Dense(10)(x)

    rng = np.random.RandomState(0)
    x = rng.normal(size=(256 * args.steps, 32, 32, 3)).astype(np.float32)
    y = rng.randint(0, 10, len(x)).astype(np.int32)
    mod = Module(Net(), optimizer="sgd",
                 optimizer_params={"learning_rate": 0.01}, seed=0)
    obs_trace.set_enabled(True)
    tracer = obs_trace.tracer()
    mod.fit(data.NDArrayIter(x[:256], y[:256], batch_size=256))  # compiles
    jax.block_until_ready(mod.state)
    tracer.drain()

    trace_dir = tempfile.mkdtemp(prefix="clock_offset_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1   # the lowest that records annotations
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        mod.fit(data.NDArrayIter(x, y, batch_size=256),
                batch_end_callback=lambda p: None)
        jax.block_until_ready(mod.state)
    finally:
        jax.profiler.stop_trace()
    spans = {}
    for rec in tracer.drain():
        if rec[0] == "X":
            spans.setdefault(rec[2], []).append(rec[3])   # name -> ts_us
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    starts, device_ops = annotation_starts(path, set(spans))
    scoped, sample = scoped_operations(path)

    per_name, offsets_us = {}, []
    for name, ring in sorted(spans.items()):
        seen = starts.get(name, [])
        if len(seen) != len(ring):
            print(f"# {name}: {len(ring)} spans in the ring, {len(seen)} "
                  "annotations in the trace: not matched", flush=True)
            continue
        # annotation start (ns) less span start (the ring keeps us)
        diffs = [a / 1e3 - s for a, s in zip(seen, sorted(ring))]
        per_name[name] = {"n": len(diffs),
                          "median_us": statistics.median(diffs),
                          "min_us": min(diffs), "max_us": max(diffs)}
        offsets_us += diffs
    if not offsets_us:
        print("clock_offset: no annotation of the loop's spans in the trace",
              file=sys.stderr)
        return 1
    result = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "steps": args.steps, "host_tracer_level": 1,
        "matched": len(offsets_us),
        "offset_us": {"median": statistics.median(offsets_us),
                      "min": min(offsets_us), "max": max(offsets_us)},
        "per_span": per_name,
        "device_ops_in_trace": device_ops,
        "scope_found_in": scoped, "scoped_operation_sample": sample,
        "xplane_bytes": os.path.getsize(path),
    }
    line = json.dumps(result)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
