"""The one reduction from a profiler trace (``.xplane.pb``) to numbers.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of a ``/device:`` plane), averaged over the
device planes.  An operation's time is the sum of the durations of the events
of that name.  A gap is a stretch of the window in which no operation ran; the
program has no host annotations yet, so every gap is ``unannotated``.
"""

import glob
import os

OPS_LINE = "XLA Ops"


def find(trace_dir):
    """The newest ``.xplane.pb`` under a profiler output directory."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def short_name(name):
    """A device event is named by its whole HLO instruction
    (``%fusion.3 = bf16[...] fusion(...)``); the instruction's own name is
    what stays the same from run to run."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path):
    """[(plane name, line name, [(event name, start_ns, duration_ns)])]."""
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device and line.name != OPS_LINE:
                continue
            rows.append((plane.name, line.name,
                         [(short_name(e.name) if on_device else e.name,
                           float(e.start_ns), float(e.duration_ns))
                          for e in line.events]))
    return rows


def merge(intervals):
    """Union of [start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_rows(rows, window_ns=None, top=10):
    """``rows`` as ``load`` gives them -> busy seconds (mean over devices),
    seconds by operation name (summed over devices), the idle seconds (mean
    over devices) and the window.  ``window_ns`` (start, end) defaults to
    the span of the device operations."""
    device = {}
    for plane, line, events in rows:
        if plane.startswith("/device:") and line == OPS_LINE and events:
            device.setdefault(plane, []).extend(events)
    if not device:
        return None
    if window_ns is None:
        window_ns = (min(s for ev in device.values() for _, s, _ in ev),
                     max(s + d for ev in device.values() for _, s, d in ev))
    w0, w1 = window_ns
    busy, ops = 0.0, {}
    for events in device.values():
        inside = [(n, s, d) for n, s, d in events if s + d > w0 and s < w1]
        busy += sum(e - s for s, e in merge(
            [max(s, w0), min(s + d, w1)] for _, s, d in inside))
        for n, _, d in inside:
            ops[n] = ops.get(n, 0.0) + d
    n_dev = len(device)
    busy_s, window_s = busy * 1e-9 / n_dev, (w1 - w0) * 1e-9
    op_seconds = {k: v * 1e-9 for k, v in ops.items()}
    return {"devices": n_dev, "busy_s": busy_s, "window_s": window_s,
            "op_seconds": op_seconds,
            "device_ops": sorted(map(list, op_seconds.items()),
                                 key=lambda kv: -kv[1])[:top],
            "idle_gaps": [["unannotated", window_s - busy_s]]}


def op_seconds(reduced, needle):
    """Seconds of the operations whose name holds ``needle``, and how many
    names matched."""
    hits = {k: v for k, v in reduced["op_seconds"].items() if needle in k}
    return sum(hits.values()), len(hits)
