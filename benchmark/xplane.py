"""The one reduction from a profiler trace (``.xplane.pb``) to numbers.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of a ``/device:`` plane), averaged over the
device planes.  An operation's time is the sum of the durations of the events
of that name; its scope is the path of the program's ``jit``s, transforms,
``named_scope``s and flax modules it was traced under
(``jit(train_step)/transpose(jvp(forward))/block3/mlp_in/dot_general``), so
the busy time is also kept by scope (``self_ns``).  ``whole_steps`` cuts the
window to the device's own step edges.  A gap is a stretch of the window in
which no operation ran; the benchmark's trace keeps the host tracers off, so
every gap is ``unannotated``.
"""

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"    # one event an execution of a whole program
SCOPE_STAT = "tf_op"


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


def _varint(buf, i):
    """The varint at ``buf[i:]`` -> (value, index after it)."""
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf):
    """A protobuf message's fields as (number, value): a varint as int, a
    length-delimited field as bytes; fixed-width fields are skipped."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield number, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        else:
            i += 8 if wire == 1 else 4


def event_scopes(path):
    """{device plane name: {event name: scope}}.  The profiler keeps an
    operation's name and statistics once, in the plane's event metadata
    (which ``jax.profiler.ProfileData`` does not show), and an event only
    points there; the scope is the metadata's ``tf_op`` statistic.  Read raw
    (``xplane.proto``: XSpace.planes = 1; XPlane.name = 2, .event_metadata =
    4, .stat_metadata = 5, both maps with key = 1 and value = 2;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1, .str_value =
    5, .ref_value = 7, a string kept as a statistic's name; XStatMetadata
    .name = 2).  An operation without the statistic is left out."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for num, val in _fields(plane):
            if num == 2:
                name = val.decode("utf-8", "replace")
            elif num in (4, 5):
                entry = dict(_fields(val))
                if num == 4:
                    events.append(entry.get(2, b""))
                else:
                    stat_names[entry.get(1, 0)] = dict(_fields(
                        entry.get(2, b""))).get(2, b"").decode(
                            "utf-8", "replace")
        if not name.startswith("/device:"):
            continue
        scopes = out.setdefault(name, {})
        for meta in events:
            event_name = scope = None
            for num, val in _fields(meta):
                if num == 2:
                    event_name = val.decode("utf-8", "replace")
                elif num == 5:
                    st = dict(_fields(val))
                    if stat_names.get(st.get(1)) != SCOPE_STAT:
                        continue
                    if isinstance(st.get(5), bytes):
                        scope = st[5].decode("utf-8", "replace")
                    elif 7 in st:
                        scope = stat_names.get(st[7])
            if event_name and scope:
                scopes.setdefault(event_name, scope.rstrip(":"))
    return out


def load(path):
    """[(plane name, line name, [(event name, start_ns, duration_ns,
    scope)])]; the scope is "" off the device and where the operation
    carries none."""
    from jax.profiler import ProfileData
    scopes = event_scopes(path)
    rows = []
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith("/device:")
        scope_of = scopes.get(plane.name, {})
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            rows.append((plane.name, line.name,
                         [(short_name(e.name) if on_device else e.name,
                           float(e.start_ns), float(e.duration_ns),
                           scope_of.get(e.name, ""))
                          for e in line.events]))
    return rows


def whole_steps(rows):
    """The traced window cut to whole steps -> ((start_ns, end_ns), steps),
    or None where the trace holds under three executions of a program.
    The profiler starts and stops in the middle of a step (the host asks
    between two completions, while the device runs the step dispatched
    ahead), so the first and the last execution of the step's program are
    cut short, and busy time over the host's count of steps under-reads a
    step by what the first lost (2% of twenty ResNet-50 steps).  The step's
    program is the one whose executions took most time on the first device
    that ran one; its first and last execution are dropped, and the window
    runs from the start of the first one left to the start of the last, so
    that the gap after each step is in it."""
    for plane, line, events in rows:
        if not (plane.startswith("/device:") and line == MODULES_LINE):
            continue
        seconds = {}
        for name, _, d, _ in events:
            seconds[name] = seconds.get(name, 0.0) + d
        if not seconds:
            continue
        step = max(seconds, key=seconds.get)
        starts = sorted(s for name, s, _, _ in events if name == step)
        if len(starts) < 3:
            return None
        return (starts[1], starts[-1]), len(starts) - 2
    return None


def phase(scope):
    """The first part of a scope path that is neither a ``jit`` nor the
    primitive the path ends in: ``jit(train_step)/jit(main)/optimizer/mul``
    -> ``optimizer``; ``jit(train_step)/mul`` -> ``""``."""
    for part in scope.split("/")[:-1]:
        if part and not part.startswith(("jit(", "pjit(")):
            return part
    return ""


def merge(intervals):
    """Union of [start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_ns(events):
    """One device's events -> the nanoseconds each ran as the innermost
    one: every instant in which an operation ran goes to the operation
    that started last among those running.  Where none overlap this is each
    event's duration; where they nest (a ``while``, a ``conditional`` or a
    call and its body) or overlap, the parts still add up to the busy time,
    which summed durations do not."""
    own = [0.0] * len(events)
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    stack, t = [], 0.0
    for i in order + [None]:
        s = float("inf") if i is None else events[i][1]
        while stack and t < s:
            top = stack[-1]
            end = events[top][1] + events[top][2]
            if end > t:
                own[top] += min(end, s) - t
                t = min(end, s)
            if end <= s:
                stack.pop()
        t = s
        stack.append(i)
    return own


def reduce_rows(rows, window_ns=None, top=10):
    """``rows`` as ``load`` gives them -> busy seconds (mean over devices),
    seconds and events by operation name (its events' durations and their
    number, summed over devices: an operation that a ``while`` runs several
    times a step has that many events), seconds by scope path (``self_ns``,
    summed over devices: they add up to the busy time; "" holds the
    operations that carry no scope), the idle
    seconds (mean over devices) and the window.  ``device_ops`` names the
    operations that took most time, each with its phase before it
    (``optimizer/fusion.7``).  ``window_ns`` (start, end) defaults to the
    span of the device operations."""
    device = {}
    for plane, line, events in rows:
        if plane.startswith("/device:") and line == OPS_LINE and events:
            device.setdefault(plane, []).extend(events)
    if not device:
        return None
    if window_ns is None:
        window_ns = (min(e[1] for ev in device.values() for e in ev),
                     max(e[1] + e[2] for ev in device.values() for e in ev))
    w0, w1 = window_ns
    busy, ops, counts, scopes, phases = 0.0, {}, {}, {}, {}
    for events in device.values():
        inside = [e for e in events if e[1] + e[2] > w0 and e[1] < w1]
        clipped = [(n, max(s, w0), min(s + d, w1) - max(s, w0), scope)
                   for n, s, d, scope in inside]
        busy += sum(e - s for s, e in merge(
            [s, s + d] for _, s, d, _ in clipped))
        for (n, _, d, scope), own in zip(inside, self_ns(clipped)):
            ops[n] = ops.get(n, 0.0) + d
            counts[n] = counts.get(n, 0) + 1
            scopes[scope] = scopes.get(scope, 0.0) + own
            phases.setdefault(n, phase(scope))
    n_dev = len(device)
    busy_s, window_s = busy * 1e-9 / n_dev, (w1 - w0) * 1e-9
    op_seconds = {k: v * 1e-9 for k, v in ops.items()}
    return {"devices": n_dev, "busy_s": busy_s, "window_s": window_s,
            "op_seconds": op_seconds, "op_events": counts,
            "scope_seconds": {k: v * 1e-9 for k, v in scopes.items()},
            "device_ops": [[(phases[k] + "/" if phases[k] else "") + k, v]
                           for k, v in sorted(op_seconds.items(),
                                              key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [["unannotated", window_s - busy_s]]}


def op_seconds(reduced, needle):
    """Seconds of the operations whose name holds ``needle``, and how many
    names matched."""
    hits = {k: v for k, v in reduced["op_seconds"].items() if needle in k}
    return sum(hits.values()), len(hits)


def op_events(reduced, needle):
    """Events of the operations whose name holds ``needle``: how often they
    ran in the window, whatever their names (``flash_bwd.29``,
    ``flash_bwd.35``: one a layer; one name under a ``while``: one event an
    iteration)."""
    return sum(v for k, v in reduced["op_events"].items() if needle in k)


def scope_matches(scope, holds=(), lacks=()):
    """The scope path holds every string of ``holds`` and none of
    ``lacks``."""
    return all(h in scope for h in holds) and \
        not any(x in scope for x in lacks)


def scope_seconds(reduced, holds=(), lacks=()):
    """Seconds of the operations whose scope path matches."""
    return sum(v for k, v in reduced["scope_seconds"].items()
               if scope_matches(k, holds, lacks))


def scopes_missing(reduced):
    """Under half of the busy time carries any scope: the executable came
    from a compile cache written before the program had scopes (the cache's
    key leaves metadata out), and a split by scope would be a split of
    nothing."""
    scoped = sum(v for k, v in reduced["scope_seconds"].items() if k)
    return scoped < 0.5 * reduced["busy_s"] * reduced["devices"]
