#!/usr/bin/env python
"""dtop — terminal summary of a dt_tpu.obs job timeline.

Renders step-time percentiles, stall attribution, the r13 critical-path
split (compute / d2h / send / server queue / straggler-wait / reply /
h2d), the straggler board, the r14 policy-decisions section (current
batch shares, breach streaks, decision timeline — ``docs/policy.md``),
the r15 health board (active SLO breaches with the blamed worker,
breach/clear timeline, per-worker training-health gauges —
``dt_tpu/obs/metrics.py``), the r21 serving board (per-replica QPS /
p99 / queue-depth gauges, served weights step, refresh counts, and the
autoscale decision log — ``docs/serving.md``), per-worker retry/fault
counts, and the membership/leadership timeline from either a merged
chrome trace
written by ``dt_tpu.obs.export`` (e.g. ``tools/chaos_run.py --trace
out.json``) or a LIVE scheduler (the ``obs_dump`` control command — the
job-level counterpart of the reference's remote profiler dump,
``kvstore_dist_server.h:275-322``).

Usage::

    python tools/dtop.py /tmp/trace.json
    python tools/dtop.py --scheduler 127.0.0.1:9091
    python tools/dtop.py --scheduler 127.0.0.1:9091 --follow   # live
    python tools/dtop.py /tmp/trace.json --critical-path 3     # one step
    python tools/dtop.py /tmp/trace.json --json   # machine-readable
    python tools/dtop.py --postmortem .blackbox   # r16 crash report
    python tools/dtop.py --postmortem .blackbox/bb-...json     # one bundle

``--follow`` polls ``obs_dump`` every ``--interval`` seconds and
re-renders a compact live board (step rate since the previous poll,
critical-path split, straggler board, membership/leadership events);
``--iterations`` bounds the loop (0 = until interrupted — tests run one
cycle).  ``--critical-path N`` drills into step N's decomposition on
every worker track.

jax-free: loads only ``dt_tpu.obs.export`` (and the wire protocol for
``--scheduler``).
"""

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

# Import dt_tpu.obs/.elastic WITHOUT executing dt_tpu/__init__.py (which
# pulls the ops surface and therefore jax): register a path-only shim for
# the parent package first — same trick as tools/dtlint.py.  Under pytest
# dt_tpu is already real and the shim is skipped.
if "dt_tpu" not in sys.modules:
    import types
    _shim = types.ModuleType("dt_tpu")
    _shim.__path__ = [os.path.join(_ROOT, "dt_tpu")]
    sys.modules["dt_tpu"] = _shim


def _load_chrome(args):
    from dt_tpu.obs import export as obs_export
    if args.scheduler:
        resp = _sched_request(args.scheduler, {"cmd": "obs_dump"},
                              timeout=30)
        return obs_export.chrome_trace(resp["job"])
    if not args.trace:
        raise SystemExit("give a trace file or --scheduler host:port")
    with open(args.trace) as f:
        return json.load(f)


def _fmt_ms(v):
    return f"{v:10.1f}"


def render(summary) -> str:
    lines = []
    tracks = summary.get("tracks", {})
    worker_tracks = sorted(t for t in tracks if t != "control-plane")
    lines.append(f"{'track':<22}{'steps':>7}{'p50 ms':>10}{'p90 ms':>10}"
                 f"{'p99 ms':>10}{'stall ms':>10}{'retries':>9}"
                 f"{'faults':>8}{'drop':>6}")
    for name in worker_tracks + (["control-plane"]
                                 if "control-plane" in tracks else []):
        t = tracks[name]
        st = t["steps"]
        stall = sum(t.get("stall_ms", {}).values())
        nfaults = sum(t.get("faults", {}).values())
        lines.append(
            f"{name:<22}{st['count']:>7}{_fmt_ms(st['p50_ms'])}"
            f"{_fmt_ms(st['p90_ms'])}{_fmt_ms(st['p99_ms'])}"
            f"{_fmt_ms(stall)}{t.get('retries', 0):>9}{nfaults:>8}"
            f"{t.get('dropped', 0):>6}")
    # stall attribution: where did waiting time go, per worker
    lines.append("")
    lines.append("stall attribution (ms):")
    for name in worker_tracks:
        stall = tracks[name].get("stall_ms", {})
        if stall:
            parts = "  ".join(f"{k}={v:.1f}"
                              for k, v in sorted(stall.items()))
            lines.append(f"  {name:<20}{parts}")
    # overlap-pipeline split: the allreduce stall above, broken into the
    # d2h/wire/h2d stage spans of the bucketed host-sync pipeline (these
    # run concurrently, so the stage sums exceed the stall wall-clock
    # exactly when the overlap is working)
    pipe_any = any(tracks[n].get("pipeline_ms") for n in worker_tracks)
    if pipe_any:
        lines.append("")
        lines.append("pipeline stages (ms; concurrent — sums exceed the "
                     "allreduce stall when overlap works):")
        for name in worker_tracks:
            pm = tracks[name].get("pipeline_ms", {})
            if pm:
                parts = "  ".join(f"{k}={v:.1f}"
                                  for k, v in sorted(pm.items()))
                nb = tracks[name].get("pipeline_buckets", 0)
                lines.append(f"  {name:<20}{parts}  buckets={nb}")
    faults_any = any(tracks[n].get("faults") for n in tracks)
    if faults_any:
        lines.append("")
        lines.append("fault events:")
        for name in sorted(tracks):
            f = tracks[name].get("faults", {})
            if f:
                parts = "  ".join(f"{k}={v}" for k, v in sorted(f.items()))
                lines.append(f"  {name:<20}{parts}")
    # r13 critical path: where each worker's step time actually went —
    # decomposed via the cross-process span join (docs/observability.md)
    cp = summary.get("critical_path", {})
    if cp:
        lines.append("")
        lines.append("critical path (ms, totals over steps; stage spans "
                     "overlap, so sums can exceed step wall-clock):")
        for name in sorted(cp):
            t = cp[name]["totals"]
            lines.append(
                f"  {name:<20}compute={t['compute_ms']:.1f}  "
                f"d2h={t['d2h_ms']:.1f}  send={t['send_ms']:.1f}  "
                f"queue={t['server_queue_ms']:.1f}  "
                f"straggler={t['straggler_wait_ms']:.1f}  "
                f"reply={t['reply_ms']:.1f}  h2d={t['h2d_ms']:.1f}")
        blame = summary.get("straggler_blame", {})
        if blame:
            lines.append("  straggler-wait attribution (ms): " + "  ".join(
                f"{h}={v:.1f}" for h, v in
                sorted(blame.items(), key=lambda kv: -kv[1])))
    # straggler board: the scheduler's live round-lag EWMA per worker
    stragglers = summary.get("straggler", {})
    if stragglers:
        lines.append("")
        lines.append("straggler board (round-lag EWMA ms):")
        for h, v in sorted(stragglers.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {h:<20}{v:10.1f}")
    # policy decisions (r14, dt_tpu/policy): current batch shares,
    # breach streaks, and the decision timeline — from obs_dump (live)
    # or the .metrics.json snapshot, same section either way
    pol = summary.get("policy", {})
    if pol.get("enabled") or pol.get("log"):
        lines.append("")
        lines.append(f"policy decisions (seq {pol.get('seq', 0)}, "
                     f"lr_scale {pol.get('lr_scale', 1.0):g}):")
        shares = pol.get("shares") or {}
        if shares:
            total = sum(shares.values()) or 1
            parts = "  ".join(
                f"{h}={u} ({100.0 * u / total:.1f}%)"
                for h, u in sorted(shares.items()))
            lines.append(f"  batch shares: {parts}")
        streaks = {h: s for h, s in (pol.get("streaks") or {}).items()
                   if s}
        if streaks:
            lines.append("  breach streaks: " + "  ".join(
                f"{h}={s}" for h, s in sorted(streaks.items())))
        for d in pol.get("log", []):
            what = []
            if d.get("breached"):
                what.append(f"breached={d['breached']}")
            if d.get("evicted"):
                what.append(f"evicted={d['evicted']}")
            for p in d.get("proposals", []):
                what.append(f"proposal={p}")
            sh = d.get("shares") or {}
            what.append("shares=" + "/".join(
                str(sh[h]) for h in sorted(sh)))
            lines.append(f"  #{d.get('seq')} epoch {d.get('epoch')}: "
                         + "  ".join(what))
    # r15 health board (dt_tpu/obs/metrics.py): active SLO breaches,
    # the recent breach/clear timeline (with the blamed worker), the
    # post-hoc export breaches, and each worker's latest shipped
    # training-health gauges — same section from a dump file or a live
    # scheduler's obs_dump
    health = summary.get("health", {})
    if health.get("enabled"):
        slo = health.get("slo", {})
        active = slo.get("active", {})
        lines.append("")
        lines.append(f"health board ({len(slo.get('rules', []))} SLO "
                     f"rules, {len(active)} active breach(es)):")
        for name, b in sorted(active.items()):
            lines.append(
                f"  BREACH {name}: worker={b.get('worker') or '-'}  "
                f"value={b.get('value')}  "
                f"threshold={b.get('threshold')}")
        for e in slo.get("history", [])[-8:]:
            lines.append(
                f"  {e.get('what', ''):<7}{e.get('rule')}  "
                f"worker={e.get('worker') or '-'}  "
                f"value={e.get('value')}")
        for e in health.get("export_breaches", []):
            lines.append(
                f"  breach* {e.get('rule')} (post-hoc, export): "
                f"value={e.get('value')}  "
                f"threshold={e.get('threshold')}")
        for track, w in sorted(health.get("workers", {}).items()):
            g = w.get("gauges", {})
            parts = "  ".join(f"{k}={g[k]:.4g}" for k in sorted(g))
            lines.append(f"  {track:<20}samples={w.get('samples', 0)}"
                         f"  {parts}")
    # r18 device board (dt_tpu/obs/device.py): per-worker compile
    # observatory totals (+ the recompile-cause timeline folded from
    # compile.recompile events), XLA's static memory estimate next to
    # the measured HBM/RSS with the delta, and who is compiling NOW
    dev = summary.get("device", {})
    if dev.get("workers") or dev.get("recompiles_by_track"):
        lines.append("")
        compiling = dev.get("compiling") or []
        lines.append("device board (compile observatory + memory)"
                     + (f"  COMPILING: {', '.join(compiling)}"
                        if compiling else "") + ":")
        for host, w in sorted((dev.get("workers") or {}).items()):
            c = w.get("compile") or {}
            parts = [f"compiles={c.get('compiles', 0)}",
                     f"recompiles={c.get('recompiles', 0)}",
                     f"cache={c.get('cache_hits', 0)}h/"
                     f"{c.get('cache_misses', 0)}m",
                     f"compile_ms={c.get('ms_total', 0.0):.0f}"]
            if w.get("compiling"):
                parts.append(f"compiling={w['compiling']}")
            lines.append(f"  {host:<20}" + "  ".join(parts))
            mem = w.get("mem") or {}
            est = c.get("est") or {}
            for d in mem.get("devices", []):
                line = (f"    hbm[{d.get('id')}]: "
                        f"in_use={d.get('bytes_in_use', 0) / 2**20:.1f}MiB"
                        f"  peak={d.get('peak_bytes_in_use', 0) / 2**20:.1f}"
                        f"MiB")
                if d.get("bytes_limit"):
                    line += f"  limit={d['bytes_limit'] / 2**20:.0f}MiB"
                if est.get("peak_mb"):
                    # estimated-vs-measured: XLA's buffer-assignment
                    # peak (the static estimate) vs live HBM
                    delta = d.get("peak_bytes_in_use", 0) / 2**20 \
                        - est["peak_mb"]
                    line += (f"  est_peak={est['peak_mb']:.1f}MiB"
                             f"  delta={delta:+.1f}MiB")
                lines.append(line)
            if not mem.get("devices") and "host_rss_bytes" in mem:
                line = (f"    rss={mem['host_rss_bytes'] / 2**20:.1f}MiB"
                        " (no HBM stats: CPU backend)")
                if est.get("peak_mb"):
                    line += f"  est_peak={est['peak_mb']:.1f}MiB"
                lines.append(line)
            st = (w.get("mem") or {}).get("staging")
            if st:
                lines.append(f"    staging: {st.get('bytes', 0) / 2**20:.1f}"
                             f"MiB pooled  outstanding="
                             f"{st.get('outstanding', 0)}")
        for track, evs in sorted(
                (dev.get("recompiles_by_track") or {}).items()):
            for e in evs[-6:]:
                lines.append(f"  recompile {track}: {e.get('what')} "
                             f"changed={e.get('changed')} "
                             f"cache={e.get('cache', '-')}")
    # r21 serving board (dt_tpu/serve): per-replica QPS / latency /
    # queue-depth gauges with the served weights step and refresh
    # count, plus the autoscale decision log (docs/serving.md)
    srv = summary.get("serving", {})
    srv_events = summary.get("serve_events") or []
    if srv.get("replicas") or srv.get("decisions") or srv_events:
        lines.append("")
        want = srv.get("want")
        lines.append("serving board"
                     + (f"  want={want}" if want is not None else "")
                     + ":")
        for host, r in sorted((srv.get("replicas") or {}).items()):
            g = r.get("gauges") or {}
            parts = [f"qps={g.get('serve.qps', 0.0):.1f}",
                     f"p99={g.get('serve.p99_ms', 0.0):.1f}ms",
                     f"queue={g.get('serve.queue_depth', 0.0):.0f}",
                     f"weights=step {r.get('weights_step', 0)}",
                     f"refreshes={r.get('refreshes', 0)}"]
            if r.get("draining"):
                parts.append("DRAINING")
            lines.append(f"  {host:<20}" + "  ".join(parts))
        for d in srv.get("decisions") or []:
            row = (f"  scale decision {d.get('seq')}: {d.get('kind')} "
                   f"{d.get('n_before')} -> {d.get('n_after')}")
            if d.get("host"):
                row += f"  drain={d['host']}"
            lines.append(row)
        for ev in srv_events:
            # the refresh/scale timeline (serve.refresh / serve.scale
            # trace events), chronological across tracks
            ts = (ev.get("ts") or 0) / 1e6
            if ev.get("what") == "serve.refresh":
                lines.append(f"  [{ts:10.3f}s] {ev.get('track')}: "
                             f"weights refreshed to step "
                             f"{ev.get('step')}")
            else:
                row = (f"  [{ts:10.3f}s] {ev.get('track')}: scale "
                       f"{ev.get('kind')}")
                if ev.get("host"):
                    row += f" host={ev['host']}"
                if ev.get("replicas") is not None:
                    row += f" replicas={ev['replicas']}"
                lines.append(row)
    causal = summary.get("causal", {})
    if causal.get("client_spans"):
        lines.append("")
        lines.append(
            f"causal join: {causal['matched']}/{causal['client_spans']} "
            f"client requests linked to server spans "
            f"({causal['orphans']} orphaned, "
            f"{causal['server_unmatched']} server-only)")
    # r19 checkpoint/drain timeline (docs/checkpoint.md): committed
    # fleet checkpoints with commit latency + per-worker ack spread,
    # aborted windows with the reason, graceful drains, and the
    # cold-restart resume event — intent/ack/begin events are folded
    # into their outcome rows
    ckpt = summary.get("checkpoint", [])
    if ckpt:
        commits = sum(1 for e in ckpt if e.get("what") == "ckpt.commit")
        lines.append("")
        lines.append(f"checkpoint/drain timeline ({commits} commit(s)):")
        for e in ckpt:
            what = e.get("what")
            if what == "ckpt.commit":
                lines.append(
                    f"  commit step {e.get('step')}: "
                    f"dur={e.get('dur_ms', 0.0):.1f}ms  "
                    f"ack_spread={e.get('spread_ms', 0.0):.1f}ms")
            elif what == "ckpt.abort":
                lines.append(f"  abort step {e.get('step')}: "
                             f"{e.get('reason', '-')}")
            elif what == "ckpt.resume":
                lines.append(
                    f"  RESUME from step {e.get('step')} "
                    f"(epoch {e.get('epoch')}, "
                    f"{len(e.get('workers') or [])} blob(s))")
            elif what == "drain.requested":
                lines.append(f"  drain requested: {e.get('host') or '-'}")
            elif what == "drain.complete":
                lines.append(f"  drained: {e.get('host') or '-'}")
    mem = summary.get("membership_changes", [])
    lines.append("")
    lines.append(f"membership changes: {len(mem)}")
    for m in mem:
        lines.append(
            f"  epoch {m.get('epoch')}: removed={m.get('removed')} "
            f"added={m.get('added')} recovered={m.get('recovered')}")
    # control-plane HA (docs/ha.md): leader-incarnation timeline and any
    # scheduler.failover spans (standby takeover: duration = the stall
    # bound the chaos harness gates at < 10 s)
    lead = summary.get("leadership", [])
    fo = summary.get("failovers", [])
    if lead or fo:
        lines.append("")
        lines.append(f"leadership (incarnation timeline): "
                     f"{len(fo)} failover(s)")
        for e in lead:
            lines.append(f"  inc {e.get('incarnation')}: {e.get('what')} "
                         f"on {e.get('track')} ({e.get('reason', '-')})")
        for f in fo:
            lines.append(f"  failover -> inc {f.get('incarnation')}: "
                         f"{f['dur_ms']:.1f} ms, {f.get('workers')} "
                         f"worker(s) resumed ({f.get('reason', '-')})")
    return "\n".join(lines)


def render_critical_step(summary, step: int) -> str:
    """One step's critical-path decomposition across every worker track
    (the ``--critical-path N`` drill-down).  ``step`` indexes each
    track's OWN recorded step sequence (a restarted worker's fresh
    incarnation counts from 0 again), so rows across tracks correspond
    only while membership is stable — compare per track, not across a
    crash boundary."""
    lines = [f"critical path, step {step} (ms; per-track step index — "
             "a restarted incarnation recounts from 0):"]
    cp = summary.get("critical_path", {})
    if not cp:
        return "no critical-path data (run with DT_OBS=1 and step spans)"
    cols = ("step_ms", "compute_ms", "d2h_ms", "send_ms",
            "server_queue_ms", "straggler_wait_ms", "reply_ms", "h2d_ms")
    heads = ("step", "compute", "d2h", "send", "queue", "straggler",
             "reply", "h2d")
    lines.append(f"{'track':<22}" + "".join(f"{h:>11}" for h in heads))
    for name in sorted(cp):
        steps = cp[name].get("per_step", [])
        if step >= len(steps):
            lines.append(f"{name:<22}  (no step {step}; track has "
                         f"{len(steps)} listed)")
            continue
        row = steps[step]
        lines.append(f"{name:<22}" + "".join(
            f"{row[c]:>11.1f}" for c in cols))
    return "\n".join(lines)


def _iso(ts_ms) -> str:
    import datetime
    dt = datetime.datetime.fromtimestamp(int(ts_ms) / 1000.0,
                                         tz=datetime.timezone.utc)
    return dt.strftime("%Y-%m-%dT%H:%M:%S") + f".{int(ts_ms) % 1000:03d}Z"


def _blamed_frame(frames):
    """The frame a stalled/dead thread is 'blamed' on: the innermost
    frame inside this project (``dt_tpu``/``tools``), else the innermost
    frame outright — the one-line answer to 'where was it stuck'."""
    for fs in reversed(frames or []):
        fn = str(fs[0]).replace("\\", "/")
        if "dt_tpu/" in fn or "/tools/" in fn or fn.startswith("tools/"):
            return fs
    return frames[-1] if frames else None


def _short_path(fn: str) -> str:
    fn = str(fn).replace("\\", "/")
    for anchor in ("dt_tpu/", "tools/", "tests/"):
        i = fn.find(anchor)
        if i >= 0:
            return fn[i:]
    return fn.rsplit("/", 1)[-1]


def load_postmortem(path):
    """(bundle, manifest_rows, bundle_path) from a bundle file or a
    ``DT_BLACKBOX_DIR`` (dir: the newest bundle + the full manifest
    timeline).  jax-free — bundles are the whole input, no scheduler."""
    from dt_tpu.obs import blackbox
    if os.path.isdir(path):
        rows = blackbox.read_manifest(path)
        brows = [r for r in rows if r.get("kind") == "bundle"
                 and r.get("file")]
        if not brows:
            raise SystemExit(f"no bundle rows in "
                             f"{blackbox.manifest_path(path)}")
        newest = max(brows, key=lambda r: r.get("ts_ms", 0))
        bpath = os.path.join(path, newest["file"])
        with open(bpath) as f:
            return json.load(f), rows, bpath
    with open(path) as f:
        bundle = json.load(f)
    rows = blackbox.read_manifest(os.path.dirname(path) or ".")
    return bundle, rows, path


def render_postmortem(bundle, manifest_rows=None, path="") -> str:
    """The crash report: death timeline, open spans at death, per-thread
    stacks collapsed to the blamed frame, last SLO breaches, ring-drop
    accounting — from the bundle alone (the post-mortem the reference
    never had; its ceiling was scrolling PS_VERBOSE logs)."""
    lines = []
    lines.append(f"== dt_tpu post-mortem: {os.path.basename(path)} ==")
    lines.append(
        f"trigger={bundle.get('trigger')}  "
        f"fatal={'yes' if bundle.get('fatal') else 'no'}  "
        f"host={bundle.get('host') or '-'}  pid={bundle.get('pid')}  "
        f"at {_iso(bundle.get('ts_ms', 0))}")
    extra = bundle.get("extra") or {}
    if extra:
        lines.append("  " + "  ".join(f"{k}={extra[k]}"
                                      for k in sorted(extra)))
    rows = manifest_rows or []
    if rows:
        lines.append("")
        lines.append(f"death timeline (manifest, {len(rows)} row(s)):")
        for r in sorted(rows, key=lambda r: r.get("ts_ms", 0)):
            what = r.get("trigger") or r.get("outcome") or r.get("kind")
            mark = " FATAL" if r.get("fatal") else ""
            tail = f"  {r.get('file')}" if r.get("file") else ""
            lines.append(f"  {_iso(r.get('ts_ms', 0))}  "
                         f"{r.get('host') or '-':<12}pid "
                         f"{r.get('pid')}  {r.get('kind')}:{what}"
                         f"{mark}{tail}")
    spans = bundle.get("open_spans") or []
    lines.append("")
    lines.append(f"open spans at death ({len(spans)}):")
    for s in spans:
        attrs = s.get("attrs") or {}
        at = ("  " + "  ".join(f"{k}={attrs[k]}" for k in sorted(attrs))
              ) if attrs else ""
        lines.append(f"  {s.get('name'):<20}age={s.get('age_ms'):.1f}ms"
                     f"  tid={s.get('tid')}  sid={s.get('sid')}{at}")
    threads = bundle.get("threads") or []
    lines.append("")
    lines.append(f"threads ({len(threads)}; collapsed to the blamed "
                 "frame):")
    for t in threads:
        blamed = _blamed_frame(t.get("frames"))
        where = (f"{_short_path(blamed[0])}:{blamed[1]} {blamed[2]}"
                 if blamed else "(no frames)")
        d = " daemon" if t.get("daemon") else ""
        lines.append(f"  {t.get('name'):<28}tid={t.get('tid')}{d}: "
                     f"{where}")
        for fs in (t.get("frames") or [])[-4:]:
            lines.append(f"      {_short_path(fs[0])}:{fs[1]} {fs[2]}")
    ring = bundle.get("flight_ring") or []
    if ring:
        lines.append("")
        lines.append(f"flight ring (last {min(len(ring), 16)} of "
                     f"{len(ring)}):")
        for ts, kind, attrs in ring[-16:]:
            at = ("  " + "  ".join(f"{k}={attrs[k]}"
                                   for k in sorted(attrs))) if attrs \
                else ""
            lines.append(f"  {_iso(ts)}  {kind}{at}")
    # last SLO breaches: scheduler-side bundles carry slo_history in
    # their state; any bundle may hold health.* events in the span ring
    breaches = []
    for name, st in sorted((bundle.get("state") or {}).items()):
        for e in (st or {}).get("slo_history", []):
            breaches.append((e.get("ts_ms", 0),
                             f"{e.get('what')} {e.get('rule')} "
                             f"worker={e.get('worker') or '-'} "
                             f"value={e.get('value')}"))
    for rec in (bundle.get("span_ring") or {}).get("records", []):
        if len(rec) > 8 and rec[2] in ("health.breach", "health.clear"):
            a = rec[8] or {}
            breaches.append((rec[3] // 1000,
                             f"{rec[2].split('.')[1]} {a.get('rule')} "
                             f"worker={a.get('worker') or '-'} "
                             f"value={a.get('value')}"))
    if breaches:
        lines.append("")
        lines.append("last SLO breaches:")
        for ts, desc in sorted(breaches)[-8:]:
            lines.append(f"  {_iso(ts)}  {desc}")
    # r18 device plane: the bundle's device state provider (compile
    # ledger + memory + census) and any OOM census in extra
    devst = (bundle.get("state") or {}).get("device") or {}
    census = (bundle.get("extra") or {}).get("census") \
        or devst.get("census") or []
    comp = devst.get("compile") or {}
    if comp.get("compiles"):
        lines.append("")
        lines.append(
            f"device plane: compiles={comp.get('compiles', 0)}  "
            f"recompiles={comp.get('recompiles', 0)}  "
            f"cache={comp.get('cache_hits', 0)}h/"
            f"{comp.get('cache_misses', 0)}m  "
            f"compiling={devst.get('compiling') or '-'}")
    if census:
        lines.append("top live buffers (shape  dtype  count  MiB  tag):")
        for g in census[:8]:
            lines.append(
                f"  {g.get('shape'):<20}{g.get('dtype'):<10}"
                f"{g.get('count'):>5}{g.get('bytes', 0) / 2**20:>9.1f}"
                f"  {g.get('tag') or '-'}")
    sr = bundle.get("span_ring") or {}
    mr = bundle.get("metrics_ring") or {}
    lines.append("")
    lines.append(
        f"ring drops: spans={sr.get('dropped', 0)}  "
        f"metrics={mr.get('dropped', 0)}  "
        f"span_tail={len(sr.get('records') or [])}  "
        f"series_tail={len(mr.get('series') or [])}"
        + ("  TRUNCATED" if bundle.get("truncated") else ""))
    faults = bundle.get("faults_applied") or []
    if faults:
        lines.append("faults applied: " + "  ".join(
            f"{k}@{h or '-'}x{n}" for k, h, n in faults))
    # non-default env knobs (the resolved view rides the bundle; the
    # registry defaults come from config — jax-free)
    try:
        from dt_tpu import config as dt_config
        defaults = {k: v for k, (v, _) in dt_config.ENV_REGISTRY.items()}
    except Exception:
        defaults = {}
    diff = {k: v for k, v in (bundle.get("env") or {}).items()
            if v != defaults.get(k, "")}
    if diff:
        lines.append("env (non-default): " + "  ".join(
            f"{k}={diff[k]}" for k in sorted(diff)))
    return "\n".join(lines)


def _sched_request(spec: str, msg: dict, timeout: float = 10.0) -> dict:
    """One control request against a live ``host:port`` scheduler —
    shared by the ``obs_dump`` pull and the r17 ``status``/``health``
    introspection commands (PROTOCOL_REGISTRY), which answer on PASSIVE
    standbys too and cost none of ``obs_dump``'s payload."""
    from dt_tpu.elastic import protocol
    host, _, port = spec.rpartition(":")
    try:
        portnum = int(port)
    except ValueError:
        raise SystemExit(f"--scheduler needs host:port, got {spec!r}")
    resp = protocol.request(host or "127.0.0.1", portnum, msg,
                            timeout=timeout)
    if "error" in resp:
        raise SystemExit(f"scheduler error: {resp['error']}")
    return resp


def render_status(resp: dict) -> str:
    """The ``status`` command's one-screen identity/progress view:
    leadership + incarnation (docs/ha.md), membership, epoch progress,
    the straggler board, and the applied policy shares."""
    lines = [f"leader: {'yes' if resp.get('active') else 'PASSIVE'}   "
             f"incarnation: {resp.get('incarnation', 0)}   "
             f"last_completed_epoch: "
             f"{resp.get('last_completed_epoch', -1)}"]
    lines.append("workers: " + (", ".join(resp.get("workers", []))
                                or "(none)"))
    strag = resp.get("straggler") or {}
    if strag:
        lines.append("straggler board (round-lag EWMA ms): " + "  ".join(
            f"{h}={v:.1f}" for h, v in sorted(strag.items())))
    pol = resp.get("policy") or {}
    if pol.get("enabled"):
        shares = pol.get("shares") or {}
        lines.append(
            f"policy: seq={pol.get('seq', 0)} lr_scale="
            f"{pol.get('lr_scale', 1.0)} shares=" + (" ".join(
                f"{h}:{u}" for h, u in sorted(shares.items())) or "-"))
    srv = resp.get("serving") or {}
    if srv:
        lines.append(f"serving: {len(srv.get('replicas') or [])} "
                     f"replica(s) want={srv.get('want')} "
                     f"decisions={srv.get('decisions', 0)}  ("
                     + (", ".join(srv.get("replicas") or []) or "-")
                     + ")")
    return "\n".join(lines)


def render_health(resp: dict) -> str:
    """The ``health`` command's SLO/gauge view (the r15 training-health
    surface the serving plane scrapes)."""
    h = resp.get("health") or {}
    if not h.get("enabled"):
        return "metrics plane off (DT_METRICS=0)"
    lines = []
    slo = h.get("slo") or {}
    active = slo.get("active") or {}
    lines.append(f"SLO: {len(active)} active breach(es)")
    for rule, b in sorted(active.items()):
        lines.append(f"  BREACH {rule}: worker="
                     f"{b.get('worker') or '-'} value={b.get('value')} "
                     f"threshold={b.get('threshold')}")
    gauges = h.get("gauges") or []
    if gauges:
        parts = []
        for name, labels, val in gauges:
            lk = ",".join(f"{k}={v}" for k, v in sorted(dict(labels)
                                                        .items()))
            parts.append(f"{name}{{{lk}}}={val}" if lk
                         else f"{name}={val}")
        lines.append("scheduler gauges: " + "  ".join(parts))
    workers = h.get("workers") or {}
    for track, w in sorted(workers.items()):
        g = "  ".join(f"{k}={v}" for k, v in
                      sorted((w.get("gauges") or {}).items()))
        lines.append(f"  {track}: samples={w.get('samples', 0)} "
                     f"dropped={w.get('dropped', 0)}  {g}")
    return "\n".join(lines)


def _follow(args) -> int:
    """Live mode: poll the scheduler's ``obs_dump`` and re-render a
    compact board each cycle.  The step RATE is computed from the delta
    of per-track step counts between polls — the number an operator
    watches during a resize or failover."""
    from dt_tpu.obs import export as obs_export
    prev_counts = {}
    prev_t = None
    n = 0
    while True:
        chrome = _load_chrome(args)
        summary = obs_export.summarize_chrome(chrome)
        now = time.monotonic()
        counts = {t: d["steps"]["count"]
                  for t, d in summary.get("tracks", {}).items()}
        rate_parts = []
        if prev_t is not None and now > prev_t:
            dt = now - prev_t
            for t in sorted(counts):
                if t == "control-plane":
                    continue
                d = counts[t] - prev_counts.get(t, 0)
                rate_parts.append(f"{t}={d / dt:.2f}/s")
        prev_counts, prev_t = counts, now
        print(f"=== dtop --follow poll {n + 1} "
              f"[{time.strftime('%H:%M:%S')}] ===")
        if rate_parts:
            print("step rate: " + "  ".join(rate_parts))
        print(render(summary))
        sys.stdout.flush()
        n += 1
        if args.iterations and n >= args.iterations:
            return 0
        time.sleep(args.interval)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="dtop", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("trace", nargs="?", default="",
                    help="merged chrome trace JSON (obs.export.write)")
    ap.add_argument("--scheduler", default="",
                    help="live scheduler host:port (obs_dump)")
    ap.add_argument("--json", action="store_true",
                    help="print the summary dict instead of the table")
    ap.add_argument("--follow", action="store_true",
                    help="live mode: poll --scheduler periodically and "
                         "re-render (step rate, critical path, "
                         "straggler board, membership/leadership)")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="--follow poll period in seconds (default 2)")
    ap.add_argument("--iterations", type=int, default=0,
                    help="stop --follow after N polls (0 = forever)")
    ap.add_argument("--postmortem", default="", metavar="BUNDLE|DIR",
                    help="render a crash report from a blackbox bundle "
                         "file (or the newest bundle in a "
                         "DT_BLACKBOX_DIR, with the manifest death "
                         "timeline) — no scheduler needed")
    ap.add_argument("--critical-path", type=int, default=None,
                    metavar="STEP",
                    help="drill into step STEP's critical-path "
                         "decomposition on every worker track (STEP "
                         "indexes each track's own recorded steps; a "
                         "restarted incarnation recounts from 0)")
    ap.add_argument("--capture", default="", metavar="WORKER",
                    help="queue a bounded jax.profiler capture on one "
                         "worker via the r18 'profile_capture' command "
                         "(needs --scheduler; the trace lands in the "
                         "job's DT_BLACKBOX_DIR + manifest.jsonl)")
    ap.add_argument("--steps", type=int, default=8,
                    help="steps the --capture trace spans (default 8)")
    ap.add_argument("--status", action="store_true",
                    help="one-screen scheduler identity/progress via "
                         "the light 'status' command (answers on a "
                         "passive standby too) instead of obs_dump")
    ap.add_argument("--health", action="store_true",
                    help="the r15 SLO/gauge training-health view via "
                         "the 'health' command instead of obs_dump")
    args = ap.parse_args(argv)

    if args.capture:
        if not args.scheduler:
            raise SystemExit("--capture needs --scheduler host:port")
        resp = _sched_request(
            args.scheduler,
            {"cmd": "profile_capture", "host": f"dtop:{os.getpid()}",
             "target": args.capture, "steps": args.steps,
             "post_seq": int(time.time() * 1000)})
        print(json.dumps({"queued": True, "target": args.capture,
                          "steps": args.steps, "seq": resp.get("seq")}))
        return 0

    if args.status or args.health:
        if not args.scheduler:
            raise SystemExit("--status/--health need --scheduler "
                             "host:port")
        resp = _sched_request(args.scheduler, {"cmd": "status"}) \
            if args.status else \
            _sched_request(args.scheduler, {"cmd": "health"})
        if args.json:
            print(json.dumps(resp, indent=2, sort_keys=True,
                             default=repr))
        else:
            print(render_status(resp) if args.status
                  else render_health(resp))
        return 0

    if args.postmortem:
        bundle, rows, bpath = load_postmortem(args.postmortem)
        if args.json:
            print(json.dumps({"bundle": bundle, "manifest": rows},
                             indent=2, sort_keys=True, default=repr))
        else:
            print(render_postmortem(bundle, rows, bpath))
        return 0

    if args.follow:
        if not args.scheduler:
            raise SystemExit("--follow needs --scheduler host:port")
        try:
            return _follow(args)
        except KeyboardInterrupt:
            return 0

    from dt_tpu.obs import export as obs_export
    chrome = _load_chrome(args)
    summary = obs_export.summarize_chrome(chrome)
    if args.json:
        print(json.dumps(summary, indent=2))
    elif args.critical_path is not None:
        print(render_critical_step(summary, args.critical_path))
    else:
        print(render(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
