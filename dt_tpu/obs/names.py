"""Span / event / counter name catalog — the single declaration point for
every ``dt_tpu.obs`` instrumentation name, mirroring
``dt_tpu.config.ENV_REGISTRY`` (the role ps-lite's one GetEnv block
played for env vars, ``ps-lite/src/postoffice.cc:18-31``; the reference
had no name discipline at all — profiler scopes were free-form strings,
``src/profiler/profiler.h:256``).

dtlint rule DT011 enforces it: a ``span``/``complete_span``/``event``/
``counter`` call anywhere in the linted tree with a literal name must
have a row here, and every row must still have an emitter (dead names
rot into cargo-cult dashboards).  Names ending in ``*`` are prefix
entries for the few dynamically-suffixed families (``fault.<kind>``,
``membership.<ACTION>``, ``rpc.<cmd>``); an f-string call site matches
by its literal prefix.

Values are ``(kind, doc)`` where kind is ``span`` / ``event`` /
``counter`` (a ``|``-separated union when one name is legitimately both,
e.g. ``client.failover``).  Tools consume this table too: the export's
stall/pipeline classification and dtop's sections are built from names
declared here, so a renamed span fails the lint instead of silently
vanishing from the dashboards.
"""

from __future__ import annotations

from typing import Mapping, Tuple

NAME_REGISTRY: Mapping[str, Tuple[str, str]] = {
    # -- training plane (training/module.py, trainer.py) -------------------
    "step": ("span", "one iteration of fit's step loop, from its top to "
                     "the next one's: the dispatch of one step and the "
                     "metric flush of the step before (attrs: dispatched, "
                     "flushed); worker track"),
    # the step account (obs/trace.py StepAccount): each is a column of
    # the always-live account rows and, under DT_OBS=1, a child span of
    # `step` and a jax.profiler annotation
    "step.input": ("span", "the feed's next(), in the loop and in the "
                           "prefetch"),
    "step.place": ("span", "_place of data and labels: the host-to-device "
                           "enqueue"),
    "step.dispatch": ("span", "the call of the compiled train/grad/apply "
                              "step: the enqueue; blocks when the "
                              "runtime's queue is full or a donated buffer "
                              "is in use"),
    "step.sync": ("span", "host-sync and async modes: gradient to the "
                          "host, allreduce or overlap engine or "
                          "push_flat, the average back to the device"),
    "step.fetch": ("span", "the wait for the previous step's per-row "
                           "metric statistics (its logits, for a metric "
                           "without a device form) and their copy to the "
                           "host"),
    "step.metric": ("span", "eval_metric.update_reduced (or the host's "
                            "softmax and eval_metric.update)"),
    "step.callback": ("span", "the batch-end callbacks"),
    "step.hooks": ("span", "the rest of an iteration: fault hooks, "
                           "watchdog beat, capture tick, health "
                           "sentinel's fetch, checkpoint cadence, drain "
                           "poll"),
    # the build account and the fit rows (obs/trace.py BUILD_ROW_FIELDS,
    # FIT_ROW_FIELDS): always-live rows and, under DT_OBS=1, spans that
    # have already ended, at the rows' own readings; read by
    # benchmark/builds.py (setup.build_*, setup.fit_*), chip_smoke.py
    # and the device plane's compile.<what> span
    "fit": ("span", "one Module.fit call, from its entry to its return "
                    "or raise (attrs: fit, its number; iterations)"),
    "fit.enter": ("span", "from fit's entry to its first iteration's "
                          "begin: bind, the steps built for the metric, "
                          "resume, drain and watchdog installs, the first "
                          "barrier, the iterator's reset; child of fit"),
    "fit.exit": ("span", "from the last iteration's close to fit's "
                         "return: epoch end, snapshot, evaluation, "
                         "checkpoint flush; child of fit"),
    "build.*": ("span", "one stage of a build as jax.monitoring reports "
                        "it: build.trace (jaxpr), build.lower (to MLIR, "
                        "the Pallas kernels' lowering included), "
                        "build.backend (XLA compile, or the persistent "
                        "cache's read); attrs: fun, fit, cache "
                        "(hit/miss/off, backend only); a first call's "
                        "lie inside its step.dispatch"),
    "epoch": ("span", "one training epoch (Module.fit)"),
    "epoch.rebuild": ("span", "mesh_manager.rebuild and new jit objects "
                              "for the new mesh; it holds no compile: the "
                              "build.* spans of the next step's "
                              "step.dispatch do"),
    "epoch.data_reshard": ("span", "the elastic iterator factory rebuilding "
                                   "the data iterators after a change"),
    "epoch.snapshot": ("span", "_publish_snapshot at the epoch's end"),
    "eval": ("span", "one evaluation pass (Module.score)"),
    "trainer.step": ("span", "one Trainer.step (low-level training loop)"),
    # -- worker client (elastic/client.py) ---------------------------------
    "mc_barrier": ("span", "client side of the membership-change barrier"),
    "allreduce": ("span", "one top-level exact-average round (serial or "
                          "pipelined wall-clock)"),
    "allreduce_sparse": ("span", "one row-sparse exact-average round"),
    "recovery.rejoin": ("span", "crash-recovery re-admission wait"),
    "allreduce.chunked": ("event", "a round split into chunk sub-rounds"),
    "client.failover": ("event|counter", "scheduler endpoint rotation"),
    "client.reattached": ("event", "re-registered under a new leader fence"),
    "heartbeat.sent": ("counter", "heartbeats issued by this worker"),
    "allreduce.rounds": ("counter", "top-level allreduce rounds"),
    "profiler.posts": ("counter", "remote profiler commands posted"),
    # -- wire (elastic/protocol.py) ----------------------------------------
    "wire.request": ("span", "one request/response attempt on a pooled "
                             "channel; carries the propagated span id"),
    "wire.retry": ("event", "an at-least-once retry (with backoff)"),
    "wire.retries": ("counter", "total transport retries"),
    "wire.bytes_sent": ("counter", "frame bytes written (all frames)"),
    "wire.bytes_recv": ("counter", "frame bytes received (all frames)"),
    # -- scheduler control plane (elastic/scheduler.py) --------------------
    "rpc.*": ("span", "server-side handler span, one per served request "
                      "that carried trace context (rpc.<cmd>)"),
    "mc_barrier.window": ("span", "barrier window: first arrival → release"),
    "membership_change": ("span", "one applied membership change"),
    "scheduler.failover": ("span", "warm-standby takeover (docs/ha.md)"),
    "membership.*": ("event", "audit-line events (membership.ADDED / "
                              "REMOVED / RECOVERED)"),
    "recovery.registered": ("event", "a crashed worker re-registered"),
    "leader.elected": ("event", "leadership assumed (start or takeover)"),
    "leader.fenced": ("event", "this leader was deposed by a newer fence"),
    "transport.connections": ("counter", "accepted control connections"),
    "transport.requests": ("counter", "control requests served"),
    "tokens.dedup_hits": ("counter", "idempotency-token replays served "
                                     "from cache"),
    "ha.rounds_replicated": ("counter", "completed rounds installed from "
                                        "the live primary"),
    # -- data plane (elastic/dataplane.py, range_server.py) ----------------
    "dataplane.round": ("span", "one allreduce round: first contribution "
                                "→ completion; attrs carry the last "
                                "(straggling) contributor + wait_ms"),
    "dataplane.survivor_complete": ("event", "round finished by survivors "
                                             "after membership shrank"),
    "worker.straggler": ("event", "a worker's round-lag EWMA crossed "
                                  "DT_STRAGGLER_MS"),
    "dataplane.rounds": ("counter", "completed allreduce rounds"),
    "dataplane.bucket_rounds": ("counter", "overlap-pipeline bucket rounds "
                                           "(key#b<i>)"),
    "data.bytes_in": ("counter", "range-server data-plane bytes received"),
    "data.requests": ("counter", "range-server data-plane requests"),
    # -- overlap pipeline (training/overlap.py, client AllreducePipeline) --
    "pipeline.d2h": ("span", "one bucket's device→host staging"),
    "pipeline.wire": ("span", "one bucket's wire round (comm thread)"),
    "pipeline.h2d": ("span", "one bucket's host→device dispatch"),
    "pipeline.buckets": ("counter", "bucket rounds pushed through the "
                                    "overlap pipeline"),
    "pipeline.aux_rounds": ("counter", "aux rounds ridden on the pipeline "
                                       "window (e.g. stats)"),
    # -- policy engine (dt_tpu/policy via elastic/scheduler.py) ------------
    "policy.rebalance": ("event", "one applied policy decision: breach "
                                  "set + the journaled batch-share units"),
    "policy.evict": ("event", "a chronic straggler dropped from "
                              "host_worker by the policy engine"),
    "policy.scale": ("event", "a scale-up/down proposal toward "
                              "DT_POLICY_TARGET_WORKERS"),
    "policy.decisions": ("counter", "journaled policy_decide ops"),
    # -- metrics / health plane (obs/metrics.py, r15) ----------------------
    # gauges and histograms are emitted through MetricsRegistry.gauge /
    # .observe and sampled into the DT_METRICS time-series ring; dtlint
    # DT011 holds them to this catalog exactly like spans/events/counters
    "train.loss": ("gauge", "last completed step's training loss"),
    "train.steps": ("gauge", "cumulative optimizer steps this process "
                             "applied (the scheduler derives step rate "
                             "from successive samples)"),
    "health.grad_norm": ("gauge", "last step's global gradient L2 norm "
                                  "(non-finite entries excluded)"),
    "health.param_norm": ("gauge", "last step's parameter L2 norm"),
    "fit.metric_device_steps": ("gauge", "steps of the current fit call "
                                         "whose metric was reduced to "
                                         "per-row statistics on the "
                                         "device"),
    "fit.metric_host_steps": ("gauge", "steps of the current fit call "
                                       "whose logits crossed to the host "
                                       "for a metric without a device "
                                       "form"),
    # set when Module builds its steps: the first three for a model whose
    # layers are read from a pattern (models/hybrid_lm.py), the two remat
    # ones also for models/routed_lm.py
    "model.layers_ssm": ("gauge", "state-space (Mamba-2) layers of the "
                                  "model the compiled steps run"),
    "model.layers_attention": ("gauge", "attention layers of the model the "
                                        "compiled steps run"),
    "model.ssm_chunk": ("gauge", "positions in one chunk of the "
                                 "state-space scan (ops/ssm.py ssd_scan)"),
    "model.remat_blocks": ("gauge", "1 where each block's activations are "
                                    "recomputed in the backward pass "
                                    "(linen.remat per block), else 0"),
    "model.remat_saved_names": ("gauge", "names on the list a rematerialised "
                                         "block keeps from its forward pass "
                                         "(the model file's SAVED; the rest "
                                         "is recomputed); 0 with remat off"),
    # set at each flushed step of fit from what a routed expert layer
    # (parallel/moe.py RoutedExperts) counted in it, label `layer`
    # (training/module.py _count_step)
    "moe.held_load_share_pct": ("gauge", "the step's assignments to the "
                                         "experts this chip holds, over "
                                         "all the layer made (T x k); "
                                         "100 x held / total is even"),
    "moe.fullest_over_mean_load": ("gauge", "the fullest held expert's "
                                            "assignments over the held "
                                            "experts' mean"),
    "moe.buffer_fill_pct": ("gauge", "rows of the layer's static buffer "
                                     "that held an assignment, over "
                                     "buffer_rows"),
    "moe.overflow_assignments": ("gauge", "assignments to held experts "
                                          "that found no room in the "
                                          "buffer and were dropped from "
                                          "the step's result"),
    # set at trace time, once per distinct (s, sk, d, dtype), label `shape`,
    # under a mask rule label `mask`, and where the keys and values have
    # fewer heads than the queries label `rep`, the query heads a key-value
    # head (ops/pallas/attention.py)
    "flash.block_q": ("gauge", "query rows in one tile of the flash "
                               "forward, derived from the shape "
                               "(forward_tiles) or given"),
    "flash.block_k": ("gauge", "key rows in one tile of the flash forward, "
                               "derived or given"),
    "flash.bwd_block_q": ("gauge", "query rows in one tile of the flash "
                                   "backward, derived from the shape "
                                   "(backward_tiles)"),
    "flash.bwd_block_k": ("gauge", "key rows in one tile of the flash "
                                   "backward (backward_tiles)"),
    "flash.pairs_computed_pct": ("gauge", "pairs the flash forward computes "
                                          "over the pairs of the tiles it "
                                          "runs: under 100 where crossed "
                                          "tiles are walked in sub-blocks "
                                          "(computed_tiles), 100 where "
                                          "every tile is computed whole"),
    "flash.bwd_pairs_computed_pct": ("gauge", "the same of the flash "
                                              "backward at its own tiles"),
    # set at trace time by every call of ops/ssm.py ssd_scan
    # (ops/pallas/ssd.py note_path): the process's counts so far
    "ssd.kernel_calls": ("gauge", "calls of ssd_scan traced so far that "
                                  "took the Pallas kernels (ssd_fwd, "
                                  "ssd_bwd)"),
    "ssd.xla_calls": ("gauge", "calls of ssd_scan traced so far that fell "
                               "back to ssd_scan_xla: a chunk, a state "
                               "width or a head size off the lane tiles"),
    "worker.step_rate": ("gauge", "scheduler-derived per-worker step "
                                  "rate (steps/s) from the shipped "
                                  "train.steps series"),
    "sched.heartbeat_staleness_s": ("gauge", "seconds since each live "
                                             "worker's last heartbeat"),
    "obs.ring_dropped": ("gauge", "total obs ring/pending records shed "
                                  "job-wide (scheduler view)"),
    "step.ms": ("histogram", "host-side wall-clock of one training step"),
    "round.wait_ms": ("histogram", "allreduce round wait-for-last-"
                                   "contributor window (data plane)"),
    "journal.append_ms": ("histogram", "control-journal fsync-append "
                                       "latency"),
    "metrics.samples": ("counter", "time-series samples taken by the "
                                   "background sampler"),
    "metrics.scrapes": ("counter", "/metrics exposition scrapes served"),
    "health.nonfinite": ("event", "the fused non-finite sentinel fired: "
                                  "a gradient/loss went NaN/Inf this "
                                  "step"),
    "health.halt": ("event", "DT_HEALTH_HALT stopped training before "
                             "the poisoned update was applied"),
    "health.breach": ("event", "an SLO rule started breaching (attrs "
                               "carry rule, blamed worker, value, "
                               "threshold)"),
    "health.clear": ("event", "a breaching SLO rule recovered"),
    # -- flight recorder / hang forensics (obs/blackbox.py, r16) -----------
    "blackbox.bundle": ("event", "a crash/hang bundle was written to "
                                 "DT_BLACKBOX_DIR (attrs: trigger, file, "
                                 "fatal)"),
    "blackbox.bundles": ("counter", "flight-recorder bundles written by "
                                    "this process"),
    "hang.suspect": ("event", "edge-triggered: step/fleet progress "
                              "stalled past DT_HANG_S (worker watchdog "
                              "or scheduler fleet detector; attrs carry "
                              "the stall age and — scheduler-side — the "
                              "blamed worker)"),
    "hang.clear": ("event", "a suspected hang recovered (progress "
                            "resumed / the stalled round completed)"),
    # -- device plane (obs/device.py, r18) ---------------------------------
    "compile.*": ("span", "one build of an instrumented step "
                          "(compile.<what>); open while the compiler "
                          "runs, so hang bundles can label a "
                          "compile-in-progress stall; attrs trace_ms, "
                          "lower_ms, backend_ms and cache are the build "
                          "account's rows of it"),
    "compile.recompile": ("event", "an instrumented step compiled AGAIN "
                                   "(attrs name the signature delta: "
                                   "shape/dtype/mesh/donate/nargs, or "
                                   "'rebuild' for an identical-signature "
                                   "elastic rebuild)"),
    "compile.compiles": ("counter", "XLA compiles observed by the device "
                                    "plane"),
    "compile.cache_hits": ("counter", "compiles served from the "
                                      "persistent compilation cache"),
    "compile.cache_misses": ("counter", "compiles the persistent cache "
                                        "was asked for and did not serve"),
    "device.hbm_bytes": ("gauge", "per-device HBM bytes in use "
                                  "(jax.Device.memory_stats)"),
    "device.hbm_peak_bytes": ("gauge", "per-device peak HBM bytes in use"),
    "device.hbm_limit_bytes": ("gauge", "per-device HBM capacity"),
    "device.host_rss_bytes": ("gauge", "process resident-set bytes (the "
                                       "CPU fallback when the backend "
                                       "reports no HBM stats)"),
    "device.staging_bytes": ("gauge", "overlap StagingPool pooled host "
                                      "bytes (free-list occupancy)"),
    "device.staging_outstanding": ("gauge", "overlap StagingPool buffers "
                                            "acquired and not yet "
                                            "released"),
    "device.oom": ("event", "a RESOURCE_EXHAUSTED allocation failure was "
                            "caught; the OOM bundle carries the "
                            "live-buffer census"),
    "profile.capture": ("event", "a bounded on-demand jax.profiler "
                                 "capture finished (profile_capture "
                                 "wire command; trace dir in attrs)"),
    # -- job survivability plane (r19 — coordinated fleet checkpointing,
    # cold-restart resume, graceful drain; docs/checkpoint.md) -------------
    "ckpt.save": ("span", "one worker's fleet-checkpoint save: device_get "
                          "+ msgpack + atomic write (async tail included "
                          "— the span closes when the blob is on disk)"),
    "ckpt.intent": ("event", "scheduler journaled a fleet-checkpoint "
                             "intent (attrs: step, epoch, workers)"),
    "ckpt.ack": ("event", "scheduler recorded one worker's save ack "
                          "(attrs: host, step)"),
    "ckpt.commit": ("event", "all acks in — the manifest is journaled and "
                             "the checkpoint is durable (attrs: step, "
                             "epoch, workers, dur_ms, spread_ms)"),
    "ckpt.abort": ("event", "a pending intent was abandoned (superseded "
                            "or its worker set changed before commit)"),
    "ckpt.resume": ("event", "cold-restart resume: the newest committed "
                             "manifest was adopted (scheduler) / restored "
                             "(worker)"),
    "ckpt.committed_step": ("gauge", "global step of the newest committed "
                                     "fleet checkpoint (scheduler view)"),
    "ckpt.save_errors": ("counter", "background checkpoint writes that "
                                    "failed (surfaced on the next save / "
                                    "fit exit)"),
    "drain.requested": ("event", "SIGTERM preemption notice received — "
                                 "finish the current step, then depart "
                                 "through the membership machinery"),
    "drain.begin": ("event", "scheduler accepted a drain (attrs: host); "
                             "the host leaves host_worker and the next "
                             "barrier removes it"),
    "drain.complete": ("event", "a draining worker departed cleanly (no "
                                "crash bundle — the manifest carries a "
                                "drain row instead)"),
    # -- serving plane (dt_tpu/serve, r21 — docs/serving.md) ---------------
    "serve.batch": ("span", "one coalesced dynamic batch through the "
                            "Predictor (attrs: bucket, rows, reqs, "
                            "weights_step)"),
    "serve.requests": ("counter", "infer requests admitted by the gateway"),
    "serve.rows": ("counter", "rows admitted by the gateway"),
    "serve.batches": ("counter", "dynamic batches executed"),
    "serve.shed": ("counter", "requests shed by admission control "
                              "(queue-row cap DT_SERVE_QUEUE_ROWS)"),
    "serve.queue_depth": ("gauge", "requests queued in the gateway "
                                   "batcher right now (the ServePolicy "
                                   "autoscale signal)"),
    "serve.p99_ms": ("gauge", "rolling p99 gateway latency "
                              "(enqueue -> reply) over the last window"),
    "serve.qps": ("gauge", "rolling requests/s over the last window"),
    "serve.latency_ms": ("histogram", "per-request gateway latency "
                                      "(enqueue -> reply)"),
    "serve.refresh": ("event", "rolling weight refresh: this replica "
                               "swapped to a new committed manifest "
                               "(attrs: step)"),
    "serve.scale": ("event", "a serving-policy decision was applied "
                             "(attrs: kind, host, replicas)"),
    "serve.replicas": ("gauge", "registered live serving replicas "
                                "(scheduler view)"),
    # -- predictor (dt_tpu/predictor.py — the obs face of the old ad-hoc
    # Predictor.stats dict; the dict stays as a per-instance view) ---------
    "predict.requests": ("counter", "Predictor.predict calls served"),
    "predict.rows": ("counter", "rows served through Predictor.predict"),
    "predict.compiles": ("counter", "bucket programs compiled outside "
                                    "warmup (a live request paid a "
                                    "compile)"),
    "predict.ms": ("histogram", "one Predictor.predict wall-clock "
                                "(pad + dispatch + device_get)"),
    # -- fault injection (elastic/faults.py) -------------------------------
    "fault.*": ("event", "every APPLIED fault (fault.<kind>); the chaos "
                         "harness cross-checks these against "
                         "applied_summary()"),
}


def lookup(name: str) -> Tuple[str, str, str]:
    """Resolve ``name`` against the registry: exact row first, then the
    longest matching prefix row.  Returns ``(matched_key, kind, doc)``;
    raises ``KeyError`` for unregistered names (the runtime counterpart
    of dtlint DT011)."""
    row = NAME_REGISTRY.get(name)
    if row is not None:
        return (name, row[0], row[1])
    best = None
    for key, (kind, doc) in NAME_REGISTRY.items():
        if key.endswith("*") and name.startswith(key[:-1]):
            if best is None or len(key) > len(best[0]):
                best = (key, kind, doc)
    if best is None:
        raise KeyError(f"{name!r} is not declared in "
                       f"dt_tpu.obs.names.NAME_REGISTRY (dtlint DT011)")
    return best
