"""Typed configuration system.

The reference spreads configuration over three mechanisms (SURVEY.md §5.6):
dmlc ``GetEnv`` env vars (reference ``src/kvstore/kvstore_dist.h:59``,
``ps-lite/src/postoffice.cc:18-31``), dmlc parameter structs
(``DMLC_DECLARE_FIELD``), and argparse in examples.  Here there is ONE typed
config system (frozen dataclasses) plus a small env layer used only for
distributed bootstrap — mirroring the env contract the reference's elastic fit
loop depends on (``python/mxnet/module/base_module.py:503-506``).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Mapping, Optional, Tuple

# ---------------------------------------------------------------------------
# Env contract (distributed bootstrap only).
#
# The reference reads these in base_module.py:503-506 and
# ps-lite/src/postoffice.cc:18-31; we keep the same names so reference-style
# launch scripts work unmodified.
# ---------------------------------------------------------------------------

ENV_NEW_WORKER = "NEW_WORKER"
ENV_EPOCH_BEGIN = "EPOCH_BEGIN"
ENV_ELASTIC_ENABLED = "ELASTIC_TRAINING_ENABLED"
ENV_ROLE = "DMLC_ROLE"
ENV_NUM_WORKER = "DMLC_NUM_WORKER"
ENV_WORKER_HOST_FILE = "WORKER_HOST_FILE"
ENV_TRAINING_CMD = "TRAINING_CMD"
ENV_SCHEDULER_URI = "DMLC_PS_ROOT_URI"
ENV_SCHEDULER_PORT = "DMLC_PS_ROOT_PORT"


# ---------------------------------------------------------------------------
# DT_* env-var registry — the single declaration point for every project
# knob, the role ps-lite's one GetEnv block played
# (``ps-lite/src/postoffice.cc:18-31``).  dtlint rule DT005 enforces it:
# a DT_*/JAX_* read anywhere in the tree must have a row here (undeclared
# reads and dead rows are findings).  Values are ``(default, doc)``;
# defaults are strings (callers convert) so one table serves flags,
# sizes, and paths alike.  Read through :func:`env` to inherit the
# default from this table.
# ---------------------------------------------------------------------------

ENV_REGISTRY: Mapping[str, Tuple[str, str]] = {
    # runtime / backend
    "DT_FORCE_CPU": ("", "1 = flip jax to the CPU backend before init (tests/CI)"),
    # Pallas kernel opt-in (model zoo)
    "DT_PALLAS_BN": ("", "1 = model zoo uses the Pallas fused BN (models/common.py)"),
    # elastic control plane / wire
    "DT_ELASTIC_SECRET": ("", "HMAC secret authenticating control frames (launcher generates per-job)"),
    "DT_ELASTIC_INSECURE": ("", "1 = explicit opt-out of frame authentication (trusted single host)"),
    "DT_ELASTIC_BIND": ("0.0.0.0", "interface the scheduler/range servers listen on"),
    "DT_ELASTIC_ADVERTISE": ("", "address peers dial to reach a server bound here (DMLC_NODE_HOST analog)"),
    "DT_WIRE_SOCKBUF": (str(4 << 20), "SO_SNDBUF/SO_RCVBUF for data-plane sockets (bytes)"),
    "DT_WIRE_INBAND": ("", "1 = legacy copying framing (no pickle-5 out-of-band buffers)"),
    "DT_AR_CHUNK_BYTES": (str(4 << 20), "represented-gradient bytes per chunked-allreduce round"),
    "DT_AR_SHARD_MIN_BYTES": (str(64 << 10), "tensors above this split across ALL range servers"),
    "DT_AR_WINDOW": ("0", "in-flight chunk-round window (0 = 2x fleet, min 4)"),
    "DT_AR_BUCKET_BYTES": (str(4 << 20), "represented-gradient bytes per overlap-pipeline bucket (D2H/wire/H2D granularity)"),
    "DT_AR_OVERLAP": ("1", "0 = serial host-sync step (no bucketed D2H/wire/H2D overlap); must be identical job-wide"),
    "DT_AR_STAGING_MB": ("64", "cap on reusable host staging-buffer bytes held by the overlap pipeline"),
    "DT_WORKER_ID": ("", "this worker's host identity under the launcher env contract"),
    "DT_RECOVERY": ("", "1 = re-register under the old identity after a crash (restart wrapper)"),
    "DT_SERVER_ID": ("0", "range-server index under the launcher env contract"),
    # control-plane HA (scheduler journal / warm standby / client failover)
    "DT_CTRL_JOURNAL": ("", "control-state write-ahead journal path (enables scheduler HA replay)"),
    "DT_CTRL_LEASE": ("", "leader lease file path (default <journal>.lease)"),
    "DT_CTRL_LEASE_S": ("2.0", "leader lease duration; the standby takes over after this much silence"),
    "DT_CTRL_TOKEN_TTL_S": ("300", "idempotency-token response-cache TTL (LRU cap + TTL bound scheduler memory)"),
    "DT_CTRL_ENDPOINTS": ("", "ordered scheduler endpoints host:port[,host:port] for client failover (leader first)"),
    "DT_CTRL_FAILOVER_S": ("60", "client-side wall budget for failing a request over across the endpoint list"),
    "DT_CTRL_SNAP_KEEP": ("2", "newest snapshot sidecars retained per journal (older ones pruned on snapshot write; min 1)"),
    # job survivability plane (r19 — coordinated fleet checkpointing,
    # cold-restart resume, graceful drain; docs/checkpoint.md)
    "DT_CKPT_DIR": ("", "fleet-checkpoint directory (per-worker <dir>/<host>/fleet-<step>.state blobs + manifest in the scheduler journal); empty = fleet checkpointing off"),
    "DT_CKPT_EVERY": ("0", "global steps between coordinated fleet checkpoints (0 = only scheduler-forced epoch-boundary checkpoints)"),
    "DT_RESUME": ("", "1 = cold-restart resume: scheduler replays the journal for the newest committed manifest; workers restore TrainState + iterator cursor and continue at the next step"),
    # observability (dt_tpu/obs)
    "DT_OBS": ("", "1 = enable dt_tpu.obs tracing (span/event ring buffer + heartbeat export)"),
    "DT_OBS_RING": (str(4096), "obs ring-buffer capacity (records per tracer; overflow drops oldest)"),
    "DT_STRAGGLER_MS": ("500", "round-contribution-lag EWMA threshold (ms) that fires the worker.straggler event"),
    # metrics / health plane (dt_tpu/obs/metrics.py — docs/observability.md r15)
    "DT_METRICS": ("", "1 = enable the dt_tpu.obs.metrics plane (gauges/histograms, time-series sampling, heartbeat export, health RPC)"),
    "DT_METRICS_INTERVAL_S": ("2.0", "wall-clock cadence of the per-process time-series sampler"),
    "DT_METRICS_RING": ("360", "time-series ring capacity (samples per process; overflow drops oldest)"),
    "DT_METRICS_PORT": ("", "scheduler Prometheus/health HTTP port (empty = no endpoint; 0 = ephemeral for tests)"),
    "DT_HEALTH_HALT": ("", "1 = training-health sentinel stops cleanly BEFORE a non-finite update is applied"),
    "DT_SLO_RULES": ("", "JSON list (or @/path) overriding the default SLO rule set by rule name (dt_tpu.obs.metrics.DEFAULT_SLO_RULES)"),
    # flight recorder / hang forensics (dt_tpu/obs/blackbox.py, r16 —
    # docs/observability.md)
    "DT_BLACKBOX": ("", "1 = arm the flight-recorder plane: crash bundles, hang watchdog, manifest (chaos_run arms it; works with DT_OBS=0)"),
    "DT_BLACKBOX_DIR": (".blackbox", "bundle + manifest.jsonl output directory"),
    "DT_BLACKBOX_RING": ("512", "flight-note ring capacity (last-N lifecycle notes per process; overflow drops oldest)"),
    "DT_BLACKBOX_MAX_MB": ("8", "per-bundle size cap (MiB), best-effort: ring tails trimmed first, thread stacks truncated last"),
    "DT_BLACKBOX_MAX_BUNDLES": ("64", "per-directory bundle retention cap: oldest bundles pruned on write (manifest rows are kept)"),
    "DT_HANG_S": ("120", "step/fleet-progress stall threshold (seconds) before the hang watchdog dumps a live bundle"),
    # device-plane observability (dt_tpu/obs/device.py, r18 —
    # docs/observability.md)
    "DT_DEVICE_OBS": ("", "1 = arm the device plane: compile.* spans + recompile-cause ledger, device.hbm_* gauges, OOM census bundles, on-demand profile_capture (chaos arms it; works with DT_OBS=0)"),
    # policy engine (dt_tpu/policy — straggler-adaptive dynamic mini-batch
    # + autoscaling; docs/policy.md)
    "DT_POLICY": ("", "1 = enable the scheduler-side policy engine (batch-share rebalancing, auto-eviction, scale proposals)"),
    "DT_POLICY_STRAGGLER_MS": ("", "breach threshold (ms) for policy decisions (default: DT_STRAGGLER_MS)"),
    "DT_POLICY_SHRINK": ("0.5", "per-breach-streak geometric batch-share shrink factor"),
    "DT_POLICY_MIN_FRAC": ("0.25", "floor on a straggler's relative share weight before eviction"),
    "DT_POLICY_EVICT_AFTER": ("0", "consecutive breaches before a non-base straggler is evicted (0 = off)"),
    "DT_POLICY_TARGET_WORKERS": ("", "autoscale target worker count for scale proposals (empty = off)"),
    # serving plane (r21 — dt_tpu/serve inference gateway + autoscale;
    # docs/serving.md)
    "DT_SERVE_DEADLINE_MS": ("50", "per-request latency budget (ms): the dynamic batcher launches a partial batch once the oldest queued request has spent half of it waiting"),
    "DT_SERVE_MAX_BATCH": ("64", "largest dynamic-batch bucket the gateway coalesces into (Predictor batch_buckets cap)"),
    "DT_SERVE_QUEUE_ROWS": ("256", "admission-control cap on queued rows per gateway; past it requests are shed with a counted serve.shed drop, never queued unbounded"),
    "DT_SERVE_POLICY": ("", "1 = scheduler-side serving autoscale mode: the policy engine scales the replica set from live serve gauges (docs/serving.md)"),
    "DT_SERVE_QHI": ("8.0", "mean queued rows per replica at/above which an overload streak accrues toward a scale_up decision"),
    "DT_SERVE_QLO": ("0.5", "mean queued rows per replica at/below which an idle streak accrues toward a scale_down decision"),
    "DT_SERVE_UP_AFTER": ("3", "consecutive overloaded serve-policy evaluations before a scale_up decision fires"),
    "DT_SERVE_DOWN_AFTER": ("6", "consecutive idle serve-policy evaluations before a scale_down decision fires"),
    "DT_SERVE_MIN_REPLICAS": ("1", "serving autoscale floor (scale_down never goes below it)"),
    "DT_SERVE_MAX_REPLICAS": ("8", "serving autoscale ceiling (scale_up never goes above it)"),
    # fault injection / chaos
    "DT_FAULT_PLAN": ("", "fault-plan JSON (or @/path) for subprocess workers (elastic/faults.py)"),
    "DT_DROP_MSG": ("", "percent of received control messages to drop (ps-lite PS_DROP_MSG fuzz)"),
    # data pipeline
    "DT_DECODE_THREADS": ("", "recordio decode pool size (default min(cpus, 16))"),
    # tools/convergence_run.py
    "DT_CONV_EPOCHS": ("40", "convergence-run epoch budget"),
    "DT_CONV_SKIP_ELASTIC": ("", "1 = skip the elastic leg of the convergence run"),
}


def env(name: str, default: Optional[str] = None) -> str:
    """Read a REGISTERED env var; unset falls back to ``default`` (when
    given) else the registry default.  Unregistered names raise — the
    runtime counterpart of dtlint DT005, so a typo'd knob fails loudly
    instead of silently returning ''."""
    spec = ENV_REGISTRY.get(name)
    if spec is None:
        raise KeyError(f"{name!r} is not declared in "
                       f"dt_tpu.config.ENV_REGISTRY (dtlint DT005)")
    v = os.environ.get(name)
    if v is not None:
        return v
    return spec[0] if default is None else default


def env_flag(name: str, default: bool = False) -> bool:
    """Parse a boolean env var the way the reference's fit loop does
    (string compare against "1"/"true", base_module.py:503-506)."""
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes")


def env_int(name: str, default: int = 0) -> int:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return int(v)


def env_str(name: str, default: str = "") -> str:
    return os.environ.get(name, default)


def enable_compilation_cache() -> str:
    """Persistent XLA compilation cache (SURVEY §7 mesh-resize mitigation:
    recompiles after elastic world rebuilds hit the cache, keyed by program
    + world size).  The one place that sets ``jax_compilation_cache_dir``:
    where ``JAX_COMPILATION_CACHE_DIR`` placed the cache (jax reads that
    variable itself) nothing is set here; otherwise it goes to
    ``<checkout>/.xla_cache`` — a fixed path, because the path is part of
    the cache key and a directory that moves never hits.
    ``Module.__init__`` calls this, so every training process of a job
    shares one cache.  Returns the effective directory."""
    import jax
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), ".xla_cache"))
    # cache everything, including small programs (elastic restarts pay
    # full compile cost otherwise)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def maybe_force_cpu() -> bool:
    """Honor ``DT_FORCE_CPU=1``: pin jax to the CPU backend before any
    backend init — the explicit in-code switch the examples, tools and
    tests use to run off the chip."""
    if env("DT_FORCE_CPU") == "1":
        import jax
        jax.config.update("jax_platforms", "cpu")
        # the persistent cache is for the chip: every XLA:CPU executable
        # reloaded from it logs a screenful of cpu_aot_loader errors
        jax.config.update("jax_enable_compilation_cache", False)
        return True
    return False


# ---------------------------------------------------------------------------
# Typed configs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device-mesh layout.

    Replaces the reference's implicit topology (N workers × G GPUs each,
    ps-lite node groups) with an explicit ``jax.sharding.Mesh``.  Axes:

    - ``data``: data parallelism (the reference's worker dimension —
      gradients psum over this axis instead of push/pull to servers).
    - ``model``: tensor parallelism (reference has only manual ``group2ctx``
      model parallelism; here it is a first-class mesh axis).
    """

    data: int = 1
    model: int = 1
    axis_names: Tuple[str, str] = ("data", "model")

    @property
    def num_devices(self) -> int:
        return self.data * self.model


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    name: str = "sgd"
    learning_rate: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 1e-4
    # Multi-precision: keep fp32 master weights when params are bf16/fp16,
    # mirroring the server-side `store_realt_` copies
    # (reference src/kvstore/kvstore_dist_server.h:240-273).
    multi_precision: bool = True
    extra: Mapping[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class LRSchedulerConfig:
    name: str = "constant"  # constant|factor|multifactor|poly|cosine
    base_lr: float = 0.1
    step: int = 1
    steps: Tuple[int, ...] = ()
    factor: float = 1.0
    stop_factor_lr: float = 1e-8
    final_lr: float = 0.0
    pwr: int = 2  # field names match dt_tpu.optim.lr_scheduler kwargs so the
    # config can be splatted straight into lr_scheduler.make()
    max_update: int = 0
    warmup_steps: int = 0
    warmup_begin_lr: float = 0.0
    warmup_mode: str = "linear"  # linear|constant

    def make(self):
        """Build the scheduler this config describes."""
        from dt_tpu.optim import lr_scheduler
        kw = dict(base_lr=self.base_lr, warmup_steps=self.warmup_steps,
                  warmup_begin_lr=self.warmup_begin_lr,
                  warmup_mode=self.warmup_mode)
        if self.name == "constant":
            return lr_scheduler.make("constant", **kw)
        if self.name == "factor":
            return lr_scheduler.make("factor", step=self.step,
                                     factor=self.factor,
                                     stop_factor_lr=self.stop_factor_lr, **kw)
        if self.name == "multifactor":
            return lr_scheduler.make("multifactor", steps=self.steps,
                                     factor=self.factor, **kw)
        if self.name == "poly":
            return lr_scheduler.make("poly", max_update=self.max_update,
                                     final_lr=self.final_lr, pwr=self.pwr,
                                     **kw)
        if self.name == "cosine":
            return lr_scheduler.make("cosine", max_update=self.max_update,
                                     final_lr=self.final_lr, **kw)
        raise ValueError(f"unknown scheduler {self.name!r}")


@dataclasses.dataclass(frozen=True)
class DataConfig:
    batch_size: int = 128  # GLOBAL batch size (Lin et al. policy: fixed
    # across membership changes; per-worker batch = global/num_workers,
    # reference example/dynamic-training/train_resnet.py:315-317).
    shuffle: bool = True
    num_parts: int = 1
    part_index: int = 0
    image_shape: Tuple[int, ...] = (3, 224, 224)
    num_classes: int = 1000
    dtype: str = "float32"


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Elastic-training control-plane knobs (reference README.md:28-70,
    ps-lite/src/elastic_training.cc)."""

    enabled: bool = False
    worker_host_file: str = ""
    # Hosts present at launch can never be removed (reference README.md:54-61).
    base_workers: Tuple[str, ...] = ()
    heartbeat_interval_s: float = 1.0
    dead_node_timeout_s: float = 60.0
    scheduler_uri: str = "127.0.0.1"
    scheduler_port: int = 9091


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    num_epochs: int = 1
    kvstore: str = "local"  # local | device | tpu_sync | dist_sync (alias)
    eval_every: int = 1
    checkpoint_prefix: str = ""
    checkpoint_period: int = 1
    log_every: int = 50
    seed: int = 0
    compute_dtype: str = "float32"  # bfloat16 for TPU perf runs
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    lr_scheduler: LRSchedulerConfig = dataclasses.field(default_factory=LRSchedulerConfig)
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    elastic: ElasticConfig = dataclasses.field(default_factory=ElasticConfig)


def replace(cfg, **kw):
    """Functional update helper for frozen configs."""
    return dataclasses.replace(cfg, **kw)
