"""A temporary copy of the benchmark with toy-size cells added as files.

What a later PR does, done in a scratch directory: a configuration (one of
them cut: ``reduced``, ``published``, ``deployment``), a traffic mix (with a
generator module of its own), per-layer metrics (with a reader module of
their own, which reads the job and the kept trace through ``ctx``) and a
cell are each new files plus one new entry; no file that is there is
edited.  The
toy cells are the CPU rehearsal's: ResNet-50's graph on 32x32 images and a
two-layer GPT-2, float32, through the real runner.
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")

#: float32 on the CPU against the float32 reference agrees to about 1e-6;
#: the limits leave room for the order of summation and are far below what
#: the float8 control or a broken step gives (about 1e-2 and 1)
TOY_LIMITS = {"loss_rel": 1e-4, "first_gradient_worst_leaf": 3e-3,
              "first_gradient_difference": 3e-3,
              "param_change_worst_leaf": 3e-3,
              "param_change_difference": 3e-3}


def load(path):
    with open(path) as f:
        return json.load(f)


def dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def toy_config(base, limits=None, **changes):
    """A toy configuration; with ``reduced`` among the ``changes`` a cut
    one, whose ``published`` values are the base's."""
    cfg = load(os.path.join(BENCH, "configs", base + ".json"))
    if changes.get("reduced"):
        changes["published"] = {k: cfg[k] for k in changes["reduced"]}
    cfg.update(changes)
    cfg["source"] = f"toy-size copy of {base} for the CPU rehearsal"
    cfg["assumed"], cfg["departures"] = {}, ["toy size"]
    cfg["dtype"] = "float32"
    cfg["check"] = {**cfg["check"], "limits": dict(limits or TOY_LIMITS),
                    "limits_set_from": "tests/benchmark/bench_toy.py"}
    return cfg


TOY_CONFIGS = {
    "resnet50-toy": lambda: toy_config(
        "resnet50", name="resnet50-toy", image_shape=[32, 32, 3],
        num_classes=10),
    # a cut configuration: what a model_config PR brings for a model that
    # does not fit one chip whole (depth and vocabulary rows held here)
    "gpt2-toy": lambda: toy_config(
        "gpt2-medium", name="gpt2-toy", vocab_size=64, n_positions=128,
        n_ctx=128, n_embd=128, n_layer=2, n_head=2, n_inner=512,
        reduced=["n_layer", "vocab_size"],
        deployment="toy: this chip holds 2 of 24 layers and 64 of 50,257 "
                   "vocabulary rows, as if the others were other chips' of "
                   "a pipeline and of a head shared by rows"),
}
for _broken in ("FrozenLMJob", "HalfBatchLMJob"):
    # the same toy LM with the timed path broken underneath (toy/)
    TOY_CONFIGS["gpt2-toy-" + _broken] = lambda b=_broken: {
        **TOY_CONFIGS["gpt2-toy"](), "name": "gpt2-toy-" + b,
        "driver": "broken_drivers:" + b}
TOY_TRAFFIC = {
    "synth_b8": {"generator": "traffic:uniform_images", "what": "toy",
                 "batch": 8,
                 "distinct_batches": 3, "steps_per_reading": 2,
                 "warm_steps": 1, "trace_last_s": 0.6},
    "tokens_b2_s128": {"generator": "toy_traffic:counting_tokens",
                       "what": "toy",
                       "batch": 2, "seq_len": 128, "seq_parallel": "flash",
                       "distinct_batches": 3, "steps_per_reading": 1,
                       "warm_steps": 0, "trace_last_s": 0.6},
}
#: toy cell -> (configuration, traffic, the real cell whose metrics it takes)
TOY_CELLS = {"toy-synth": ("resnet50-toy", "synth_b8", "resnet50-synth"),
             "toy-lm": ("gpt2-toy", "tokens_b2_s128", "gpt2m-seq1024"),
             "toy-lm-frozen": ("gpt2-toy-FrozenLMJob", "tokens_b2_s128",
                               "gpt2m-seq1024"),
             "toy-lm-half": ("gpt2-toy-HalfBatchLMJob", "tokens_b2_s128",
                             "gpt2m-seq1024")}

TOY_GENERATOR = '''"""A generator module a later PR adds beside traffic.py."""

import numpy as np


def counting_tokens(rng, traffic, cfg):
    """Rows that count upward from a seeded start, so that every row of a
    batch differs and the next token can be learned."""
    start = rng.integers(0, cfg["vocab_size"], (traffic["batch"], 1))
    step = rng.integers(1, 7, (traffic["batch"], 1))
    toks = ((start + step * np.arange(traffic["seq_len"])[None, :])
            % cfg["vocab_size"]).astype(np.int32)
    return toks, np.roll(toks, -1, axis=1)
'''

TOY_READER = '''"""A reader module a later PR adds beside the others.  Given only ``ctx``
it reads the runner's own numbers, a counter the program keeps on the job,
and the traced steps' file (kept until every reader has returned)."""

import os


def steps_in_window(ctx, m):
    return float(ctx["steps"])


def job_device_flushes(ctx, m):
    return float(ctx["job"].mod.metric_flushes["device"])


def trace_bytes(ctx, m):
    path = ctx["trace_path"]
    return float(os.path.getsize(path)) if path else None
'''
#: the per-layer metrics the copy adds, each a file naming a reader above
TOY_METRICS = {
    "loop.steps_in_window": ("steps_in_window", "count",
                             "steps completed inside the window"),
    "loop.job_device_flushes": ("job_device_flushes", "count",
                                "Module.metric_flushes through ctx['job']"),
    "loop.trace_bytes": ("trace_bytes", "bytes",
                         "size of the traced steps' .xplane.pb"),
}


def make_copy(dest):
    """``dest``/BENCHMARK.json and ``dest``/benchmark with the toy cells,
    new per-layer metrics and their readers added.  Returns the manifest's
    path."""
    shutil.copytree(BENCH, os.path.join(dest, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = os.path.join(dest, "benchmark")
    manifest = load(os.path.join(REPO, "BENCHMARK.json"))
    for name, make in TOY_CONFIGS.items():
        cfg = make()
        dump(cfg, os.path.join(bench, "configs", name + ".json"))
        manifest["configs"].append({
            "name": name, "source": "toy", "reduced": cfg["reduced"],
            "why": "toy", "file": f"benchmark/configs/{name}.json"})
    for name, traffic in TOY_TRAFFIC.items():
        dump(traffic, os.path.join(bench, "traffic", name + ".json"))
    for cell, (config, traffic, like) in TOY_CELLS.items():
        manifest["workloads"].append({"name": cell, "config": config,
                                      "traffic": traffic, "chips": 1,
                                      "why": "toy"})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(cell)
    with open(os.path.join(bench, "toy_readers.py"), "w") as f:
        f.write(TOY_READER)
    with open(os.path.join(bench, "toy_traffic.py"), "w") as f:
        f.write(TOY_GENERATOR)
    for name, (reader, unit, what) in TOY_METRICS.items():
        dump({"reader": "toy_readers:" + reader, "what": what},
             os.path.join(bench, "metrics", name + ".json"))
        manifest["per_layer"].append({
            "name": name, "unit": unit, "better": "higher",
            "source": "program_counter",
            "layer": "loop: training/module.py fit",
            "moves": "tokens_per_s_per_chip", "workloads": ["toy-lm"]})
    for src in os.listdir(TOY):   # the broken drivers of the rehearsal
        if src.endswith(".py"):
            shutil.copy(os.path.join(TOY, src), bench)
    path = os.path.join(dest, "BENCHMARK.json")
    dump(manifest, path)
    return path


#: what a model_config PR appends after ``make_copy``: a cell of its own on
#: ``tokens_per_s_per_chip`` and two per-layer entries that list it alone
LATER_CELL = "later-lm"
LATER_METRICS = {"later.steps_in_window": "steps_in_window",
                 "later.trace_bytes": "trace_bytes"}
#: an accepted cell's own metric that the later cell reports too (its blocks
#: are rematerialised as well): its name goes to the end of that list
LATER_SHARED = ("model.remat_ms_per_step",)


def later_pr_appends(path):
    """To the copy's manifest at ``path``, what ``benchmark/README.md`` says
    a later PR does for a language-model cell: a traffic file and a cell at
    the end of ``workloads``, the cell's name at the end of
    ``tokens_per_s_per_chip``'s list, of every ``.lm`` list and of the
    lists of ``LATER_SHARED``, two per-layer entries of its own (each with
    its file) at the end of ``per_layer``.  Nothing that is there is edited
    or moved."""
    bench = os.path.join(os.path.dirname(path), "benchmark")
    manifest = load(path)
    dump(TOY_TRAFFIC["tokens_b2_s128"],
         os.path.join(bench, "traffic", "tokens_b2_s128_later.json"))
    manifest["workloads"].append({
        "name": LATER_CELL, "config": "gpt2-toy",
        "traffic": "tokens_b2_s128_later", "chips": 1, "why": "toy"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "tokens_per_s_per_chip" or \
                m["name"].endswith(".lm") or m["name"] in LATER_SHARED:
            m["workloads"].append(LATER_CELL)
    for name, reader in LATER_METRICS.items():
        dump({"reader": "toy_readers:" + reader, "what": "a later cell's"},
             os.path.join(bench, "metrics", name + ".json"))
        manifest["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter",
            "layer": "loop: training/module.py fit",
            "moves": "tokens_per_s_per_chip", "workloads": [LATER_CELL]})
    dump(manifest, path)
    return manifest


def rehearsal_env():
    """The CPU rehearsal's environment: one CPU device, as a one-chip cell
    has, and one thread for the arithmetic."""
    return {**os.environ, "DT_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_cpu_multi_thread_eigen=false",
            "OMP_NUM_THREADS": "1", "OPENBLAS_NUM_THREADS": "1"}


def two_cores():
    """Confine a rehearsal's process to two of the cores this one may use:
    XLA compiles on every core it finds, and a rehearsal beside five other
    test workers must not take the machine from their timed tests."""
    os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[:2])


def run_cell(manifest, workload, seed=7, seconds=1, trace=0, timeout=600):
    """One run of the real runner on the CPU; returns (rc, last stdout
    line parsed or None, all output)."""
    env = rehearsal_env()
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         manifest, "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
        preexec_fn=two_cores)
    lines = proc.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1])
    except (IndexError, ValueError):
        last = None
    return proc.returncode, last, proc.stdout + proc.stderr
