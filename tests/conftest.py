"""Test fixture: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's distributed-test mechanism (single-machine
multi-process via the local tracker, SURVEY.md §4): here the analog is
``--xla_force_host_platform_device_count=8`` so sharding/collective tests
exercise real multi-device paths without TPU hardware.  Must run before any
jax import.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# The suite opts out of the persistent compilation cache that
# ``config.enable_compilation_cache`` (every ``Module``) now places at
# ``<checkout>/.xla_cache``: XLA:CPU executables reloaded across runs log
# ``cpu_aot_loader`` errors by the screenful, and a tier-1 result must not
# depend on what an earlier run left on disk.  Worker subprocesses inherit it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import faulthandler  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import spawned  # noqa: E402  (tests/)

#: seconds a test may take: above the longest honest test on the driver's
#: machine (a benchmark rehearsal, some 450 s there) and under half of the
#: tier-1 command's limit
TEST_LIMIT_S = 600


@pytest.fixture(autouse=True)
def _limit(request):
    """Fails the one test that runs past ``TEST_LIMIT_S``, naming it and what
    its threads were doing, and lets the worker go on to the next test.  An
    alarm raising in the main thread, where pytest and xdist's workers
    (``execmodel=main_thread_only``) run the tests."""
    def expired(signum, frame):
        with tempfile.TemporaryFile("w+") as stacks:
            faulthandler.dump_traceback(stacks)
            stacks.seek(0)
            pytest.fail(f"{request.node.nodeid} ran past its limit of "
                        f"{TEST_LIMIT_S} s; its threads were at:\n"
                        f"{stacks.read()}", pytrace=False)
    before = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


#: The order in which tier-1's long files start (seconds: PR 46's runs of the
#: driver's command, six workers on eight cores); every other file follows in
#: the order of collection.  A file is one unit of work under ``--dist
#: loadfile``, handed to the next free worker, and two things decide the run's
#: length.  xdist's own order starts the files with the most cases first, which
#: left ``test_benchmark_rehearsal.py`` (8 cases, 270 s) to start at 525 s of
#: 795 and end alone.  And every rehearsal under ``tests/benchmark`` pins its
#: subprocess to the same two cores (``bench_toy.two_cores``): four of those
#: files started together took 1.5 to 2.1 times their seconds each (823 s for
#: the run), and beside one other the suite's longest test,
#: ``test_the_control_one_precision_below_fails_a_limit``, took 233 s for its
#: 87, too near its own 600 s on a slower machine.  So: that file first and
#: the only rehearsal file while it runs, beside the long files of the rest;
#: then the cells' rehearsal files, three at a time as xdist's order had them.
START_ORDER = (
    "benchmark/test_benchmark_rehearsal.py",    # 270
    "test_sparse_attention.py",                 # 188
    "test_routed_lm.py",                        # 182
    "test_flash_attention.py",                  # 178
    "test_models.py",                           # 128
    "test_flash_compile_tpu.py",                # 111
    "test_pattern_lm.py",                       # 99 (PR 47, alone)
    "test_mixed_attention.py",                  # 98
    "test_examples.py",                         # 93
    "test_onnx.py",                             # 84
    "test_head_lanes.py",                       # 78
    "test_dtlint.py",                           # 72
    "test_ssm.py",                              # 71
    "test_hybrid_lm.py",                        # 71
    "benchmark/test_hybrid_cell.py",            # 268
    "benchmark/test_keye_cell.py",              # 237
    "benchmark/test_sdar_cell.py",              # 220
    "test_transformer_ulysses.py",              # 66
    "test_short_conv_lm.py",                    # 62
    "test_interchange.py",                      # 56
    "test_smoke_chip.py",                       # 48
    "test_crash_recovery.py",                   # 46
    "test_pipeline_transformer.py",             # 46
    "test_training.py",                         # 44
    "test_rcnn.py",                             # 36
    "test_elastic_integration.py",              # 34
    "test_metric_device_form.py",               # 34
    "test_jax_distributed.py",                  # 30
    "benchmark/test_program_spans.py",          # 124
    "benchmark/test_laguna_cell.py",            # 107
    "benchmark/test_lfm2_cell.py",              # 103
    "benchmark/test_nemotron_cell.py",          # 55 (PR 47, alone)
    "benchmark/test_build_metrics.py",          # 41
)


def pytest_configure(config):
    # the order below is the order of work: not xdist's own, by count of cases
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    """``START_ORDER``'s files to the front, in its order; the others and
    every file's own cases stay in the order of collection."""
    rank = {name: at for at, name in enumerate(START_ORDER)}
    items.sort(key=lambda item: rank.get(
        item.nodeid.split("::")[0].removeprefix("tests/"), len(rank)))


@pytest.fixture
def workers():
    """The test's worker processes under one deadline of 300 s
    (``spawned.Workers``); what still runs at the end is killed."""
    with spawned.Workers() as started:
        yield started


@pytest.fixture
def rng():
    import jax
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _seed_numpy():
    np.random.seed(0)
