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

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    import jax
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _seed_numpy():
    np.random.seed(0)
