"""The grouped products compiled by the TPU's own compiler for a described
v5e, no chip attached (``tests/test_flash_compile_tpu.py``'s pattern): Mosaic
refuses what the Pallas interpreter takes, and a refusal here costs no chip
time.  Nothing runs; no time is read."""

import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from dt_tpu.ops.pallas import grouped


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# the two routed cells' buffers against their experts' matrices (gate and
# up, then down: the turned form of the other), 16 experts held
SHAPES = [
    (49152, 2048, 768, 16),
    (24576, 2048, 768, 16),
    (49152, 768, 2048, 16),
    (24576, 768, 2048, 16),
    # the mixed-attention cell's: 32 experts held, of width 512 (PR 41)
    (24576, 2048, 512, 32),
    (24576, 512, 2048, 32),
    # the widest experts': 8 held, of width 1,792: the transposed product
    # holds 33.5 MiB by vmem_bytes' reckoning, under its own budget (PR 43)
    (24576, 2048, 1792, 8),
    (24576, 1792, 2048, 8),
    # experts that work in a 1,024-wide latent: 8 held, of width 2,688, over
    # a buffer of 33 row tiles of 128 (256 does not divide 4,224) (PR 47)
    (4224, 1024, 2688, 8),
    (4224, 2688, 1024, 8),
]


def _kernels(text):
    return [ln.split("=")[0] for ln in text.splitlines()
            if "tpu_custom_call" in ln and "custom-call(" in ln]


@pytest.mark.parametrize("m,k,n,groups", SHAPES)
def test_mosaic_takes_the_three_products(one_chip, monkeypatch, m, k, n,
                                         groups):
    """The value and both gradients at the derived tiles, under the names
    the benchmark's ``kernel.gmm_*.pl`` find them by, and no ``ragged_dot``
    left beside them."""
    shape = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one_chip)  # noqa: E731
    lhs, rhs = shape((m, k), jnp.bfloat16), shape((groups, k, n),
                                                  jnp.bfloat16)
    sizes, d_out = shape((groups,), jnp.int32), shape((m, n), jnp.float32)

    def value_and_gradients(lhs, rhs, sizes, d_out):
        out, pull = jax.vjp(
            lambda a, b: grouped.grouped_matmul(a, b, sizes), lhs, rhs)
        return out, pull(d_out)

    # the process sees the CPU and would take the interpreter's branch
    monkeypatch.setattr(grouped, "_default_interpret", lambda: False)
    text = jax.jit(value_and_gradients).lower(
        lhs, rhs, sizes, d_out).compile().as_text()
    calls = _kernels(text)
    assert len(calls) == 3 and all("grouped_mm" in c for c in calls), calls
    assert sum("grouped_mm_t" in c for c in calls) == 1, calls
    assert "ragged-dot" not in text


@pytest.mark.parametrize("m,k,n,groups", SHAPES)
def test_the_derived_tiles_are_within_the_budget(m, k, n, groups):
    """bfloat16 operands, float32 out and cotangent: each product has a tile
    and ``vmem_bytes`` of it is within the budget ``row_tile`` states."""
    for args, transposed in (((m, k, n, 2, 2, 4), False),
                             ((m, n, k, 4, 2, 2), False),
                             ((m, k, n, 2, 4, 2), True)):
        tm = grouped.row_tile(*args, transposed=transposed)
        assert tm in grouped.ROW_TILES and m % tm == 0
        assert grouped.vmem_bytes(tm, *args[1:], transposed=transposed) \
            <= (grouped.VMEM_BUDGET_T if transposed else grouped.VMEM_BUDGET)
