"""The state-space scan's kernels compiled by the TPU's own compiler for a
described v5e, no chip attached (``tests/test_grouped_compile_tpu.py``'s
pattern): Mosaic refuses what the Pallas interpreter takes, and a refusal
here costs no chip time.  Nothing runs; no time is read."""

import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from dt_tpu.ops import ssm
from dt_tpu.ops.pallas import ssd


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


# (B, L, H, P, G, N, chunk, dtype): the hybrid cell's nine layers
# (granite-4.0-h-micro: 64 heads of 64, one group, states of 128, chunks of
# 256, bfloat16); a length with a tail; two groups in float32; one chip's
# share of a mixer of 128 heads in 8 groups (16 heads, their one group) at
# chunks of 128 over 8,192 positions (PR 47)
SHAPES = [
    (2, 4096, 64, 64, 1, 128, 256, jnp.bfloat16),
    (1, 1000, 16, 64, 1, 128, 256, jnp.bfloat16),
    (1, 512, 16, 64, 2, 128, 256, jnp.float32),
    (1, 8192, 16, 64, 1, 128, 128, jnp.bfloat16),
]


def _kernels(text):
    return [ln.split("=")[0] for ln in text.splitlines()
            if "tpu_custom_call" in ln and "custom-call(" in ln]


@pytest.mark.parametrize("b,l,h,p,g,n,chunk,dtype", SHAPES)
def test_mosaic_takes_both_kernels(one_chip, monkeypatch, b, l, h, p, g, n,
                                   chunk, dtype):
    """The value and the five gradients through ``ssd_scan``: one kernel
    named ``ssd_fwd`` and one named ``ssd_bwd``, the names the benchmark's
    ``kernel.ssd_*`` find them by, and no loop left beside them."""
    shape = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one_chip)  # noqa: E731
    x, dt, a = shape((b, l, h, p), dtype), shape((b, l, h), jnp.float32), \
        shape((h,), jnp.float32)
    bm = cm = shape((b, l, g, n), dtype)

    def value_and_gradients(x, dt, a, bm, cm, dy):
        y, pull = jax.vjp(lambda *t: ssm.ssd_scan(*t, chunk=chunk),
                          x, dt, a, bm, cm)
        return y, pull(dy)

    assert ssd.head_block(h, g, p, n, chunk, jnp.dtype(dtype).itemsize) == 8
    # the process sees the CPU and would take the interpreter's branch
    monkeypatch.setattr(ssd, "_default_interpret", lambda: False)
    text = jax.jit(value_and_gradients).lower(
        x, dt, a, bm, cm, x).compile().as_text()
    calls = _kernels(text)
    assert len(calls) == 2, calls
    assert sum("ssd_fwd" in c for c in calls) == 1, calls
    assert sum("ssd_bwd" in c for c in calls) == 1, calls
    assert " while(" not in text


def test_the_head_block_is_within_the_budget():
    """At the cell's shapes the reckoning leaves room under the budget the
    compiler is given twice of."""
    assert ssd.vmem_bytes(8, 64, 64, 128, 256, 2) <= ssd.VMEM_BUDGET
    assert ssd.vmem_bytes(8, 16, 64, 128, 128, 2) <= ssd.VMEM_BUDGET
