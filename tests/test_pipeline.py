"""Pipeline parallelism vs sequential oracle on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np

from dt_tpu.parallel import mesh as mesh_lib
from dt_tpu.parallel.pipeline import (pipeline_apply, sequential_apply)


def _setup(stages=4, micro=6, mb=2, d=8, seed=0):
    rng = np.random.RandomState(seed)
    params = {
        "w": jnp.asarray(rng.normal(0, 0.5, (stages, d, d)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(0, 0.1, (stages, d)).astype(np.float32)),
    }
    x = jnp.asarray(rng.normal(0, 1, (micro, mb, d)).astype(np.float32))
    return params, x


def _stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def test_pipeline_matches_sequential():
    mesh = mesh_lib.make_mesh(data=4, axis_names=("pipe", "model"),
                              model=1, devices=jax.devices()[:4])
    params, x = _setup(stages=4)
    got = pipeline_apply(_stage_fn, params, x, mesh, axis_name="pipe")
    want = sequential_apply(_stage_fn, params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_eight_stages_under_jit():
    mesh = mesh_lib.make_mesh(data=8, axis_names=("pipe", "model"))
    params, x = _setup(stages=8, micro=3)

    @jax.jit
    def f(params, x):
        return pipeline_apply(_stage_fn, params, x, mesh, axis_name="pipe")

    got = f(params, x)
    want = sequential_apply(_stage_fn, params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_stage_count_mismatch_raises():
    """More stages than pipe devices must raise, not silently drop layers."""
    import pytest
    mesh = mesh_lib.make_mesh(data=4, axis_names=("pipe", "model"),
                              model=1, devices=jax.devices()[:4])
    params, x = _setup(stages=8)
    with pytest.raises(ValueError, match="8 stages"):
        pipeline_apply(_stage_fn, params, x, mesh, axis_name="pipe")


def test_pipeline_grad_matches_oracle():
    mesh = mesh_lib.make_mesh(data=4, axis_names=("pipe", "model"),
                              model=1, devices=jax.devices()[:4])
    params, x = _setup(stages=4, micro=4)

    def loss_p(params):
        return jnp.sum(pipeline_apply(_stage_fn, params, x, mesh,
                                      axis_name="pipe") ** 2)

    def loss_s(params):
        return jnp.sum(sequential_apply(_stage_fn, params, x) ** 2)

    gp = jax.grad(loss_p)(params)
    gs = jax.grad(loss_s)(params)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


_TRAIN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax; jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp, numpy as np, optax
from dt_tpu.parallel import mesh as mesh_lib
from dt_tpu.parallel.pipeline import pipeline_apply
from dt_tpu import optim

mesh = mesh_lib.make_mesh(data=4, axis_names=("pipe", "model"), model=1,
                          devices=jax.devices()[:4])
rng = np.random.RandomState(0)
params = {"w": jnp.asarray(rng.normal(0, 0.5, (4, 8, 8)).astype(np.float32)),
          "b": jnp.asarray(rng.normal(0, 0.1, (4, 8)).astype(np.float32))}
x = jnp.asarray(rng.normal(0, 1, (4, 2, 8)).astype(np.float32))
target = jnp.ones((4, 2, 8)) * 0.3
stage = lambda p, h: jnp.tanh(h @ p["w"] + p["b"])
tx = optim.adam(1e-2)
st = tx.init(params)

@jax.jit
def step(params, st):
    l, g = jax.value_and_grad(lambda p: jnp.mean(
        (pipeline_apply(stage, p, x, mesh, axis_name="pipe") - target) ** 2
    ))(params)
    u, st2 = tx.update(g, st, params)
    return optax.apply_updates(params, u), st2, l

l0 = None
for _ in range(40):
    params, st, l = step(params, st)
    l0 = l0 if l0 is not None else float(l)
assert float(l) < l0 * 0.2, (l0, float(l))
print("PIPELINE_TRAIN_OK", float(l))
"""


def test_pipeline_trains():
    """End-to-end: fit a tiny pipelined MLP to a regression target.

    Runs in a subprocess with one crash-retry: this jax build's XLA CPU
    CollectivePermuteThunk has an intermittent crash under many repeated
    executions (upstream runtime race; does not affect TPU).  A wrong
    RESULT still fails immediately — only abnormal termination retries.
    """
    import sys

    import spawned
    for attempt in range(2):
        r = spawned.run([sys.executable, "-c", _TRAIN_SCRIPT],
                        cwd=spawned.REPO)
        if r.returncode == 0:
            assert "PIPELINE_TRAIN_OK" in r.stdout
            return
        if r.returncode > 0:  # real Python failure: no retry
            raise AssertionError(r.stdout[-2000:] + r.stderr[-2000:])
    raise AssertionError(
        f"pipeline training crashed twice (rc={r.returncode}):\n"
        + r.stderr[-1500:])
