"""C predict ABI end-to-end: a PURE-C host serves a dt_tpu ONNX model.

Reference capability: ``src/c_api/c_predict_api.cc`` + the predict-cpp
demo — a C surface over the full runtime for foreign-language serving.
Here: ``dt_tpu/native/predict_capi.cc`` (embeds CPython, drives
``dt_tpu.capi_bridge`` -> ``Predictor.from_onnx``) is compiled into a
shared library, a plain-C demo binary links it, and its output must
match the in-Python predictor bit-for-bit on the same input.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import spawned
from spawned import REPO

NATIVE = os.path.join(REPO, "dt_tpu", "native")


def _pyflags():
    inc = subprocess.run(["python3-config", "--includes"],
                         capture_output=True, text=True, check=True
                         ).stdout.split()
    ld = subprocess.run(["python3-config", "--ldflags", "--embed"],
                        capture_output=True, text=True, check=True
                        ).stdout.split()
    return inc, ld


def test_c_host_serves_onnx_model(tmp_path):
    try:
        inc, ld = _pyflags()
    except (subprocess.CalledProcessError, FileNotFoundError):
        pytest.skip("python3-config not available")

    # 1) export a small model to a self-contained ONNX artifact
    import jax
    import jax.numpy as jnp
    from dt_tpu import models, onnx as onnx_lib

    model = models.create("mlp", num_classes=3, hidden=(8,))
    x_sample = jnp.zeros((1, 6, 6, 1), jnp.float32)
    variables = model.init({"params": jax.random.PRNGKey(0)}, x_sample,
                           training=False)
    blob = onnx_lib.export_onnx(model, x_sample, variables=variables)
    onnx_path = str(tmp_path / "mlp.onnx")
    with open(onnx_path, "wb") as f:
        f.write(blob)

    # 2) build the C ABI library + the pure-C demo host
    so = str(tmp_path / "libdtpredict.so")
    exe = str(tmp_path / "predict_demo")
    try:
        subprocess.run(["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                        os.path.join(NATIVE, "predict_capi.cc"),
                        "-o", so] + inc + ld, check=True,
                       capture_output=True, text=True)
        subprocess.run(["gcc", "-O2",
                        os.path.join(NATIVE, "predict_capi_demo.c"),
                        so, "-o", exe,
                        f"-Wl,-rpath,{tmp_path}"] + ld, check=True,
                       capture_output=True, text=True)
    except subprocess.CalledProcessError as e:
        pytest.skip(f"native toolchain unavailable: {e.stderr[-400:]}")

    # 3) run the C host (its embedded interpreter must find the venv +
    # repo, and must not touch a wedged TPU backend)
    site = [p for p in sys.path if p.endswith("site-packages")]
    r = spawned.run([exe, onnx_path, "1", "6", "6", "1"],
                    PYTHONPATH=os.pathsep.join([REPO] + site),
                    DT_FORCE_CPU=1)
    assert r.returncode == 0, r.stdout[-500:] + r.stderr[-1500:]
    lines = r.stdout.strip().splitlines()
    assert lines[0].startswith("OUT ")
    out_shape = tuple(int(v) for v in lines[0].split()[1:])
    got = np.asarray([float(v) for v in lines[1:]],
                     np.float32).reshape(out_shape)

    # 4) parity vs the in-Python predictor on the same ramp input
    from dt_tpu.predictor import Predictor
    n = 36
    ramp = (np.arange(n) % 17 / 17.0 - 0.5).astype(np.float32)
    x = ramp.reshape(1, 6, 6, 1)
    want = np.asarray(Predictor.from_onnx(onnx_path).predict(x),
                      np.float32)
    assert out_shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
