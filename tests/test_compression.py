"""2-bit gradient compression tests.

Reference analogs: the compression math checks in
``tests/python/unittest/test_kvstore.py`` (2-bit quantize invariants) and
``tests/nightly/dist_sync_kvstore.py`` compressed push/pull."""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from dt_tpu.parallel import compression as C


def test_quantize_values_and_residual():
    g = np.array([0.7, -0.9, 0.2, 0.0, 0.5], np.float32)
    r = np.zeros(5, np.float32)
    packed, new_r = C.np_quantize_2bit(g, r, threshold=0.5)
    out = C.np_dequantize_2bit(packed, 5, threshold=0.5)
    np.testing.assert_allclose(out, [0.5, -0.5, 0.0, 0.0, 0.5])
    np.testing.assert_allclose(new_r, g - out, rtol=1e-6)


def test_error_feedback_accumulates():
    """Small gradients below threshold eventually fire via the residual —
    the error-feedback property the reference relies on for convergence."""
    gc = C.GradientCompression(threshold=0.5)
    g = np.full(4, 0.2, np.float32)
    outs = []
    for _ in range(5):
        packed = gc.compress(g)
        outs.append(C.np_dequantize_2bit(packed, 4, 0.5))
    total = np.sum(outs, axis=0)
    # 5 * 0.2 = 1.0 of signal; two 0.5-firings expected
    np.testing.assert_allclose(total, 1.0, rtol=1e-6)


def test_jnp_matches_numpy():
    rng = np.random.RandomState(0)
    g = rng.normal(0, 1, 100).astype(np.float32)
    r = rng.normal(0, 0.1, 100).astype(np.float32)
    p_np, r_np = C.np_quantize_2bit(g, r, 0.5)
    p_j, r_j = C.quantize_2bit(jnp.asarray(g), jnp.asarray(r), 0.5)
    np.testing.assert_array_equal(p_np, np.asarray(p_j))
    np.testing.assert_allclose(r_np, np.asarray(r_j), rtol=1e-6)
    np.testing.assert_allclose(
        C.np_dequantize_2bit(p_np, 100, 0.5),
        np.asarray(C.dequantize_2bit(jnp.asarray(p_j), 100, 0.5)))


def test_packing_is_16x():
    g = np.zeros(1600, np.float32)
    packed, _ = C.np_quantize_2bit(g, np.zeros_like(g))
    assert packed.size == 100
    assert packed.dtype == np.uint32


def test_compressed_allreduce_through_scheduler():
    """End-to-end: two workers push compressed gradients; the scheduler
    dequantizes then averages (DataHandleCompressed semantics)."""
    from dt_tpu.elastic import Scheduler, WorkerClient
    s = Scheduler(initial_workers=["a", "b"])
    try:
        ca = WorkerClient("127.0.0.1", s.port, host="a", is_new=False)
        cb = WorkerClient("127.0.0.1", s.port, host="b", is_new=False)
        ga = np.array([0.7, -0.7, 0.0, 0.7], np.float32)
        gb = np.array([0.7, 0.7, 0.0, -0.7], np.float32)
        outs = {}

        def push(c, g):
            pk, _ = C.np_quantize_2bit(g, np.zeros_like(g), 0.5)
            outs[c.host] = c.allreduce(
                "g", {"packed": pk, "n": 4, "threshold": 0.5})

        ts = [threading.Thread(target=push, args=args)
              for args in ((ca, ga), (cb, gb))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        # mean of {+-0.5, 0} quantized values
        np.testing.assert_allclose(outs["a"], [0.5, 0.0, 0.0, 0.0])
        np.testing.assert_allclose(outs["a"], outs["b"])
    finally:
        s.close()


def test_kvstore_set_gradient_compression():
    from dt_tpu import parallel
    kv = parallel.create("local")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.25})
    assert kv._gradient_compression.threshold == 0.25
    with pytest.raises(ValueError, match="unsupported"):
        kv.set_gradient_compression({"type": "1bit"})


def test_compress_on_device_matches_np_sequence():
    """The device-side production path (Module.fit host-sync: quantize in
    HBM, fetch packed words) must track the np host path bit-for-bit,
    including the error-feedback residual across steps."""
    import jax.numpy as jnp
    import numpy as np
    from dt_tpu.parallel.compression import (GradientCompression,
                                             np_dequantize_2bit)

    rng = np.random.RandomState(0)
    dev = GradientCompression(0.4)
    host = GradientCompression(0.4)
    for _ in range(4):
        g = rng.randn(333).astype(np.float32)
        pk_dev = np.asarray(dev.compress_on_device(jnp.asarray(g)))
        pk_host = host.compress(g)
        np.testing.assert_array_equal(pk_dev, pk_host)
    # residual parity after the sequence
    np.testing.assert_allclose(np.asarray(dev._residual_dev),
                               host._residual, atol=1e-6)
    # and the wire decodes
    out = np_dequantize_2bit(pk_dev, 333, 0.4)
    expected = {np.float32(-0.4), np.float32(0.0), np.float32(0.4)}
    assert set(np.unique(out)).issubset(expected)


def test_module_host_sync_with_compression_end_to_end():
    """Two Modules under sync_mode='host' with 2-bit compression: the
    on-device quantize path carries the whole run and both workers end
    bit-identical (the reference's dist_sync + gradient compression
    contract, dist_sync_kvstore.py compressed section).

    Each worker gets a DISJOINT 4-device submesh and its jit steps are
    compiled on the MAIN thread before the fit threads start: two
    threads concurrently executing programs that span all 8 CPU devices
    share every device thread, and XLA CPU can wedge one program behind
    the other indefinitely (same hazard — and same medicine — as
    ``tests/test_overlap.py::_run_host_pair``; real deployments run one
    process per worker)."""
    import jax
    from dt_tpu import data, models, parallel
    from dt_tpu.elastic import Scheduler, WorkerClient
    from dt_tpu.parallel import mesh as mesh_lib
    from dt_tpu.training import Module, metrics

    s = Scheduler(initial_workers=["w0", "w1"])
    rng = np.random.RandomState(5)
    X = rng.uniform(-1, 1, (64, 12)).astype(np.float32)
    Y = rng.randint(0, 3, 64)
    params_out, errs = {}, {}

    mods = {}
    devs = jax.devices()
    try:
        for wi, host in enumerate(("w0", "w1")):
            cli = WorkerClient("127.0.0.1", s.port, host=host)
            kv = parallel.create("dist_sync")
            kv.set_controller(cli)
            kv.set_gradient_compression({"type": "2bit",
                                         "threshold": 0.05})
            mod = Module(models.create("mlp", num_classes=3, hidden=(16,)),
                         optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1},
                         kvstore=kv, seed=9,
                         mesh=mesh_lib.make_mesh(
                             devices=devs[wi * 4:(wi + 1) * 4]))
            mod.sync_mode = "host"
            # pre-compile grad/apply on the main thread (exact fit-batch
            # shapes via the iterator); outputs discarded, state untouched
            b = data.NDArrayIter(X, Y, batch_size=16).next()
            mod.init_params(b.data)
            mod._use_metric(metrics.create("acc"))  # fit's default
            mod._ensure_unravel()
            fg, fs, _, _ = mod._grad_step(
                mod.state, mod._place(b.data), mod._place(b.label),
                jax.random.PRNGKey(0))
            mod._apply_step(mod.state, fg, fs)
            mods[host] = (cli, mod)

        def worker(host):
            try:
                cli, mod = mods[host]
                mod.fit(data.NDArrayIter(X, Y, batch_size=16), num_epoch=2)
                params_out[host] = [np.asarray(p) for p in
                                    jax.tree_util.tree_leaves(
                                        mod.state.params)]
                cli.close()
            except Exception as e:  # noqa: BLE001 - surfaced by the assert
                errs[host] = e

        ts = [threading.Thread(target=worker, args=(h,))
              for h in ("w0", "w1")]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=180)
        assert not errs, errs
        assert set(params_out) == {"w0", "w1"}
        for a, b in zip(params_out["w0"], params_out["w1"]):
            np.testing.assert_array_equal(a, b)
    finally:
        s.close()
