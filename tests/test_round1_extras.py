"""Tests: remat (memory mirror), MNIST idx format, Dataset/DataLoader, SVRG."""

import gzip
import struct
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import spawned
from dt_tpu import data, models, optim
from dt_tpu.training import Module


def test_remat_module_same_results():
    """remat=True must not change the math (BASELINE memory-mirror row:
    same model, less memory, same numbers)."""
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (32, 8, 8, 3)).astype(np.float32)
    y = rng.randint(0, 2, 32).astype(np.int32)
    outs = []
    for remat in (False, True):
        mod = Module(models.create("resnet20_cifar", num_classes=2),
                     optimizer="sgd", optimizer_params={"learning_rate": 0.1},
                     seed=5, remat=remat)
        mod.fit(data.NDArrayIter(x, y, batch_size=16), num_epoch=1)
        flat, _ = jax.flatten_util.ravel_pytree(mod.state.params)
        outs.append(np.asarray(flat))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)


def _write_mnist(tmp_path, n=30, gz=False):
    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 255, (n, 28, 28), dtype=np.uint8)
    labels = rng.randint(0, 10, n).astype(np.uint8)
    opener = gzip.open if gz else open
    suffix = ".gz" if gz else ""
    ip = str(tmp_path / f"imgs-idx3-ubyte{suffix}")
    lp = str(tmp_path / f"labels-idx1-ubyte{suffix}")
    with opener(ip, "wb") as f:
        f.write(struct.pack(">IIII", 2051, n, 28, 28))
        f.write(imgs.tobytes())
    with opener(lp, "wb") as f:
        f.write(struct.pack(">II", 2049, n))
        f.write(labels.tobytes())
    return ip, lp, imgs, labels


@pytest.mark.parametrize("gz", [False, True])
def test_mnist_iter(tmp_path, gz):
    ip, lp, imgs, labels = _write_mnist(tmp_path, gz=gz)
    it = data.MNISTIter(ip, lp, batch_size=10)
    b = it.next()
    assert b.data.shape == (10, 28, 28, 1)
    np.testing.assert_allclose(b.data[0, :, :, 0], imgs[0] / 255.0,
                               rtol=1e-6)
    np.testing.assert_array_equal(b.label, labels[:10])
    flat = data.MNISTIter(ip, lp, batch_size=10, flat=True).next()
    assert flat.data.shape == (10, 784)


def test_mnist_bad_magic(tmp_path):
    p = tmp_path / "bad"
    p.write_bytes(struct.pack(">IIII", 1234, 1, 28, 28))
    from dt_tpu.data.mnist import read_idx_images
    with pytest.raises(IOError, match="magic"):
        read_idx_images(str(p))


def test_dataset_dataloader():
    x = np.arange(10 * 3).reshape(10, 3).astype(np.float32)
    y = np.arange(10).astype(np.int32)
    ds = data.ArrayDataset(x, y)
    assert len(ds) == 10
    loader = data.DataLoader(ds, batch_size=4, last_batch="keep")
    batches = list(loader)
    assert len(batches) == 3
    assert batches[0].data.shape == (4, 3)
    assert batches[2].data.shape == (2, 3)  # 'keep' keeps the partial batch
    # discard drops it
    loader2 = data.DataLoader(ds, batch_size=4, last_batch="discard")
    assert len(list(loader2)) == 2
    # transform applies lazily
    ds2 = ds.transform_first(lambda img: img * 2)
    loader3 = data.DataLoader(ds2, batch_size=10)
    np.testing.assert_allclose(list(loader3)[0].data, x * 2)


def test_dataloader_shuffle_covers_all():
    ds = data.ArrayDataset(np.arange(8).reshape(8, 1))
    loader = data.DataLoader(ds, batch_size=4, shuffle=True, seed=3)
    seen = []
    for b in loader:
        seen.extend(b.data[:, 0].tolist())
    assert sorted(seen) == list(range(8))
    seen2 = []
    for b in loader:  # next epoch reshuffles
        seen2.extend(b.data[:, 0].tolist())
    assert sorted(seen2) == list(range(8))
    assert seen != seen2


def _before_the_backend(check):
    """``check`` (a function of this module) in a process that has not
    touched the JAX backend.  ``DataLoader`` forks its pool, and asks for
    that before XLA's threads exist (its docstring): a pytest worker that
    has run other files has some ninety, and a child forked there can block
    for good on a lock one of them held, with the test waiting on it
    (2,605 s in this PR's first run of the suite, until the child was
    killed by hand)."""
    r = spawned.run([sys.executable, "-c", "import test_round1_extras as t; "
                     f"t.{check.__name__}()"], cwd=spawned.HERE,
                    PYTHONPATH=spawned.REPO)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]


def test_dataloader_with_workers():
    """num_workers>0 forks a real process pool (reference
    gluon/data/dataloader.py:26-75): batches match the in-process path
    exactly, order preserved, epochs repeat, transforms run in workers."""
    _before_the_backend(_loader_with_workers)


def _loader_with_workers():
    ds = data.ArrayDataset(np.arange(12).reshape(12, 1).astype(np.float32))
    loader = data.DataLoader(ds, batch_size=4, num_workers=2)
    try:
        got = list(loader)
        assert len(got) == 3
        want = list(data.DataLoader(ds, batch_size=4))
        for b, w in zip(got, want):
            np.testing.assert_array_equal(b.data, w.data)
        assert len(list(loader)) == 3  # second epoch works
    finally:
        loader.close()

    # unpicklable transform (closure) still works: fork inherits it
    scale = 3.0
    ds2 = ds.transform_first(lambda v: v * scale)
    loader2 = data.DataLoader(ds2, batch_size=6, num_workers=2,
                              last_batch="discard")
    try:
        got = list(loader2)
        assert len(got) == 2
        np.testing.assert_allclose(
            np.concatenate([b.data for b in got])[:, 0],
            np.arange(12, dtype=np.float32) * 3.0)
    finally:
        loader2.close()


def test_dataloader_workers_shuffle_matches_inprocess():
    """Same seed -> same shuffled order with and without workers (the
    sampler runs in the master; workers only evaluate batches)."""
    _before_the_backend(_loader_workers_shuffle)


def _loader_workers_shuffle():
    ds = data.ArrayDataset(np.arange(20).reshape(20, 1))
    a = data.DataLoader(ds, batch_size=4, shuffle=True, seed=7,
                        num_workers=2)
    try:
        got = [b.data[:, 0].tolist() for b in a]
    finally:
        a.close()
    b = data.DataLoader(ds, batch_size=4, shuffle=True, seed=7)
    want = [bb.data[:, 0].tolist() for bb in b]
    assert got == want


def test_svrg_reduces_variance_and_converges():
    """SVRG on a quadratic with noisy per-batch gradients: corrected steps
    converge where plain SGD with the same lr oscillates more."""
    rng = np.random.RandomState(0)
    target = jnp.asarray(rng.normal(0, 1, 8).astype(np.float32))
    noises = rng.normal(0, 0.5, (10, 8)).astype(np.float32)
    noises -= noises.mean(axis=0, keepdims=True)  # zero-mean: the true
    # full gradient then vanishes exactly at w = target
    batches = [jnp.asarray(n) for n in noises]

    def grad_fn(w, noise):
        return {"w": 2 * (w["w"] - target) + noise}

    tx = optim.svrg(optim.sgd(0.05))
    w = {"w": jnp.zeros(8)}
    state = tx.init(w)
    for epoch in range(6):
        # epoch boundary: full gradient at snapshot (noise averages out)
        full = optim.full_gradient(lambda p, b: grad_fn(p, b), w, batches)
        state = optim.refresh_snapshot(state, w, full)
        snap = state.w_snap
        for b in batches:
            g_w = grad_fn(w, b)
            g_s = grad_fn(snap, b)
            updates, state = tx.update((g_w, g_s), state, w)
            w = optax.apply_updates(w, updates)
    err = float(jnp.abs(w["w"] - target).max())
    assert err < 0.05, err
