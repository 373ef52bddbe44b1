"""MeshManager snapshot/rebuild tests (single-process paths; the
jax.distributed branch needs a real pod)."""

import jax
import jax.numpy as jnp
import numpy as np

from dt_tpu.elastic.mesh_manager import (MeshManager, restore_state,
                                         snapshot_state)
from dt_tpu.parallel import mesh as mesh_lib


def test_snapshot_and_restore_roundtrip():
    mesh = mesh_lib.make_mesh()
    state = {"w": jax.device_put(jnp.arange(8.0),
                                 mesh_lib.replicate_sharding(mesh)),
             "step": jnp.asarray(3)}
    host = snapshot_state(state)
    assert isinstance(host["w"], np.ndarray)
    back = restore_state(host, mesh)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.arange(8.0))
    assert len(back["w"].sharding.device_set) == 8


def test_rebuild_single_process():
    mm = MeshManager()
    mesh = mm.initialize()
    state = {"w": jax.device_put(jnp.ones(4),
                                 mesh_lib.replicate_sharding(mesh))}
    new_mesh, restored = mm.rebuild(state, num_processes=1, process_id=0)
    assert new_mesh.devices.size == 8
    np.testing.assert_array_equal(np.asarray(restored["w"]), 1.0)
    # training continues on the new mesh
    y = jax.jit(lambda s: s["w"].sum())(restored)
    assert float(y) == 4.0


def test_multiprocess_without_coordinator_raises():
    mm = MeshManager()
    import pytest
    with pytest.raises(ValueError, match="coordinator_address"):
        mm.initialize(num_processes=4, process_id=1)


def test_solo_rebuild_parks_and_restores_cpu_collectives(monkeypatch):
    """Shrinking to a solo world must reset a gloo/mpi CPU-collectives
    config (the backend would otherwise demand a distributed client that
    a 1-process world never creates), and growing back must RESTORE it —
    a regrown world without it would silently skip cross-host
    gradient averaging."""
    def read_impl():
        return jax.config.jax_cpu_collectives_implementation
    orig = read_impl()
    # jax.distributed.initialize would need real peers; the regrow path
    # under test is the config handling AROUND it
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: None)
    mm = MeshManager(coordinator_address="127.0.0.1:1")
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        mm.initialize(num_processes=1, process_id=0)
        assert read_impl() is None  # parked: solo backend builds clean
        assert mm._saved_cpu_collectives == "gloo"
        mm.initialize(num_processes=2, process_id=0)
        assert read_impl() == "gloo"  # restored for the regrown world
        assert mm._saved_cpu_collectives is None
    finally:
        mm._initialized = False  # initialize() was monkeypatched
        jax.config.update("jax_cpu_collectives_implementation", orig)


def test_restore_with_explicit_shardings():
    mesh = mesh_lib.make_mesh()
    host = {"w": np.arange(16.0).reshape(16, 1)}
    sh = {"w": mesh_lib.data_sharding(mesh, 2)}
    out = restore_state(host, mesh, shardings=sh)
    assert len(out["w"].sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.arange(16.0).reshape(16, 1))


def test_module_fit_invokes_mesh_manager_on_membership_change():
    """Wiring test: Module.fit must route a membership change through the
    mesh manager (rebuild + recompile) before resharding data."""
    from dt_tpu import data, models
    from dt_tpu.training import Module

    calls = []

    class RecordingManager(MeshManager):
        def rebuild(self, state, num_processes, process_id,
                    coordinator_address=None):
            calls.append((num_processes, process_id))
            mesh = mesh_lib.make_mesh()
            return mesh, restore_state(snapshot_state(state), mesh)

    class FakeController:
        """num_workers flips 1 -> 2 at the epoch-1 barrier."""
        rank = 0
        num_workers = 1

        def membership_change_barrier(self, info):
            if info.get("EPOCH_BEGIN", 0) >= 1:
                FakeController.num_workers = 2

        def publish_snapshot(self, blob):
            pass

    from dt_tpu.parallel import kvstore as kvlib
    kv = kvlib.create("tpu_sync")
    kv.set_controller(FakeController())

    x = np.zeros((64, 4, 4, 1), np.float32)
    y = np.zeros(64, np.int32)

    def factory(parts, idx, bs):
        return data.NDArrayIter(x, y, batch_size=bs, num_parts=parts,
                                part_index=idx), None

    eit = data.ElasticDataIterator(factory, 32)  # per-worker 16, 8-divisible
    train, _ = eit.get_data_iterator(kv)
    mod = Module(models.create("mlp", num_classes=2, hidden=(4,)),
                 kvstore=kv, mesh_manager=RecordingManager())
    mod.fit(train, num_epoch=2, elastic_data_iterator=eit)
    assert calls == [(2, 0)]
    assert int(mod.state.step) > 0  # training continued after the rebuild
