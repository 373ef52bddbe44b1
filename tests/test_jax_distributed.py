"""2-process ``jax.distributed`` smoke: the multi-host data plane.

Executes MeshManager's ``jax.distributed`` branch for real (VERDICT
round-1 item 3): two OS processes, one virtual CPU device each, forming a
2-device global mesh; cross-process gradient allreduce through the jit
step; batches assembled with ``jax.make_array_from_process_local_data``
(``Module._place`` multi-host path); then the full rebuild dance — same
size with a new coordinator, and shrink-to-one after a worker leaves.

Reference analog: ``tests/nightly/dist_sync_kvstore.py`` (multi-process
worker sync) + ps-lite rendezvous/resize (``van.cc:95-185``,
``postoffice.cc:71-187``).

Workers run in SUBPROCESSES (not pytest's process): jax.distributed can
only be initialized in a process whose backend isn't already up, and the
suite's conftest initializes the 8-device CPU backend.
"""

import socket

import numpy as np
import pytest

import spawned


@pytest.fixture
def workers():
    """These worlds take longer to form than the suite's other workers: one
    deadline of 540 s a test.  Each worker leads a process group that goes
    with it: phase 4's survivors of the four-process test start a restarted
    self detached and ``os._exit``, and such a grandchild left behind would
    hold the next run's ports and gloo rendezvous."""
    with spawned.Workers(seconds=540, own_session=True) as started:
        yield started


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_world(workers, script, tmp_path, ids, ports):
    """One worker a process id (each sets its own count of devices), all
    waited for under the one deadline and held to exit code 0; their
    outputs by id."""
    procs = {pid: workers.spawn(script, tmp_path, pid, *ports,
                                PYTHONPATH=spawned.REPO) for pid in ids}
    for pid, p in procs.items():
        workers.finish(p, f"rank {pid}")
    return {pid: workers.output(p) for pid, p in procs.items()}


def test_two_process_world_fit_rebuild_shrink(tmp_path, workers):
    outs = _run_world(workers, "jaxdist_worker.py", tmp_path, (0, 1),
                      [_free_port(), _free_port()])

    # param sync: after every multi-process epoch, both ranks hold
    # IDENTICAL params (the allreduce really crossed processes)
    for tag in ("epoch1", "epoch2"):
        a = np.load(tmp_path / f"params_{tag}_r0.npy")
        b = np.load(tmp_path / f"params_{tag}_r1.npy")
        np.testing.assert_array_equal(a, b, err_msg=f"{tag} diverged")
    # training actually moved the params each epoch
    e1 = np.load(tmp_path / "params_epoch1_r0.npy")
    e2 = np.load(tmp_path / "params_epoch2_r0.npy")
    e3 = np.load(tmp_path / "params_epoch3_r0.npy")
    assert np.abs(e2 - e1).max() > 1e-6
    assert np.abs(e3 - e2).max() > 1e-6
    assert "solo world" in outs[0]


def test_two_process_multidevice_zero_dp_and_shrink(tmp_path, workers):
    """2 processes x 4 devices (VERDICT r3 item 4): 8-device global DP
    mesh with ZeRO-1 opt-state sharding (cross-process reduce-scatter /
    all-gather), then an elastic membership change rebuilding to a
    1-process x 4-device world."""
    outs = _run_world(workers, "jaxdist_worker_md.py", tmp_path, (0, 1),
                      [_free_port()])
    # both ranks hold identical params after the 8-device epoch
    a = np.load(tmp_path / "mdparams_epoch1_r0.npy")
    b = np.load(tmp_path / "mdparams_epoch1_r1.npy")
    np.testing.assert_array_equal(a, b, err_msg="8-device DP diverged")
    # the post-shrink epoch kept training
    e2 = np.load(tmp_path / "mdparams_epoch2_r0.npy")
    assert np.abs(e2 - a).max() > 1e-6
    assert "8-device ZeRO DP" in outs[0] and "4-device world" in outs[0]


def test_four_process_full_elastic_lifecycle(tmp_path, workers):
    """4 processes x 2 devices with ZeRO-1 + FSDP, driven through the
    full elastic lifecycle in ONE job: remove (rank 3 departs) -> add (a
    new process bootstraps from the host snapshot) -> coordinator kill
    (rank 0 exits without the shutdown handshake; survivors re-form
    under a new coordinator).  VERDICT r4 next 6; reference analog ran a
    7-worker local tracker (ci/docker/runtime_functions.sh:907-915)."""
    outs = _run_world(workers, "jaxdist_worker_4p.py", tmp_path,
                      (0, 1, 2, 3, 4), [_free_port() for _ in range(4)])

    def load(tag, wid):
        return np.load(tmp_path / f"p4_{tag}_w{wid}.npy")

    # epoch 1: all four initial ranks identical (8-device FSDP DP)
    e1 = [load("epoch1", w) for w in (0, 1, 2, 3)]
    for b in e1[1:]:
        np.testing.assert_array_equal(e1[0], b, "epoch1 diverged")
    # epoch 2: the three survivors identical
    e2 = [load("epoch2", w) for w in (0, 1, 2)]
    for b in e2[1:]:
        np.testing.assert_array_equal(e2[0], b, "epoch2 diverged")
    # epoch 3: survivors + joiner identical (snapshot bootstrap worked)
    e3 = [load("epoch3", w) for w in (0, 1, 2, 4)]
    for b in e3[1:]:
        np.testing.assert_array_equal(e3[0], b, "epoch3 diverged")
    # epoch 4: post-coordinator-kill world identical and still training
    e4 = [load("epoch4", w) for w in (1, 2, 4)]
    for b in e4[1:]:
        np.testing.assert_array_equal(e4[0], b, "epoch4 diverged")
    for a, b in ((e1[0], e2[0]), (e2[0], e3[0]), (e3[0], e4[0])):
        assert np.abs(b - a).max() > 1e-6, "params stopped moving"
    assert "joiner: bootstrapped from snapshot" in outs[4]
    assert "coordinator dying" in outs[0]
    assert "new coordinator" in outs[1]
