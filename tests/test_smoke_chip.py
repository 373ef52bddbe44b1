"""chip_smoke.py rehearsed off the chip: the stage functions at toy size on
the CPU mesh, and the script's refusal of anything but a TPU."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_stage_a_toy_on_cpu_mesh():
    """The trainer stage through examples/common.py -> Module.fit on all 8
    virtual devices: its own checks (steps, finite metrics, replicas, batch
    shards, one train_step compile) pass at toy size."""
    out = chip_smoke.stage_a("toy")
    assert out["steps"] == 3 and out["train_step_compiles"] == 1
    assert out["model"] == "resnet20" and out["batch_per_chip"] == 2
    assert not out["donated"]  # Module donates off the CPU only


def test_stage_b_toy_in_interpret_mode():
    """The kernel stage's plumbing (cases, tolerances, the flash LM through
    Module.fit) with the Pallas interpreter standing in for Mosaic."""
    out = chip_smoke.stage_b("toy", interpret=True)
    assert all(k["ok"] for k in out["kernels"]) and len(out["kernels"]) == 4
    assert out["lm_steps"] == 2


def test_stage_c_toy_worker_joins_and_ends_bit_identical():
    """The elastic quick start under the launcher: one base worker, one that
    joins at an epoch boundary, same final state digest."""
    out = chip_smoke.stage_c("toy", chips=2)
    assert out["workers"] == ["worker-0", "worker-1"]
    assert out["joined_mid_run"] == "worker-1"
    assert out["worker_devices"] == ["cpu (cpu)"]


def test_script_refuses_a_cpu():
    """``python chip_smoke.py`` with no accelerator: non-zero exit, the
    platform it found named, no result line."""
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "JAX found platform 'cpu'" in r.stderr
    for line in r.stdout.splitlines():
        assert not line.startswith("{"), line
    assert "platform=cpu" in r.stdout  # the child printed what it found


def test_launcher_refuses_more_workers_than_chips(monkeypatch):
    """A chip belongs to one process: asked for more local workers than the
    host has chips, the launcher fails at start with a message instead of
    leaving workers in backend init."""
    import pytest
    from dt_tpu.launcher import launch
    monkeypatch.setattr(launch, "_local_tpu_chips", lambda: 1)
    with pytest.raises(SystemExit, match="2 local workers .* 1 TPU chip"):
        launch.launch_local(2, [sys.executable, "-c", "pass"])


def test_chip_pool_gives_each_worker_its_own_chip(tmp_path):
    """Worker i's environment — and only its environment — carries the
    runtime's visibility variables for one chip; an exited worker's chip is
    handed out again, and a full pool refuses."""
    import time
    import pytest
    from dt_tpu.launcher.launch import _ChipPool
    pool = _ChipPool(2)
    started = []

    def start(host, hold_s):
        show = ("import os, sys, time; "
                "open(sys.argv[1], 'w').write(os.environ['TPU_VISIBLE_CHIPS']);"
                " time.sleep(float(sys.argv[2]))")
        started.append(pool.popen(
            host, [sys.executable, "-c", show, str(tmp_path / host),
                   str(hold_s)], dict(os.environ)))
        return started[-1]

    def chip_of(host):
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if (tmp_path / host).exists() and (tmp_path / host).read_text():
                return (tmp_path / host).read_text()
            time.sleep(0.05)
        raise AssertionError(f"{host} never reported its chip")

    try:
        w0 = start("w0", 0)
        start("w1", 120)
        assert (chip_of("w0"), chip_of("w1")) == ("0", "1")
        assert w0.wait(timeout=30) == 0  # w0 exits: chip 0 is free again
        start("w2", 120)
        assert chip_of("w2") == "0"
        with pytest.raises(RuntimeError, match="no free TPU chip"):
            start("w3", 0)
        assert "TPU_VISIBLE_CHIPS" not in os.environ
    finally:
        for p in started:
            p.kill()
            p.wait()
