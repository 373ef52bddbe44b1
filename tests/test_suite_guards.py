"""The suite's own guards: the limit ``conftest.py`` sets on every test, and
``spawned.py``'s environment and deadline for the processes tests start."""

import os
import shutil
import signal
import subprocess
import sys
import time

import pytest

import spawned

PAST_THE_LIMIT = '''
import time

import conftest

conftest.TEST_LIMIT_S = 1


def test_sleeps_past_the_limit():
    time.sleep(60)


def test_the_next_one_runs():
    pass
'''


@pytest.mark.parametrize("flags", [[], ["-p", "xdist", "-n", "1"]],
                         ids=["one-process", "xdist-worker"])
def test_a_test_past_its_limit_fails_alone_and_is_named(tmp_path, flags):
    """The suite's ``conftest.py`` over two tests, its limit set to 1 s: the
    one that sleeps fails within seconds with its name and the line it was
    at in the report, and the one after it passes; under xdist's worker as
    in one process."""
    shutil.copy(os.path.join(spawned.HERE, "conftest.py"), tmp_path)
    (tmp_path / "test_two.py").write_text(PAST_THE_LIMIT)
    began = time.monotonic()
    r = spawned.run([sys.executable, "-m", "pytest", str(tmp_path), "-q",
                     "-p", "no:cacheprovider", *flags], seconds=120,
                    cwd=str(tmp_path), PYTHONPATH=spawned.HERE)
    assert time.monotonic() - began < 50, "the sleep was not cut"
    assert r.returncode == 1, r.stdout[-2000:] + r.stderr[-2000:]
    assert "1 failed, 1 passed" in r.stdout, r.stdout[-2000:]
    assert "test_two.py::test_sleeps_past_the_limit ran past its limit " \
        "of 1 s" in r.stdout
    assert "in test_sleeps_past_the_limit" in r.stdout   # the stack's line


def test_a_childs_environment(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    monkeypatch.setenv("JAX_ENABLE_COMPILATION_CACHE", "1")
    env = spawned.child_env(ELASTIC_TRAINING_ENABLED=1, EPOCH_BEGIN=3)
    assert "XLA_FLAGS" not in env
    assert env["JAX_ENABLE_COMPILATION_CACHE"] == "0"
    assert (env["ELASTIC_TRAINING_ENABLED"], env["EPOCH_BEGIN"]) == ("1", "3")
    assert env["JAX_PLATFORMS"] == "cpu" and env["PATH"] == os.environ["PATH"]
    r = spawned.run([sys.executable, "-c", "import os; print("
                     "os.environ.get('XLA_FLAGS'), os.environ['WHO'])"],
                    WHO="child")
    assert r.stdout.split() == ["None", "child"]


def test_a_spent_deadline_raises_at_once_and_the_children_are_killed(
        tmp_path, capsys):
    script = tmp_path / "sleeper.py"
    script.write_text("import time\nprint('asleep', flush=True)\n"
                      "time.sleep(600)\n")
    with spawned.Workers(seconds=0) as workers:
        procs = [workers.spawn(str(script)) for _ in range(2)]
        began = time.monotonic()
        for proc in procs:
            with pytest.raises(subprocess.TimeoutExpired):
                workers.wait(proc)
        with pytest.raises(AssertionError, match="never"):
            workers.until(lambda: False, "never")
        assert time.monotonic() - began < 1
        assert [proc.poll() for proc in procs] == [None, None]
    assert [proc.returncode for proc in procs] == [-signal.SIGKILL] * 2
    assert capsys.readouterr().out.count("its last output:") == 2


def test_a_wait_is_for_what_a_worker_started_into_its_output(tmp_path):
    """``own_session``: the worker exits at once and leaves a child that
    writes to the same output a second later; the wait ends after it."""
    script = tmp_path / "leaver.py"
    script.write_text(
        "import subprocess, sys\n"
        "subprocess.Popen([sys.executable, '-c', 'import time; "
        "time.sleep(1); print(\"late\", flush=True)'])\n"
        "print('early', flush=True)\n")
    with spawned.Workers(seconds=60, own_session=True) as workers:
        proc = workers.spawn(str(script))
        workers.finish(proc, "leaver")
        assert workers.output(proc).split() == ["early", "late"]
