"""Every process the tests start: its environment, its start, and the one
deadline a test waits on.  The worker scripts (``elastic_worker.py``,
``async_worker.py``, ``jaxdist_worker*.py``) live beside this module; no test
of its own."""

import os
import signal
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def child_env(**env):
    """The suite's environment for a child: without ``XLA_FLAGS`` (the eight
    virtual devices are the suite's own; a worker sets the count it needs),
    the persistent compilation cache off as ``conftest.py`` sets it, and
    ``env`` on top."""
    out = dict(os.environ)
    out.pop("XLA_FLAGS", None)
    out["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    out.update({name: str(value) for name, value in env.items()})
    return out


def run(argv, seconds=300, cwd=None, **env):
    """One process run to its end under ``child_env(**env)``, its output
    captured as text; ``subprocess.TimeoutExpired`` after ``seconds``."""
    return subprocess.run(argv, capture_output=True, text=True,
                          timeout=seconds, cwd=cwd, env=child_env(**env))


def _group_alive(pgid):
    try:
        os.killpg(pgid, 0)
        return True
    except ProcessLookupError:
        return False


class Workers:
    """The worker processes of one test.  Every wait takes what is left of
    ONE deadline, set when the first process starts: a stuck worker costs
    the test ``seconds`` once and not once a process.  ``close`` (the end of
    the ``with`` block) kills whatever still runs and prints its last
    output.  ``own_session``: each worker leads a process group, for workers
    that start detached children into their own output: a wait is for the
    whole group, and the whole group is killed."""

    def __init__(self, seconds=300, own_session=False):
        self.seconds = seconds
        self.own_session = own_session
        self.deadline = None
        self._started = []      # (process, the file its output goes to)

    def spawn(self, script, *args, **env):
        """``python tests/<script> args...`` under ``child_env(**env)``,
        stdout and stderr into one unnamed file (no pipe to fill)."""
        if self.deadline is None:
            self.deadline = time.monotonic() + self.seconds
        out = tempfile.TemporaryFile()
        proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, script), *map(str, args)],
            env=child_env(**env), stdout=out, stderr=subprocess.STDOUT,
            start_new_session=self.own_session)
        self._started.append((proc, out))
        return proc

    def left(self):
        """Seconds to the deadline, 0 once it is spent."""
        return max(0.0, self.deadline - time.monotonic())

    def wait(self, proc):
        """The exit code; ``subprocess.TimeoutExpired`` at the deadline (at
        once where it is spent already)."""
        rc = proc.wait(timeout=self.left())
        if self.own_session:
            self.until(lambda: not _group_alive(proc.pid),
                       f"what {proc.args[1:]} started still runs")
        return rc

    def finish(self, proc, name):
        """Wait, and hold the worker to exit code 0."""
        rc = self.wait(proc)
        assert rc == 0, f"{name} rc={rc}:\n{self.output(proc)[-3000:]}"

    def until(self, condition, what):
        """Poll ``condition()`` to the deadline."""
        while not condition():
            assert self.left() > 0, what
            time.sleep(0.1)

    def output(self, proc):
        """What ``proc`` has written so far."""
        out = next(o for p, o in self._started if p is proc)
        out.seek(0)
        return out.read().decode(errors="replace")

    def close(self):
        for proc, out in self._started:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
                print(f"killed {proc.args[1:]}, its last output:\n"
                      f"{self.output(proc)[-2000:]}")
            if self.own_session:
                try:    # detached grandchildren share the worker's group
                    os.killpg(proc.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass    # the whole group is gone: the healthy case
            out.close()
        self._started = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
