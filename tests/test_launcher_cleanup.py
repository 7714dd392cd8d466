"""Launcher process-group hygiene.

Reference ``run/common/util/safe_shell_exec.py:1-120``: children run in
their own process group and job termination kills the whole group, so
an aborted launcher can never orphan ranks.  Here the same guarantees
come from ``setpgid`` + ``killpg`` + ``PR_SET_PDEATHSIG`` in
``run/launcher.py``.
"""
import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

pytestmark = pytest.mark.skipif(
    not sys.platform.startswith("linux"),
    reason="process-group/PDEATHSIG semantics are Linux-specific")


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


def _spawn_job(tmp_path, np_=2, sleep_s=120, prelude=""):
    """hvdrun -np N over a sleeper that records its PID, then wait for
    all rank PID files to appear.  ``prelude`` lines run first in each
    rank (e.g. signal-disposition setup)."""
    script = tmp_path / "sleeper.py"
    script.write_text(textwrap.dedent(f"""\
        import os, signal, time
        {prelude}
        rank = os.environ["HOROVOD_RANK"]
        # written whole, then renamed: the test polls for the name and
        # must never read it empty
        part = os.path.join({str(tmp_path)!r}, "part." + rank)
        with open(part, "w") as f:
            f.write(str(os.getpid()))
        os.replace(part, os.path.join({str(tmp_path)!r}, "pid." + rank))
        time.sleep({sleep_s})
    """))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    launcher = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.run.launcher",
         "-np", str(np_), "--", sys.executable, str(script)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    deadline = time.time() + 60
    pids = []
    while time.time() < deadline:
        files = sorted(tmp_path.glob("pid.*"))
        if len(files) == np_:
            pids = [int(f.read_text()) for f in files]
            break
        if launcher.poll() is not None:
            pytest.fail(f"launcher exited early rc={launcher.returncode}")
        time.sleep(0.2)
    assert len(pids) == np_, "ranks never started"
    return launcher, pids


def _wait_dead(pids, timeout=20.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        alive = [p for p in pids if _alive(p)]
        if not alive:
            return []
        time.sleep(0.3)
    return [p for p in pids if _alive(p)]


def test_sigkill_launcher_reaps_ranks(tmp_path):
    """SIGKILL the launcher mid-job: PDEATHSIG must reap every rank.

    This is the round-3 orphan repro (two example ranks survived an
    aborted pytest run for over an hour)."""
    launcher, pids = _spawn_job(tmp_path)
    launcher.kill()  # SIGKILL: launcher gets no chance to clean up
    launcher.wait()
    leftover = _wait_dead(pids)
    for p in leftover:  # don't leak on failure
        os.kill(p, signal.SIGKILL)
    assert not leftover, f"orphaned ranks after launcher SIGKILL: {leftover}"


def test_sigkill_launcher_reaps_term_immune_ranks(tmp_path):
    """The round-4/5 orphan repro: ranks whose SIGTERM disposition is
    useless (libraries register Python handlers that a main thread
    parked in a C++ futex never runs — simulated here with SIG_IGN)
    survived a launcher kill -9 for hours at 2 GB RSS each.  PDEATHSIG
    is SIGKILL precisely so this class dies with the launcher."""
    launcher, pids = _spawn_job(
        tmp_path, prelude="signal.signal(signal.SIGTERM, signal.SIG_IGN)")
    launcher.kill()
    launcher.wait()
    leftover = _wait_dead(pids)
    for p in leftover:  # don't leak on failure
        os.kill(p, signal.SIGKILL)
    assert not leftover, (
        f"TERM-immune ranks survived launcher SIGKILL: {leftover}")


def test_rank_grandchildren_die_with_job(tmp_path):
    """A rank that forks a helper: killing the job must kill the whole
    process group, not just the directly-tracked PID (killpg path)."""
    script = tmp_path / "forker.py"
    script.write_text(textwrap.dedent(f"""\
        import os, subprocess, sys, time
        rank = os.environ["HOROVOD_RANK"]
        child = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(120)"])
        with open(os.path.join({str(tmp_path)!r}, "pid." + rank), "w") as f:
            f.write(str(child.pid))
        if rank == "1":
            time.sleep(1.0)
            sys.exit(3)   # rank failure -> fail-fast group TERM
        time.sleep(120)
    """))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    launcher = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.run.launcher",
         "-np", "2", "--", sys.executable, str(script)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        rc = launcher.wait(timeout=90)
    except subprocess.TimeoutExpired:
        launcher.kill()
        pytest.fail("launcher hung after rank failure")
    assert rc == 1  # job reported the failed rank
    pids = [int(f.read_text()) for f in sorted(tmp_path.glob("pid.*"))]
    assert len(pids) == 2
    leftover = _wait_dead(pids, timeout=10.0)
    for p in leftover:
        os.kill(p, signal.SIGKILL)
    assert not leftover, f"grandchildren survived fail-fast: {leftover}"
