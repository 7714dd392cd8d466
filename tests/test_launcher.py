"""Launcher unit + integration tests (role of reference
``test/test_run.py``: allocation math, hostfile parsing, config→env
plumbing, output capture, failure fan-in)."""

import json
import os
import subprocess
import sys

import pytest

from horovod_tpu.common import config as _config
from horovod_tpu.run.launcher import (allocate, build_parser,
                                      parse_host_spec, parse_hostfile)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_allocate_two_hosts():
    slots = allocate([("a", 2), ("b", 2)], 4)
    assert [s.rank for s in slots] == [0, 1, 2, 3]
    assert [s.hostname for s in slots] == ["a", "a", "b", "b"]
    assert [s.local_rank for s in slots] == [0, 1, 0, 1]
    assert [s.cross_rank for s in slots] == [0, 0, 1, 1]
    assert all(s.local_size == 2 and s.cross_size == 2 and s.size == 4
               for s in slots)


def test_allocate_partial_host():
    slots = allocate([("a", 4)], 3)
    assert len(slots) == 3
    assert all(s.local_size == 3 for s in slots)
    with pytest.raises(ValueError):
        allocate([("a", 2)], 4)


def test_parse_host_spec():
    assert parse_host_spec("h1:4,h2:2", 6) == [("h1", 4), ("h2", 2)]
    assert parse_host_spec(None, 3) == [("localhost", 3)]
    assert parse_host_spec("solo", 1) == [("solo", 1)]


def test_parse_hostfile(tmp_path):
    f = tmp_path / "hosts"
    f.write_text("nodeA slots=4  # gpu box\nnodeB slots=2\n\n")
    assert parse_hostfile(str(f)) == [("nodeA", 4), ("nodeB", 2)]


def test_cli_knobs_to_env():
    args = build_parser().parse_args(
        ["-np", "2", "--fusion-threshold-mb", "32",
         "--cycle-time-ms", "2.5", "--timeline-filename", "/tmp/t.json",
         "python", "x.py"])
    env: dict = {}
    _config.set_env_from_args(args, env)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
    assert env["HOROVOD_CYCLE_TIME"] == "2.5"
    assert env["HOROVOD_TIMELINE"] == "/tmp/t.json"


def test_config_file_round_trip(tmp_path, monkeypatch):
    cfg = {"tensor_fusion": {"threshold": 1234567},
           "stall_check": {"warning_time_seconds": 7}}
    path = tmp_path / "cfg.json"
    path.write_text(json.dumps(cfg))
    monkeypatch.delenv("HOROVOD_FUSION_THRESHOLD", raising=False)
    monkeypatch.delenv("HOROVOD_STALL_CHECK_TIME_SECONDS", raising=False)
    applied = _config.load_config_file(str(path))
    assert applied == {"fusion_threshold": 1234567,
                       "stall_warning_time": 7}
    assert _config.get("fusion_threshold") == 1234567
    monkeypatch.delenv("HOROVOD_FUSION_THRESHOLD", raising=False)
    monkeypatch.delenv("HOROVOD_STALL_CHECK_TIME_SECONDS", raising=False)


def test_remote_spawn_command_keeps_secret_off_argv(monkeypatch):
    """The ssh rank spawn (reference gloo_run.py:189) must export env
    inline but ship HOROVOD_SECRET_KEY via stdin only — anything on
    argv is world-readable through /proc.  Asserted against the real
    launch() path with Popen captured."""
    import io

    import horovod_tpu.run.launcher as L

    captured = {}

    class FakeProc:
        def __init__(self, argv, **kw):
            captured["argv"] = argv
            captured["stdin_is_pipe"] = kw.get("stdin") is not None
            self.stdin = io.BytesIO()
            self.stdin.close = lambda: captured.__setitem__(
                "stdin_data", self.stdin.getvalue())

        def wait(self):
            return 0

        def poll(self):
            return 0

    real_popen = subprocess.Popen

    def fake_popen(argv, **kw):
        if argv and argv[0] == "ssh":
            return FakeProc(argv, **kw)
        # non-ssh spawns (e.g. the KV store's build step) proceed for
        # real so the test exercises the KV-enabled launch path
        return real_popen(argv, **kw)

    monkeypatch.setattr(L.subprocess, "Popen", fake_popen)
    # reachability is test_preflight_*'s concern; here the host is fake
    monkeypatch.setattr(L, "preflight_hosts", lambda *a, **kw: None)
    rc = L.launch(1, ["python", "train.py"],
                  hosts="farawayhost:1", env=dict(os.environ))
    assert rc == 0
    joined = " ".join(captured["argv"])
    assert "sh -c" in joined                       # POSIX-shell wrapper
    assert "HOROVOD_RANK=0" in joined              # env exported inline
    assert "HOROVOD_GLOO_RENDEZVOUS_PORT=" in joined  # KV path active
    secret = captured.get("stdin_data", b"").decode().strip()
    assert secret and len(secret) >= 32            # secret via stdin...
    assert secret not in joined                    # ...and never argv


@pytest.mark.slow  # tier-1 runtime trim: heaviest cold-compile/subprocess tests;
# ci.sh's full (unfiltered) suite still runs them
def test_check_build_flag():
    """hvdrun --check-build (reference runner.py:115-150) reports the
    available frontends/transports and exits 0 without -np."""
    import importlib.util

    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "HOROVOD_PLATFORM": "cpu"})
    rc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "--check-build"],
        env=env, capture_output=True, text=True, timeout=180)
    assert rc.returncode == 0, rc.stderr
    assert "Available Frontends" in rc.stdout
    assert "[X] JAX" in rc.stdout
    torch_mark = "X" if importlib.util.find_spec("torch") else " "
    assert f"[{torch_mark}] PyTorch" in rc.stdout
    # no -np and no --check-build is still an error
    rc2 = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run"],
        env=env, capture_output=True, text=True, timeout=60)
    assert rc2.returncode == 2


@pytest.mark.multiprocess
def test_hvdrun_end_to_end(tmp_path):
    out_dir = tmp_path / "out"
    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "HOROVOD_PLATFORM": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    rc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
         "--output-filename", str(out_dir), "--",
         sys.executable, "-c",
         "import horovod_tpu as hvd, jax.numpy as jnp\n"
         "hvd.init()\n"
         "print('hello from', hvd.rank())\n"
         "hvd.shutdown()\n"],
        env=env, capture_output=True, text=True, timeout=180)
    assert rc.returncode == 0, rc.stderr
    for r in range(2):
        text = (out_dir / f"rank.{r}" / "stdout").read_text()
        assert f"hello from {r}" in text


@pytest.mark.multiprocess
@pytest.mark.parametrize("explicit", [False, True])
def test_output_filename_leaves_every_ring_beside_the_logs(tmp_path,
                                                           explicit):
    """``--output-filename <dir>`` implies ``HOROVOD_FLIGHT_DIR=<dir>/
    flight``: a clean ``hvd.shutdown()`` dumps each rank's ring there
    and the launcher its own, each with the process's start and, in a
    rank, a closed ``hvd_init``.  A flight directory the environment
    names still wins."""
    from horovod_tpu.trace.merge import load_dumps

    out_dir = tmp_path / "logs"
    flight_dir = tmp_path / "elsewhere" if explicit else out_dir / "flight"
    env = dict(os.environ)
    env.pop("HOROVOD_FLIGHT_DIR", None)
    env.update({"PYTHONPATH": REPO, "HOROVOD_PLATFORM": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    if explicit:
        env["HOROVOD_FLIGHT_DIR"] = str(flight_dir)
    rc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
         "--output-filename", str(out_dir), "--",
         sys.executable, "-c",
         "import horovod_tpu as hvd\n"
         "hvd.init()\n"
         "hvd.shutdown()\n"],
        env=env, capture_output=True, text=True, timeout=180)
    assert rc.returncode == 0, rc.stderr
    assert (out_dir / "flight").exists() == (not explicit)
    dumps = load_dumps(str(flight_dir))
    names = sorted(os.path.basename(d.path) for d in dumps)
    assert [n.rsplit("-p", 1)[0] for n in names] == [
        "flight-r0-g0", "flight-r0-g1", "flight-r1-g1"], names
    launcher, *ranks = dumps        # sorted by (generation, rank)
    assert launcher.meta["reason"] == "launcher wrap-up"
    for d in dumps:
        (process,) = d.of_kind("hvd_process")
        assert process["started_wall"] <= process["wall"]
        assert [e["ph"] for e in d.of_kind("hvd_import")] == ["B", "E"]
    # the launcher's ring: its spans, closed, one spawn a rank
    assert [e["ph"] for e in launcher.of_kind("hvd_launch")] == ["B", "E"]
    spawned = [e for e in launcher.of_kind("hvd_launch.spawn")
               if e["ph"] == "E"]
    assert [e["rank"] for e in spawned] == [0, 1]
    assert {e["pid"] for e in spawned} == {d.meta["pid"] for d in ranks}
    for kind in ("preflight", "kv_server", "wait"):
        assert [e["ph"] for e in launcher.of_kind(f"hvd_launch.{kind}")] \
            == ["B", "E"]
    assert not launcher.of_kind("hvd_init")
    for r, d in enumerate(ranks):
        assert d.rank == r and d.meta["reason"] == "shutdown"
        assert [e["ph"] for e in d.of_kind("hvd_init")] == ["B", "E"]
        # a rank started after its launcher, and inside its spawn span
        assert d.of_kind("hvd_process")[0]["started_wall"] >= \
            launcher.of_kind("hvd_process")[0]["started_wall"]
        assert d.of_kind("shutdown")


@pytest.mark.multiprocess
def test_hvdrun_failing_rank_kills_job(tmp_path):
    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "HOROVOD_PLATFORM": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    rc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2", "--",
         sys.executable, "-c",
         "import os, sys, time\n"
         "rank = int(os.environ['HOROVOD_RANK'])\n"
         "sys.exit(3 if rank == 1 else 0)\n"],
        env=env, capture_output=True, text=True, timeout=120)
    assert rc.returncode == 1
    assert "ranks failed" in rc.stderr


@pytest.mark.multiprocess
def test_run_function_mode():
    def fn(x):
        import horovod_tpu as hvd
        import jax.numpy as jnp

        out = hvd.allreduce(jnp.ones(2) * (hvd.rank() + x), op=hvd.Sum)
        return float(out[0])

    import horovod_tpu.run as hr

    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "HOROVOD_PLATFORM": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    results = hr.run(fn, args=(1.0,), np=2, env=env)
    assert results == [3.0, 3.0], results


@pytest.mark.multiprocess
def test_run_function_results_over_kv_without_shared_fs():
    """Reference ``run/runner.py:631-657``: run-func results return
    through the rendezvous KV server, not a shared filesystem.
    HOROVOD_RUNFUNC_NO_SHARED_FS=1 makes ranks ignore the launcher's
    tempdir entirely (as a remote host would): the function must arrive
    via the KV store and every result must come back the same way."""
    pytest.importorskip("horovod_tpu.runtime.kvstore")
    from horovod_tpu.runtime.kvstore import KVStoreServer

    try:
        KVStoreServer(secret=b"").stop()
    except Exception as exc:
        pytest.skip(f"native KV store unavailable: {exc}")

    def fn(base):
        import horovod_tpu as hvd

        return base + hvd.rank()

    import horovod_tpu.run as hr

    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "HOROVOD_PLATFORM": "cpu",
                "HOROVOD_RUNFUNC_NO_SHARED_FS": "1",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    results = hr.run(fn, args=(100,), np=2, env=env)
    assert results == [100, 101], results


def test_preflight_unreachable_host_fails_fast_with_name():
    """Reference ``run/runner.py:61-112``: an unreachable host must fail
    the job within --start-timeout, naming the host — not hang until the
    negotiation timeout."""
    import time

    from horovod_tpu.run import launcher as L

    t0 = time.monotonic()
    with pytest.raises(L.HostUnreachableError, match="bogus-host-zz"):
        L.launch(2, ["true"], hosts="bogus-host-zz.invalid:2",
                 start_timeout=5, env=dict(os.environ))
    assert time.monotonic() - t0 < 30


def test_console_output_rank_prefixing():
    """Console mode (no --output-filename) forwards each rank's lines
    prefixed ``[rank]<stdout>:`` (reference safe_shell_exec.py:61-94),
    so interleaved multi-rank output stays attributable."""
    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "HOROVOD_PLATFORM": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    rc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2", "--",
         sys.executable, "-c",
         "import os, sys\n"
         "print('hello from', os.environ['HOROVOD_RANK'])\n"
         "print('oops', file=sys.stderr)\n"],
        env=env, capture_output=True, text=True, timeout=120)
    assert rc.returncode == 0, (rc.stdout, rc.stderr)
    assert "[0]<stdout>:hello from 0" in rc.stdout
    assert "[1]<stdout>:hello from 1" in rc.stdout
    assert "[0]<stderr>:oops" in rc.stderr
    assert "[1]<stderr>:oops" in rc.stderr


def test_console_prefix_timestamp_flag():
    """--prefix-output-with-timestamp (reference runner.py flag) adds a
    timestamp before the [rank]<stream>: context."""
    import re

    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "HOROVOD_PLATFORM": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    rc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "1",
         "--prefix-output-with-timestamp", "--",
         sys.executable, "-c", "print('tick')"],
        env=env, capture_output=True, text=True, timeout=120)
    assert rc.returncode == 0, (rc.stdout, rc.stderr)
    # e.g. "Fri Jul 31 23:40:02 2026 [0]<stdout>:tick"
    assert re.search(r"\w{3} \w{3} +\d+ [\d:]{8} \d{4} \[0\]<stdout>:tick",
                     rc.stdout), rc.stdout


def test_preflight_skips_local_hosts():
    from horovod_tpu.run import launcher as L

    # must not require an ssh roundtrip for localhost-only jobs
    L.preflight_hosts([("localhost", 2), ("127.0.0.1", 1)], 5)


def test_pod_detect_tpu_worker_env():
    from horovod_tpu.run import pod

    env = {"TPU_WORKER_ID": "2",
           "TPU_WORKER_HOSTNAMES": "w0.local, w1.local, w2.local"}
    info = pod.detect(env)
    assert info is not None
    assert (info.rank, info.size) == (2, 3)
    assert info.coordinator == "w0.local:8476"
    assert info.source == "tpu_worker"


def test_pod_detect_megascale_and_none():
    from horovod_tpu.run import pod

    info = pod.detect({"MEGASCALE_SLICE_ID": "1",
                       "MEGASCALE_NUM_SLICES": "4",
                       "MEGASCALE_COORDINATOR_ADDRESS": "coord.svc"})
    assert info is not None and info.auto
    assert info.source == "megascale"
    # multislice workers also carry slice-local TPU_WORKER_* vars;
    # megascale must win or each slice forms its own world
    both = pod.detect({"MEGASCALE_NUM_SLICES": "2",
                       "MEGASCALE_COORDINATOR_ADDRESS": "c",
                       "TPU_WORKER_ID": "0",
                       "TPU_WORKER_HOSTNAMES": "a,b"})
    assert both is not None and both.auto
    assert pod.detect({}) is None
    # malformed worker id out of range -> not detected
    assert pod.detect({"TPU_WORKER_ID": "9",
                       "TPU_WORKER_HOSTNAMES": "a,b"}) is None


def test_pod_detect_malformed_env_is_not_detected():
    from horovod_tpu.run import pod

    assert pod.detect({"TPU_WORKER_ID": "",
                       "TPU_WORKER_HOSTNAMES": "a,b"}) is None
    # megascale ids aren't parsed here (auto mode) so malformed ids
    # still defer to jax's resolver
    assert pod.detect({"MEGASCALE_NUM_SLICES": "4",
                       "MEGASCALE_COORDINATOR_ADDRESS": "c"}).auto


def test_allocate_heterogeneous_sets_flag():
    """{3,2,1} ranks over 3 hosts is heterogeneous; equal slots is not.
    One rank's local_size*cross_size==size test would wrongly pass on
    the 2-rank node, so the launcher must export the global answer."""
    from horovod_tpu.run.launcher import allocate, _rank_env

    slots = allocate([("a", 3), ("b", 2), ("c", 1)], 6)
    assert all(not s.homogeneous for s in slots)
    cpu = {"HOROVOD_PLATFORM": "cpu"}
    env = _rank_env(slots[3], "localhost:1", "", 0, cpu)
    assert env["HOROVOD_IS_HOMOGENEOUS"] == "0"

    slots = allocate([("a", 2), ("b", 2)], 4)
    assert all(s.homogeneous for s in slots)
    assert _rank_env(slots[0], "localhost:1", "", 0,
                     cpu)["HOROVOD_IS_HOMOGENEOUS"] == "1"
