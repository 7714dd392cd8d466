"""What the chip bring-up (PR 21) made true, held on the CPU.

The chip itself is reached only through ``chip_smoke.py`` on a TPU
machine; these tests pin the parts of that path a CPU can check: where
the compile cache goes, that the chip script refuses to pass without a
TPU, how the launcher hands each local rank its own chip, that native
artifacts are named by their source, and that a kernel which cannot
run as a kernel says so.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

_CACHE_SNIPPET = (
    "import os, json, jax\n"
    "from horovod_tpu.common.platform import ensure_compile_cache\n"
    "before = os.environ.get('JAX_COMPILATION_CACHE_DIR')\n"
    "path = ensure_compile_cache()\n"
    "print(json.dumps({'path': path, 'before': before,\n"
    "    'env': os.environ.get('JAX_COMPILATION_CACHE_DIR'),\n"
    "    'jax': jax.config.jax_compilation_cache_dir}))\n")


def _cache_probe(tmp_path, env_value):
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    r = subprocess.run([sys.executable, "-c", _CACHE_SNIPPET], env=env,
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_set_from_outside_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it, the program sets no
    other, and no directory appears in the checkout's name."""
    outside = str(tmp_path / "elsewhere")
    got = _cache_probe(tmp_path, outside)
    assert got == {"path": outside, "before": outside, "env": outside,
                   "jax": outside}
    assert not os.path.exists(os.path.join(str(tmp_path), ".jax_cache"))


def test_compile_cache_default_is_fixed_inside_the_checkout(tmp_path):
    """Unset: <checkout>/.jax_cache, resolved from the package's own
    location — the same from any working directory and in any process,
    exported so spawned ranks inherit it, and given to a jax that was
    imported before the variable existed."""
    want = os.path.join(REPO, ".jax_cache")
    other = tmp_path / "other_cwd"
    other.mkdir()
    a = _cache_probe(tmp_path, None)
    b = _cache_probe(other, None)
    assert a == b == {"path": want, "before": None, "env": want,
                      "jax": want}
    for banned in ("/tmp", str(os.getpid())):
        assert banned not in want.replace(REPO, "")


def test_compile_cache_key_holds_the_version_of_the_names(monkeypatch):
    """The ``hvd_*`` scopes are metadata, which JAX keeps out of the
    cache key: without their version in it the cache serves an
    executable compiled from a source with other names, and a trace
    reads those (PR 23: the parent's executables gave no scope at all).
    With another version the same program has another key."""
    import jax
    import numpy as np
    from jax._src import cache_key, compiler

    from horovod_tpu.common import platform

    platform.ensure_compile_cache()
    assert cache_key.custom_hook() == platform.NAMES_VERSION

    module = jax.jit(lambda x: x + 1).lower(1.0).compiler_ir("stablehlo")
    devices = np.array(jax.devices()[:1])

    def key() -> str:
        return cache_key.get(module, devices,
                             compiler.get_compile_options(1, 1),
                             devices[0].client)

    first = key()
    assert key() == first
    monkeypatch.setattr(platform, "NAMES_VERSION", "hvd-names-other")
    assert key() != first


def test_only_one_function_names_a_compile_cache_path():
    """The acceptance grep: nothing under the package, chip_smoke.py
    or examples/ sets a compile cache path except
    common/platform.ensure_compile_cache."""
    hits = []
    roots = [os.path.join(REPO, "horovod_tpu"),
             os.path.join(REPO, "examples")]
    files = [SMOKE]
    for root in roots:
        for d, _, names in os.walk(root):
            files += [os.path.join(d, n) for n in names
                      if n.endswith(".py")]
    for path in files:
        with open(path) as f:
            for i, line in enumerate(f, 1):
                if any(w in line for w in (
                        "jax_compilation_cache_dir", "mkdtemp",
                        "gettempdir", "/tmp/horovod_tpu")):
                    hits.append(f"{os.path.relpath(path, REPO)}:{i}")
    assert hits and all(
        h.startswith("horovod_tpu/common/platform.py:") for h in hits), hits


@pytest.mark.parametrize("pattern", [
    r"bench\.py|BENCH_[A-Z]",
    r"ATTN_BLOCK|ATTN_PALLAS_BWD|ATTN_XLA_SCORE|attn_block_|attn_pallas_bwd"
    r"|attn_xla_score|attn-block|attn-pallas-bwd|attn-xla-score"
    r"|flash_block_step",
], ids=["the_pre_harness_bench", "a_removed_attention_knob"])
def test_nothing_names_what_the_one_harness_replaced(pattern):
    """The acceptance grep of PR 31: no file of the package, examples/,
    docs/, README.md, ci.sh, chip_smoke.py or the verify notes names
    the root bench script, one of its environment variables, or one of
    the four attention switches it swept — an operator reads of one
    measuring system, ``python3 -m benchmark.run``."""
    import re

    files = [SMOKE, os.path.join(REPO, "README.md"),
             os.path.join(REPO, "ci.sh"),
             os.path.join(REPO, ".claude", "skills", "verify", "SKILL.md")]
    for root, suffix in (("horovod_tpu", ".py"), ("examples", ".py"),
                         ("docs", ".md")):
        for d, _, names in os.walk(os.path.join(REPO, root)):
            files += [os.path.join(d, n) for n in names
                      if n.endswith(suffix)]
    hits = []
    for path in files:
        if not os.path.exists(path):    # the notes are not in every copy
            continue
        with open(path, encoding="utf-8") as f:
            hits += [f"{os.path.relpath(path, REPO)}:{i}"
                     for i, line in enumerate(f, 1)
                     if re.search(pattern, line)]
    assert not hits, hits


def test_ops_import_nothing_of_parallel_but_the_mesh():
    """``ops/`` is the lower layer: its modules may ask
    ``parallel/mesh.py`` (which imports nothing of ``ops/``) where the
    data axis lies, and import nothing else from ``parallel/`` — the
    flash kernels reached up into ``ring_attention`` for their
    rematerialised backward until PR 31."""
    import ast

    reached = []
    ops = os.path.join(REPO, "horovod_tpu", "ops")
    for name in sorted(os.listdir(ops)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(ops, name)) as f:
            tree = ast.parse(f.read(), filename=name)
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                # a relative import counts from horovod_tpu.ops
                module = node.module or ""
                if node.level:
                    module = ".".join(
                        ["horovod_tpu", "ops"][:3 - node.level] + [module])
                names = [f"{module}.{a.name}" for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            else:
                continue
            reached += [f"{name}:{node.lineno} {n}" for n in names
                        if n.startswith("horovod_tpu.parallel")
                        and not n.startswith("horovod_tpu.parallel.mesh")]
    assert not reached, reached


def test_chip_smoke_without_a_tpu_fails_and_prints_no_result(tmp_path):
    """JAX_PLATFORMS=cpu and no rehearsal flag: non-zero exit, "no TPU
    found", and no ``{"ok": ...}`` line — the contract's sandbox run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("HOROVOD_PLATFORM", None)
    r = subprocess.run([sys.executable, SMOKE], env=env, cwd=str(tmp_path),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout
    stages = [json.loads(line) for line in r.stdout.splitlines()
              if line.startswith('{"stage"')]
    assert [s["stage"] for s in stages] == ["device"]
    assert stages[0]["status"] == "failed"


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program beside it must fail too."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(SMOKE).read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, str(alone)], env=env,
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


# --- one process per chip ----------------------------------------------------


def _slots(n):
    from horovod_tpu.run.launcher import allocate

    return allocate([("localhost", n)], n)


_TPU_KEYS = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
             "TPU_PROCESS_BOUNDS", "TPU_PROCESS_ADDRESSES",
             "TPU_PROCESS_PORT", "CLOUD_TPU_TASK_ID")


@pytest.mark.parametrize("base", [{}, {"JAX_PLATFORMS": "tpu,cpu"},
                                  {"HOROVOD_PLATFORM": "tpu"}])
def test_rank_env_gives_each_local_rank_its_own_chip(base):
    from horovod_tpu.run.launcher import _rank_env

    envs = [_rank_env(s, "localhost:1", "", 0, dict(base))
            for s in _slots(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    # one ICI world: every rank names the same peers and the same grid
    assert len({e["TPU_PROCESS_ADDRESSES"] for e in envs}) == 1
    addrs = envs[0]["TPU_PROCESS_ADDRESSES"].split(",")
    assert [a.rsplit(":", 1)[1] for a in addrs] == [
        e["TPU_PROCESS_PORT"] for e in envs]
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}


@pytest.mark.parametrize("base", [{"HOROVOD_PLATFORM": "cpu"},
                                  {"JAX_PLATFORMS": "cpu"},
                                  {"JAX_PLATFORMS": "tpu",
                                   "HOROVOD_PLATFORM": "cpu"}])
def test_rank_env_exports_no_chip_assignment_on_the_cpu_path(base):
    from horovod_tpu.run.launcher import _rank_env

    for s in _slots(4):
        env = _rank_env(s, "localhost:1", "", 0, dict(base))
        assert not [k for k in _TPU_KEYS if k in env]
        assert env["HOROVOD_LOCAL_RANK"] == str(s.local_rank)


def test_rank_env_refuses_a_rank_count_with_no_known_chip_grid():
    from horovod_tpu.run.launcher import _rank_env

    with pytest.raises(ValueError, match="no chip grid is known"):
        _rank_env(_slots(3)[0], "localhost:1", "", 0, {})


def test_world_mesh_is_ordered_by_rank_not_by_jax_process_index(
        monkeypatch):
    """A TPU backend numbers processes by where their chips sit,
    whatever process_id jax.distributed was given: on the four-chip
    host ranks 0..3 came up as jax processes 0, 2, 3, 1 (PR 21).  The
    launcher's numbering is the job's, so mesh position r must hold
    rank r's device and this process's lead device must be its own."""
    import jax

    from horovod_tpu.common import basics

    class Dev:
        def __init__(self, id, proc):
            self.id, self.process_index = id, proc
            self.platform, self.device_kind = "tpu", "fake"

    devs = [Dev(i, i) for i in range(4)]
    st = basics._State()
    st.size, st.rank = 4, 1          # this process: rank 1, jax process 2
    monkeypatch.setattr(basics, "_state", st)
    monkeypatch.setattr(jax, "devices", lambda: devs)
    monkeypatch.setattr(jax, "process_index", lambda: 2)
    monkeypatch.setattr(basics, "_process_of_each_rank",
                        lambda: [0, 2, 3, 1])
    basics._build_meshes()
    assert [d.process_index for d in st.mesh.devices] == [0, 2, 3, 1]
    assert st.lead_device is devs[2]
    assert st.mesh.devices[st.rank] is st.lead_device
    assert list(st.local_mesh.devices) == [devs[2]]


def test_launcher_parent_stays_off_jax_backends():
    """A parent that has touched a JAX backend holds the chip its ranks
    need.  The launcher's own work — check_build included — is imports
    and environment only."""
    code = ("from horovod_tpu.run import launcher\n"
            "launcher.check_build()\n"
            "launcher._rank_env(launcher.allocate([('localhost', 4)], 4)[0],"
            " 'localhost:1', '', 0, {})\n"
            "from jax._src import xla_bridge as xb\n"
            "print('BACKENDS', list(xb._backends))\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "BACKENDS []" in r.stdout


# --- native artifacts ---------------------------------------------------------


def test_native_artifact_name_follows_the_source_bytes(tmp_path, monkeypatch):
    """A built artifact is named by a hash of its source, so a binary
    from another revision (or one copied in with meaningless mtimes) is
    never the one that loads, and nothing is built outside the tree."""
    from horovod_tpu.runtime import native_build as nb

    monkeypatch.setattr(nb, "_CSRC", str(tmp_path))
    (tmp_path / "x.cc").write_text("int f() { return 1; }\n")
    first = nb.artifact_path("libx", "x.cc")
    assert first == nb.artifact_path("libx", "x.cc")
    assert os.path.dirname(first) == str(tmp_path)
    (tmp_path / "x.cc").write_text("int f() { return 2; }\n")
    assert nb.artifact_path("libx", "x.cc") != first
    src = open(nb.__file__).read() + open(os.path.join(
        REPO, "horovod_tpu", "runtime", "kvstore.py")).read()
    assert ".cache" not in src and "getmtime" not in src


def test_native_build_failure_raises(tmp_path, monkeypatch):
    from horovod_tpu.runtime import native_build as nb

    monkeypatch.setattr(nb, "_CSRC", str(tmp_path))
    monkeypatch.setattr(nb, "_loaded", {})
    (tmp_path / "bad.cc").write_text("this is not C++\n")
    with pytest.raises(RuntimeError, match="g\\+\\+ failed"):
        nb.load_shared("libbad", "bad.cc")
    assert not [n for n in os.listdir(tmp_path) if n != "bad.cc"]


# --- kernels that cannot be kernels say so -------------------------------------


def test_pallas_interpret_follows_the_backend(monkeypatch):
    import jax

    from horovod_tpu.common.platform import pallas_interpret

    assert pallas_interpret() is True           # CPU test mesh
    assert pallas_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_interpret() is False
    assert pallas_interpret(False) is False
    with pytest.raises(ValueError, match="interpret mode"):
        pallas_interpret(True)


def test_forced_quant_kernels_refuse_an_unaligned_block(monkeypatch):
    """HOROVOD_QUANT_PALLAS=1 with a block the kernels cannot tile used
    to run the jnp codec without a word."""
    import jax.numpy as jnp

    from horovod_tpu.ops import quantization as Q

    monkeypatch.setenv("HOROVOD_QUANT_PALLAS", "1")
    x = jnp.ones((4, 96), jnp.float32)
    with pytest.raises(ValueError, match="not a multiple of 128"):
        Q.quantize_values(x, jnp.ones((4,), jnp.float32))
    with pytest.raises(ValueError, match="not a multiple of 256"):
        Q.quantize_pack4_values(jnp.ones((4, 128), jnp.float32),
                                jnp.ones((4,), jnp.float32))
    monkeypatch.setenv("HOROVOD_QUANT_PALLAS", "0")
    assert Q.quantize_values(x, jnp.ones((4,), jnp.float32)).shape == (4, 96)


def test_auto_quant_kernel_choice_logs_once_when_unaligned(monkeypatch,
                                                           capfd):
    import jax

    from horovod_tpu.ops import quantization as Q

    monkeypatch.setenv("HOROVOD_QUANT_PALLAS", "auto")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(Q, "_warned_unaligned", set())
    assert Q._use_pallas(96) is False
    assert Q._use_pallas(96) is False
    assert Q._use_pallas(256) is True
    err = capfd.readouterr().err
    assert err.count("cannot tile it") == 1
