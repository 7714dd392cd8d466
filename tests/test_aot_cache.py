"""Persistent AOT executable cache (docs/aot-cache.md).

Covers the acceptance bar of the cold-path-speed PR:
  * warm-start proof — a second run against a populated
    ``HOROVOD_AOT_CACHE_DIR`` loads every negotiated program from cache
    (zero cold builds) and spends > 2x less wall time materializing
    programs than the cold run;
  * fail-closed hygiene — corrupt, truncated, version-skewed,
    schema-skewed and wrong-key entries are evicted (one warning) and
    recompiled, never run;
  * key schema — the cfg vector, topology and program signature all
    discriminate entries;
  * the ``aot_cache`` CLI (list / info / prune / clear, also reachable
    through ``python -m horovod_tpu.trace aot-cache``);
  * an elastic 2-proc re-form whose survivor resumes from cache
    (slow: runs the SIGKILL scenario twice over one cache dir).
"""

import json
import os
import pickle
import re
import shutil
import signal  # noqa: F401  (used inside spawned scripts)
import socket
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.runtime import aot_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Unit layer: compile_or_load on plain jit programs (no init needed)
# ---------------------------------------------------------------------------


@pytest.fixture()
def cache_dir(tmp_path, monkeypatch):
    d = str(tmp_path / "aot")
    monkeypatch.setenv("HOROVOD_AOT_CACHE_DIR", d)
    monkeypatch.delenv("HOROVOD_AOT_CACHE_MODE", raising=False)
    aot_cache.reset_warnings()
    yield d


def _build():
    return jax.jit(lambda x: x * 2 + 1)


def _compile(key, x):
    return aot_cache.compile_or_load(key, _build, [x])


def test_roundtrip_hit_and_miss(cache_dir):
    x = jnp.arange(8.0)
    key = ("t_roundtrip", (8,), "f32")
    s0 = aot_cache.stats()
    fn = _compile(key, x)
    np.testing.assert_array_equal(np.asarray(fn(x)),
                                  np.asarray(x) * 2 + 1)
    s1 = aot_cache.stats()
    assert s1["misses"] == s0["misses"] + 1
    assert s1["hits"] == s0["hits"]
    assert os.path.exists(aot_cache.entry_path(key))
    # fresh in-memory state (new process simulated): load from disk
    fn2 = _compile(key, x)
    np.testing.assert_array_equal(np.asarray(fn2(x)),
                                  np.asarray(x) * 2 + 1)
    s2 = aot_cache.stats()
    assert s2["hits"] == s1["hits"] + 1
    assert s2["misses"] == s1["misses"]
    assert s2["compile_s_warm"] > s1["compile_s_warm"]


def test_export_mode_roundtrip(cache_dir, monkeypatch):
    monkeypatch.setenv("HOROVOD_AOT_CACHE_MODE", "export")
    x = jnp.arange(6.0)
    key = ("t_export", (6,))
    s0 = aot_cache.stats()
    fn = _compile(key, x)
    np.testing.assert_array_equal(np.asarray(fn(x)),
                                  np.asarray(x) * 2 + 1)
    fn2 = _compile(key, x)
    np.testing.assert_array_equal(np.asarray(fn2(x)),
                                  np.asarray(x) * 2 + 1)
    s1 = aot_cache.stats()
    assert s1["hits"] == s0["hits"] + 1
    with open(aot_cache.entry_path(key), "rb") as f:
        assert pickle.load(f)["mode"] == "export"


def test_mode_off_and_unset_dir(cache_dir, monkeypatch):
    monkeypatch.setenv("HOROVOD_AOT_CACHE_MODE", "off")
    assert not aot_cache.enabled()
    x = jnp.arange(4.0)
    fn = _compile(("t_off",), x)
    np.testing.assert_array_equal(np.asarray(fn(x)),
                                  np.asarray(x) * 2 + 1)
    assert not os.path.exists(cache_dir) or not os.listdir(cache_dir)
    monkeypatch.delenv("HOROVOD_AOT_CACHE_MODE", raising=False)
    monkeypatch.delenv("HOROVOD_AOT_CACHE_DIR", raising=False)
    assert not aot_cache.enabled()


# --- fail-closed hygiene ----------------------------------------------------


def _seed_entry(key, x):
    fn = aot_cache.compile_or_load(key, _build, [x])
    path = aot_cache.entry_path(key)
    assert os.path.exists(path)
    return fn, path


@pytest.mark.parametrize("corruption", [
    "garbage", "truncated", "version_skew", "schema_skew", "wrong_key",
])
def test_bad_entries_evicted_and_recompiled(cache_dir, corruption):
    x = jnp.arange(16.0)
    key = (f"t_{corruption}", (16,))
    _, path = _seed_entry(key, x)
    if corruption == "garbage":
        with open(path, "wb") as f:
            f.write(b"\x00not a pickle at all")
    elif corruption == "truncated":
        with open(path, "rb") as f:
            data = f.read()
        with open(path, "wb") as f:
            f.write(data[:len(data) // 3])
    elif corruption in ("version_skew", "schema_skew"):
        with open(path, "rb") as f:
            rec = pickle.load(f)
        if corruption == "version_skew":
            rec["versions"] = ("0.0.1", "0.0.1", "")
        else:
            rec["schema"] = aot_cache.SCHEMA + 999
        with open(path, "wb") as f:
            pickle.dump(rec, f)
    else:  # wrong_key: entry for ANOTHER program moved onto this key
        other = ("t_other_program", (16,))
        _seed_entry(other, x)
        shutil.copy(aot_cache.entry_path(other), path)
    s0 = aot_cache.stats()
    fn = aot_cache.compile_or_load(key, _build, [x])
    s1 = aot_cache.stats()
    assert s1["evictions"] == s0["evictions"] + 1, corruption
    assert s1["misses"] == s0["misses"] + 1  # recompiled, not crashed
    np.testing.assert_array_equal(np.asarray(fn(x)),
                                  np.asarray(x) * 2 + 1)
    # the recompile re-persisted a VALID entry in place of the bad one
    fn2 = aot_cache.compile_or_load(key, _build, [x])
    s2 = aot_cache.stats()
    assert s2["hits"] == s1["hits"] + 1
    assert s2["evictions"] == s1["evictions"]
    np.testing.assert_array_equal(np.asarray(fn2(x)),
                                  np.asarray(x) * 2 + 1)


def test_serialize_failure_is_advisory(cache_dir, monkeypatch):
    """A program the serializer rejects still runs — it is simply not
    persisted (fail-open on the write side, fail-closed on reads)."""
    def boom(*a, **k):
        raise RuntimeError("no serialization today")

    monkeypatch.setattr(aot_cache, "_serialize", boom)
    x = jnp.arange(5.0)
    key = ("t_serfail", (5,))
    fn = aot_cache.compile_or_load(key, _build, [x])
    np.testing.assert_array_equal(np.asarray(fn(x)),
                                  np.asarray(x) * 2 + 1)
    assert not os.path.exists(aot_cache.entry_path(key))


# --- key schema -------------------------------------------------------------


def test_cfg_vector_discriminates_keys(cache_dir, monkeypatch):
    key = ("t_cfgkey", (4,))
    p1 = aot_cache.entry_path(key)
    monkeypatch.setenv("HOROVOD_COMPRESSION", "int8")
    p2 = aot_cache.entry_path(key)
    monkeypatch.setenv("HOROVOD_ZERO_STAGE", "2")
    p3 = aot_cache.entry_path(key)
    assert len({p1, p2, p3}) == 3


def test_program_key_discriminates(cache_dir):
    assert aot_cache.entry_path(("ar", (4,))) \
        != aot_cache.entry_path(("ar", (8,)))


# --- CLI --------------------------------------------------------------------


def test_cli_list_info_prune_clear(cache_dir, capsys):
    x = jnp.arange(12.0)
    _seed_entry(("t_cli_a", (12,)), x)
    _seed_entry(("t_cli_b", (12,)), x)
    # one corrupt + one version-skewed entry for prune to collect
    bad = os.path.join(cache_dir, "deadbeef" + "0" * 24 + ".aot")
    with open(bad, "wb") as f:
        f.write(b"junk")
    skew_path = aot_cache.entry_path(("t_cli_skew", (12,)))
    _seed_entry(("t_cli_skew", (12,)), x)
    with open(skew_path, "rb") as f:
        rec = pickle.load(f)
    rec["versions"] = ("9.9.9", "9.9.9", "")
    with open(skew_path, "wb") as f:
        pickle.dump(rec, f)

    assert aot_cache.main(["list", cache_dir]) == 0
    out = capsys.readouterr().out
    assert "CORRUPT" in out and "4 entries" in out
    assert aot_cache.main(["info", cache_dir]) == 0
    assert "entries=4 corrupt=1" in capsys.readouterr().out
    assert aot_cache.main(["prune", cache_dir]) == 0
    assert "pruned 2 entries" in capsys.readouterr().out
    assert not os.path.exists(bad) and not os.path.exists(skew_path)
    assert aot_cache.main(["clear", cache_dir]) == 0
    assert not [n for n in os.listdir(cache_dir) if n.endswith(".aot")]


def test_trace_cli_delegates(cache_dir, capsys):
    from horovod_tpu.trace.__main__ import main as trace_main

    _seed_entry(("t_trace_cli", (3,)), jnp.arange(3.0))
    assert trace_main(["aot-cache", "list", cache_dir]) == 0
    assert "1 entry" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Warm-start proof: 2-proc negotiated world, cold run then warm run
# ---------------------------------------------------------------------------


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


_WORLD_BODY = r"""
import json, os
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd
hvd.init()
rank = hvd.rank()
# a fused allreduce (many tensors -> one program), a broadcast, a
# ragged allgather (sizes + payload programs), a reducescatter
outs = hvd.allreduce_gradients(
    {"w%d" % i: jnp.full((5, 3), float(rank + i)) for i in range(12)})
b = hvd.broadcast(jnp.full((4,), float(rank)), 0)
g = hvd.allgather(jnp.ones((2 + rank, 3)))
from horovod_tpu.ops import eager
rs = eager.reducescatter(jnp.ones((8, 2)))
assert float(np.asarray(b).sum()) == 0.0
from horovod_tpu.runtime import aot_cache, flight
stats = aot_cache.stats()
# the counters hold every program the process compiled (PR 37); what
# loading the negotiated ones from their files cost is in the ring
stats["loaded_s"] = sum(e["load_s"] for e in flight.recorder().snapshot()
                        if e["kind"] == "aot" and e["event"] == "hit")
print("AOT-STATS-%d %s" % (rank, json.dumps(stats)), flush=True)
hvd.shutdown()
print("RANK-%d-DONE" % rank, flush=True)
"""


def _run_world(np_: int, cache: str):
    port = _free_port()
    procs = []
    for r in range(np_):
        env = dict(os.environ)
        env.update({
            "HOROVOD_PLATFORM": "cpu",
            "HOROVOD_RANK": str(r), "HOROVOD_SIZE": str(np_),
            "HOROVOD_LOCAL_RANK": str(r),
            "HOROVOD_LOCAL_SIZE": str(np_),
            "HOROVOD_COORDINATOR_ADDR": f"localhost:{port}",
            "HOROVOD_AOT_CACHE_DIR": cache,
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORLD_BODY], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError(f"rank {r} timed out")
        outs.append(out)
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
    stats = []
    for r, out in enumerate(outs):
        m = re.search(rf"AOT-STATS-{r} (.+)", out)
        assert m, out
        stats.append(json.loads(m.group(1)))
    return stats


@pytest.mark.multiprocess
def test_cold_then_warm_2proc(tmp_path):
    """Acceptance: against a populated cache the second start performs
    ZERO cold builds of cached programs (misses == 0, hits > 0) and
    spends > 2x less wall time materializing them."""
    cache = str(tmp_path / "aot")
    cold = _run_world(2, cache)
    for s in cold:
        # Entries are keyed by program and topology, not by rank, so a
        # rank that reaches a program second may already load the file
        # its peer just persisted: hits can be > 0 even in a cold world.
        assert s["misses"] >= 1 and s["hits"] + s["misses"] >= 4, s
        assert s["compile_s_cold"] > 0, s
    assert [n for n in os.listdir(cache) if n.endswith(".aot")]
    # what compiling the cached programs cost, as each entry recorded it
    compiled_s = sum(meta["compile_s"]
                     for _, meta in aot_cache.iter_entries(cache))
    warm = _run_world(2, cache)
    for c, w in zip(cold, warm):
        assert w["misses"] == 0, w          # zero XLA compiles of cached
        # every program came warm
        assert w["hits"] == c["hits"] + c["misses"], w
        assert w["evictions"] == 0, w
        assert 0 < w["loaded_s"] and compiled_s > 2 * w["loaded_s"], \
            (compiled_s, c, w)


# ---------------------------------------------------------------------------
# Elastic: the survivor's re-form resumes from cache (slow: the SIGKILL
# scenario twice over one cache dir — 3 ranks so the re-formed world is
# size 2 and actually builds negotiated programs; run 1 populates the
# size-3 AND size-2 entries, run 2 must load both generations warm)
# ---------------------------------------------------------------------------


_ELASTIC_BODY = r"""
import json, os, signal, time
import numpy as np
import jax.numpy as jnp
import optax
import horovod_tpu as hvd
from horovod_tpu import elastic

hvd.init()
uid = os.environ.get("HOROVOD_ELASTIC_UID", "")
initial_rank = int(uid[4:]) if uid.startswith("rank") else -1

opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                               op=hvd.Average)
params = {"w": jnp.zeros((4,), jnp.float32)}
state = elastic.ElasticState(params=params, opt_state=opt.init(params),
                             step=0)
target = jnp.arange(1.0, 5.0)

def train(state):
    while state.step < 8:
        if state.step % 2 == 0:
            state.commit()
        if initial_rank == 2 and state.step == 4:
            os.kill(os.getpid(), signal.SIGKILL)
        g = {"w": (state.params["w"] - target) * 0.5}
        upd, state.opt_state = opt.update(g, state.opt_state,
                                          state.params)
        state.params = optax.apply_updates(state.params, upd)
        state.step += 1
    state.commit()
    return state

elastic.run(state, train)
from horovod_tpu.runtime import aot_cache
print("EL-AOT %s" % json.dumps(aot_cache.stats()), flush=True)
try:
    status = elastic._rv().try_get("el/status")
    print("EL-STATUS %s" % status, flush=True)
except Exception as exc:
    print("EL-STATUS-ERR %r" % (exc,), flush=True)
if hvd.rank() == 0:
    time.sleep(1.5)
os._exit(0)
"""


def _run_elastic_pair(cache: str):
    from horovod_tpu.runtime.kvstore import KVStoreServer

    srv = KVStoreServer(secret=b"")
    coord_port = _free_port()
    procs = []
    try:
        for r in range(3):
            env = dict(os.environ)
            env.update({
                "PYTHONPATH": REPO + os.pathsep
                + env.get("PYTHONPATH", ""),
                "HOROVOD_PLATFORM": "cpu",
                "HOROVOD_RANK": str(r), "HOROVOD_SIZE": "3",
                "HOROVOD_LOCAL_RANK": str(r), "HOROVOD_LOCAL_SIZE": "3",
                "HOROVOD_COORDINATOR_ADDR": f"127.0.0.1:{coord_port}",
                "HOROVOD_GLOO_RENDEZVOUS_ADDR": "127.0.0.1",
                "HOROVOD_GLOO_RENDEZVOUS_PORT": str(srv.port),
                "HOROVOD_SECRET_KEY": "",
                "HOROVOD_ELASTIC": "1",
                "HOROVOD_ELASTIC_UID": f"rank{r}",
                "HOROVOD_MIN_RANKS": "1",
                "HOROVOD_HEARTBEAT_INTERVAL": "0.5",
                "HOROVOD_HEARTBEAT_TIMEOUT_SECONDS": "3",
                "HOROVOD_ELASTIC_SETTLE_SECONDS": "2",
                "HOROVOD_SHUTDOWN_TIMEOUT_SECONDS": "2",
                "HOROVOD_AOT_CACHE_DIR": cache,
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            })
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _ELASTIC_BODY], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        outs = []
        for r, p in enumerate(procs):
            try:
                out, _ = p.communicate(timeout=240)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise AssertionError(f"rank {r} timed out")
            outs.append(out)
    finally:
        srv.stop()
    assert procs[2].returncode == -9
    assert procs[0].returncode == 0, outs[0]
    aot = json.loads(re.search(r"EL-AOT (.+)", outs[0]).group(1))
    status_m = re.search(r"EL-STATUS (\{.+\})", outs[0])
    assert status_m, outs[0]
    return aot, json.loads(status_m.group(1))


@pytest.mark.multiprocess
@pytest.mark.slow
def test_elastic_reform_resumes_from_cache(tmp_path):
    cache = str(tmp_path / "aot")
    aot1, status1 = _run_elastic_pair(cache)
    # re-form latency attribution rides el/status (docs/aot-cache.md)
    for field in ("compile_s", "teardown_s", "rendezvous_s", "resync_s",
                  "init_s", "aot_hits"):
        assert field in status1, status1
    assert aot1["misses"] > 0
    aot2, status2 = _run_elastic_pair(cache)
    # run 2: both the initial size-2 world AND the re-formed size-1
    # world load their programs from run 1's entries
    assert aot2["hits"] > 0 and aot2["misses"] == 0, aot2
    assert status2["aot_hits"] > 0, status2
