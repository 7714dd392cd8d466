"""Persistent AOT executable cache for the negotiated data plane.

Every restart — and every elastic re-form — used to recompile every
negotiated collective program from scratch: minutes of XLA compile
that count directly against service goodput (ROADMAP item on cold-path
speed; the observatory of docs/perf.md can measure it but PRs 1-10
never removed it).  This module serializes the compiled executables of
:mod:`horovod_tpu.ops.xla_exec`'s program caches into
``HOROVOD_AOT_CACHE_DIR`` so a warm start loads them in seconds.

**Key schema** — an entry is addressed by a SHA-256 over:

* the cache schema version (bump to invalidate every entry at once);
* jax / jaxlib / libtpu versions (an executable is an artifact of the
  exact compiler);
* the topology: world size, local/cross split, platform and device
  kind (a 4-rank executable must never serve an 8-rank world);
* the round-0 cfg i64 vector
  (:func:`horovod_tpu.runtime.controller.round0_cfg`) — by
  construction every knob that can change a negotiated program's
  shape or schedule rides that vector, so a hit under a different
  knob set is structurally impossible;
* the in-memory program cache key from ``ops/xla_exec.py`` (op kind,
  dtype, shapes, world size, hierarchical split, wire compression,
  overlap/zero cfg).

**Fail-closed semantics** — a cache can speed things up; it must never
be able to break them.  Any deserialize error, schema/version skew,
or key mismatch inside the file evicts the entry (one warning per
failure class) and falls through to a normal compile; a stale or
corrupt program can never run.  Serialization failures are likewise
advisory: the freshly compiled program is used and simply not
persisted.

**Formats** (``HOROVOD_AOT_CACHE_MODE``): ``exec`` (default via
``auto``) persists the serialized compiled executable
(``jax.experimental.serialize_executable``) — a warm load skips XLA
entirely; ``export`` persists the lowered StableHLO via ``jax.export``
— the escape hatch when executable serialization misbehaves on a
platform/jaxlib combination: a warm load still pays the XLA compile
and only skips Python tracing/lowering.  Entries are keyed on the
exact jax/jaxlib/libtpu versions in BOTH modes (a version bump always
recompiles).

CLI: ``python -m horovod_tpu.runtime.aot_cache list|info|prune|clear``
(also reachable as ``python -m horovod_tpu.trace aot-cache ...``).
"""

from __future__ import annotations

import hashlib
import os
import pickle
import time

from horovod_tpu.common import config as _config
from horovod_tpu.common import logging as _log
from horovod_tpu.common import platform as _platform
from horovod_tpu.runtime import metrics as _metrics

SCHEMA = 2
_SUFFIX = ".aot"

_M_HITS = _metrics.counter(
    "hvd_aot_cache_hits_total",
    "Programs loaded from the persistent AOT executable cache instead "
    "of compiled (docs/aot-cache.md).")
_M_MISSES = _metrics.counter(
    "hvd_aot_cache_misses_total",
    "Programs compiled cold because no (valid) AOT cache entry "
    "existed; counted only while the cache is enabled.")
_M_EVICTIONS = _metrics.counter(
    "hvd_aot_cache_evictions_total",
    "AOT cache entries evicted fail-closed (corrupt, truncated, "
    "version-skewed or wrong-key files) — each eviction recompiles.")
_M_COMPILE_S = _metrics.counter(
    "hvd_compile_seconds_total",
    "Wall seconds spent materializing programs, every jax.jit one and "
    "the negotiated ones, labeled path=cold (trace + lower + XLA "
    "compile) vs path=warm (a load from JAX's persistent cache or from "
    "the AOT cache).")

_warned: set = set()
_version_cache: tuple | None = None


def cache_dir() -> str | None:
    d = str(_config.get("aot_cache_dir")).strip()
    return d or None


def mode() -> str:
    """Resolved serialization format: ``exec`` | ``export`` | ``off``."""
    m = str(_config.get("aot_cache_mode")).strip().lower()
    if m in ("", "auto"):
        return "exec"
    if m in ("exec", "export", "off"):
        return m
    _warn_once("mode", f"unknown HOROVOD_AOT_CACHE_MODE={m!r}; "
                       "expected auto|exec|export|off — cache disabled")
    return "off"


def enabled() -> bool:
    return cache_dir() is not None and mode() != "off"


def _warn_once(category: str, msg: str) -> None:
    if category not in _warned:
        _warned.add(category)
        _log.warning(f"aot-cache: {msg}")


def reset_warnings() -> None:  # test hook
    _warned.clear()


def versions() -> tuple:
    """(jax, jaxlib, libtpu) version triple — part of every key: an
    executable is an artifact of the exact compiler that built it."""
    global _version_cache
    if _version_cache is None:
        import jax
        import jaxlib

        libtpu = ""
        try:
            from importlib.metadata import version as _v

            for name in ("libtpu", "libtpu-nightly"):
                try:
                    libtpu = _v(name)
                    break
                except Exception:
                    continue
        except Exception:
            pass
        _version_cache = (jax.__version__, jaxlib.__version__, libtpu)
    return _version_cache


def _topology() -> tuple:
    from horovod_tpu.common import basics as _basics

    st = _basics.state()
    if st.lead_device is not None:
        return (st.size, st.local_size, st.cross_size,
                st.lead_device.platform,
                getattr(st.lead_device, "device_kind", ""))
    import jax

    dev = jax.devices()[0]
    return (1, 1, 1, dev.platform, getattr(dev, "device_kind", ""))


def _cfg_vector() -> tuple:
    # Lazy: the controller module is heavier than this one, and at the
    # only call sites (a program build) it is loaded anyway.
    from horovod_tpu.runtime.controller import round0_cfg

    return tuple(int(v) for v in round0_cfg())


def context() -> tuple:
    """Everything but the program signature: recomputed per call (all
    env/state reads) so a mid-run knob change — e.g. the adaptive
    tuner rewriting ``HOROVOD_BUCKET_COMPRESSION`` — keys the rebuilt
    programs honestly."""
    return (SCHEMA, versions(), _topology(), _cfg_vector())


def _key_material(program_key) -> str:
    return repr((context(), repr(program_key)))


def entry_path(program_key) -> str:
    digest = hashlib.sha256(
        _key_material(program_key).encode()).hexdigest()[:32]
    return os.path.join(cache_dir() or "", digest + _SUFFIX)


def _label(program_key) -> str:
    """Short human name for CLI listings (kind + arity), best-effort."""
    try:
        kind = str(program_key[0])
        return f"{kind}:{len(repr(program_key))}"
    except Exception:
        return "?"


def _evict(path: str, reason: str, category: str) -> None:
    _M_EVICTIONS.inc()
    _warn_once(
        f"evict:{category}",
        f"evicting {os.path.basename(path)} ({reason}); recompiling")
    try:
        os.unlink(path)
    except OSError:
        pass
    try:
        from horovod_tpu.runtime import flight as _flight

        _flight.record("aot", event="evict", entry=os.path.basename(path),
                       reason=reason[:160])
    except Exception:
        pass


def _try_load(program_key, args):
    """Load + rebuild one entry, or ``None`` — NEVER raises (any
    failure evicts and falls through to a cold compile)."""
    path = entry_path(program_key)
    if not os.path.exists(path):
        return None
    try:
        with open(path, "rb") as f:
            rec = pickle.load(f)
    except Exception as exc:
        _evict(path, f"unreadable/corrupt: {exc!r}", "corrupt")
        return None
    # Explicit category per failure class — the warn-once dedup is per
    # class, so a later DIFFERENT failure still surfaces.
    if not isinstance(rec, dict) or rec.get("schema") != SCHEMA:
        got = rec.get("schema") if isinstance(rec, dict) else "?"
        _evict(path, f"schema skew: {got} != {SCHEMA}", "schema")
        return None
    if rec.get("versions") != versions():
        _evict(path, f"version skew: built under {rec.get('versions')}, "
                     f"running {versions()}", "version")
        return None
    if rec.get("key") != _key_material(program_key):
        _evict(path, "key mismatch (collision or relocated file)", "key")
        return None
    fmt = rec.get("mode")
    if fmt not in ("exec", "export"):
        _evict(path, f"unknown entry mode {fmt!r}", "mode")
        return None
    try:
        if fmt == "exec":
            from jax.experimental import serialize_executable as _se

            import jax

            blob, in_tree, out_tree, device_ids = rec["payload"]
            # Load onto the program's own devices, in its assignment
            # order: the default is every local device, and a 1-device
            # program loaded onto 8 refuses its arguments.
            by_id = {d.id: d for d in jax.devices()}
            return _se.deserialize_and_load(
                blob, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        import jax
        import jax.export as _je

        exported = _je.deserialize(bytearray(rec["payload"]))
        return jax.jit(exported.call).lower(*args).compile()
    except Exception as exc:
        _evict(path, f"{type(exc).__name__}: {exc}", "deserialize")
        return None


def _atomic_write(path: str, rec: dict) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "wb") as f:
            pickle.dump(rec, f)
        os.replace(tmp, path)
    except Exception as exc:
        _warn_once("persist", f"could not persist entry ({exc!r}); "
                              "programs will recompile next start")
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _serialize(compiled, fn, args, fmt: str):
    """Payload for one freshly compiled program, or ``None`` when the
    format cannot serialize it (advisory — the program still runs)."""
    if fmt == "exec":
        from jax.experimental import serialize_executable as _se

        device_ids = [d.id for d in
                      compiled._executable._unloaded_executable.device_list]
        return (*_se.serialize(compiled), device_ids)
    import jax.export as _je

    return bytes(_je.export(fn)(*args).serialize())


def compile_or_load(program_key, build, args):
    """The single entry point the program caches call on a miss:
    ``build()`` returns the jitted program, ``args`` are the concrete
    call arguments (they define the avals/shardings the AOT compile
    binds).  Returns a callable with the program's calling convention
    — a cache-loaded executable on a hit, the AOT-compiled program on
    a miss (persisted for next time), or the plain jitted function if
    AOT lowering itself fails.

    ``hvd_compile_seconds_total{path=cold|warm}`` gets every second
    once: what JAX compiles here (the miss's ``lower().compile()``, an
    ``export`` entry's recompile) is counted by its own compile events
    (``common/platform``, one ``hvd_compile`` record a program); this
    function adds only the seconds those never see — a load from the
    entry's file, a lowering that failed."""
    t0 = time.perf_counter()
    seen0 = _platform.compiled_seconds()

    def unseen_s() -> float:
        return max(0.0, time.perf_counter() - t0
                   - (_platform.compiled_seconds() - seen0))

    if enabled():
        loaded = _try_load(program_key, args)
        if loaded is not None:
            load_s = unseen_s()
            _M_HITS.inc()
            _M_COMPILE_S.inc(load_s, path="warm")
            try:
                from horovod_tpu.runtime import flight as _flight

                # (``program``, not ``kind``: that is record()'s own
                # argument, and the TypeError used to be swallowed here)
                _flight.record("aot", event="hit",
                               program=_label(program_key),
                               load_s=round(load_s, 4))
            except Exception:
                pass
            return loaded
        _M_MISSES.inc()
    fn = build()
    try:
        compiled = fn.lower(*args).compile()
    except Exception as exc:
        _M_COMPILE_S.inc(unseen_s(), path="cold")
        _warn_once("lower", f"AOT lower/compile unavailable for "
                            f"{_label(program_key)} ({exc!r}); using "
                            "lazy jit (not cacheable)")
        return fn
    if enabled():
        fmt = mode()
        try:
            payload = _serialize(compiled, fn, args, fmt)
        except Exception as exc:
            _warn_once("serialize",
                       f"could not serialize {_label(program_key)} "
                       f"({exc!r}); it will recompile next start")
            payload = None
        if payload is not None:
            _atomic_write(entry_path(program_key), {
                "schema": SCHEMA,
                "mode": fmt,
                "versions": versions(),
                "key": _key_material(program_key),
                "label": _label(program_key),
                "created": time.time(),
                # what a hit saves: the compile as JAX timed it
                "compile_s": round(
                    _platform.compiled_seconds() - seen0, 4),
                "payload": payload,
            })
    return compiled


def stats() -> dict:
    """Counter snapshot for the re-form report and tests."""
    return {
        "hits": int(_M_HITS.total()),
        "misses": int(_M_MISSES.total()),
        "evictions": int(_M_EVICTIONS.total()),
        "compile_s_cold": round(_M_COMPILE_S.value(path="cold"), 4),
        "compile_s_warm": round(_M_COMPILE_S.value(path="warm"), 4),
    }


# ---------------------------------------------------------------------------
# CLI: list / info / prune / clear
# ---------------------------------------------------------------------------


def iter_entries(d: str):
    """Yield ``(path, meta | None)`` per cache file; ``None`` meta
    marks an unreadable entry."""
    for name in sorted(os.listdir(d)):
        if not name.endswith(_SUFFIX):
            continue
        path = os.path.join(d, name)
        try:
            with open(path, "rb") as f:
                rec = pickle.load(f)
            meta = {k: rec.get(k) for k in
                    ("schema", "mode", "versions", "label", "created",
                     "compile_s")}
            meta["bytes"] = os.path.getsize(path)
            yield path, meta
        except Exception:
            yield path, None


def prune(d: str, max_age_days: float = 0.0, max_mb: float = 0.0,
          stale_only: bool = False) -> list:
    """Delete corrupt entries, entries older than ``max_age_days``,
    version-skewed entries (``stale_only`` restricts to these two),
    then the oldest entries beyond ``max_mb``.  Returns deleted paths."""
    deleted: list = []
    keep: list = []
    now = time.time()
    cur_versions = versions()
    for path, meta in iter_entries(d):
        if meta is None or meta.get("schema") != SCHEMA \
                or meta.get("versions") != cur_versions:
            deleted.append(path)
            continue
        age_days = (now - float(meta.get("created") or 0)) / 86400.0
        if max_age_days and age_days > max_age_days:
            deleted.append(path)
            continue
        keep.append((float(meta.get("created") or 0), meta["bytes"], path))
    if max_mb and not stale_only:
        keep.sort()  # oldest first
        total = sum(b for _, b, _ in keep)
        budget = max_mb * 1024 * 1024
        while keep and total > budget:
            _, b, path = keep.pop(0)
            total -= b
            deleted.append(path)
    for path in deleted:
        try:
            os.unlink(path)
        except OSError:
            pass
    return deleted


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m horovod_tpu.runtime.aot_cache",
        description="Inspect/prune the persistent AOT executable cache "
                    "(HOROVOD_AOT_CACHE_DIR; docs/aot-cache.md).")
    sub = p.add_subparsers(dest="cmd", required=True)
    for name, hlp in (("list", "one line per cached program"),
                      ("info", "aggregate totals"),
                      ("clear", "delete every entry"),
                      ("prune", "delete corrupt/skewed/old entries")):
        sp = sub.add_parser(name, help=hlp)
        sp.add_argument("dir", nargs="?", default=cache_dir(),
                        help="cache directory (default: "
                             "HOROVOD_AOT_CACHE_DIR)")
        if name == "prune":
            sp.add_argument("--max-age-days", type=float, default=0.0,
                            help="also delete entries older than this")
            sp.add_argument("--max-mb", type=float, default=0.0,
                            help="then trim oldest entries beyond this "
                                 "total size")
    args = p.parse_args(argv)
    d = args.dir
    if not d:
        print("no cache dir (set HOROVOD_AOT_CACHE_DIR or pass one)")
        return 1
    if not os.path.isdir(d):
        print(f"{d}: not a directory")
        return 1
    if args.cmd == "list":
        rows = list(iter_entries(d))
        for path, meta in rows:
            if meta is None:
                print(f"{os.path.basename(path):36s}  CORRUPT")
                continue
            age = time.time() - float(meta.get("created") or 0)
            print(f"{os.path.basename(path):36s}  {meta['mode']:6s}  "
                  f"{meta['bytes']:>9d}B  {age / 3600:6.1f}h  "
                  f"jax={meta['versions'][0]}  "
                  f"compile={meta.get('compile_s')}s  {meta['label']}")
        print(f"{len(rows)} entr{'y' if len(rows) == 1 else 'ies'}")
        return 0
    if args.cmd == "info":
        n = bad = total = 0
        saved = 0.0
        for _, meta in iter_entries(d):
            n += 1
            if meta is None:
                bad += 1
            else:
                total += meta["bytes"]
                saved += float(meta.get("compile_s") or 0)
        print(f"dir={d} entries={n} corrupt={bad} "
              f"bytes={total} cold_compile_s_banked={saved:.2f}")
        return 0
    if args.cmd == "clear":
        deleted = [path for path, _ in iter_entries(d)]
        for path in deleted:
            try:
                os.unlink(path)
            except OSError:
                pass
        print(f"deleted {len(deleted)} entr"
              f"{'y' if len(deleted) == 1 else 'ies'}")
        return 0
    deleted = prune(d, args.max_age_days, args.max_mb)
    print(f"pruned {len(deleted)} entr"
          f"{'y' if len(deleted) == 1 else 'ies'}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
