"""On-demand builder/loader for the native sources in ``csrc/``.

The reference ships its native core as extensions compiled by a 1626-line
``setup.py``; here the toolchain is just ``g++`` against the running
interpreter's headers.  A built artifact is named by a hash of its
source file's contents and lives beside the source, so what gets loaded
is always what the checked-in source says: an artifact left behind by
another revision (or copied in with meaningless mtimes) has another
name and is never picked up.  Python↔C++ binding is the CPython C API —
no pybind11 dependency.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import subprocess
import sysconfig
import threading

_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "csrc")
_lock = threading.Lock()
_loaded: dict = {}


def artifact_path(stem: str, source: str, suffix: str = ".so") -> str:
    """``csrc/<stem>-<sha256 of the source bytes, 16 hex><suffix>``."""
    with open(os.path.join(_CSRC, source), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_CSRC, f"{stem}-{digest}{suffix}")


def _build(stem: str, source: str, suffix: str, python_ext: bool) -> str:
    out = artifact_path(stem, source, suffix)
    if not os.path.exists(out):
        _compile(os.path.join(_CSRC, source), out, python_ext)
    return out


def load_extension(mod_name: str, source: str):
    """Compile (once per source content) and import ``csrc/<source>`` as
    ``mod_name``.  Raises on any build failure."""
    with _lock:
        if mod_name not in _loaded:
            suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
            out = _build(mod_name, source, suffix, python_ext=True)
            spec = importlib.util.spec_from_file_location(mod_name, out)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _loaded[mod_name] = mod
        return _loaded[mod_name]


def load_shared(lib_name: str, source: str):
    """Compile (once per source content) and dlopen ``csrc/<source>`` as
    a plain shared library (C ABI via ctypes, no Python.h).  Raises on
    build failure."""
    import ctypes

    with _lock:
        if lib_name not in _loaded:
            out = _build(lib_name, source, ".so", python_ext=False)
            _loaded[lib_name] = ctypes.CDLL(out)
        return _loaded[lib_name]


def _compile(src: str, out: str, python_ext: bool) -> None:
    # per-process tmp: N ranks on one host may all compile on first use;
    # each builds privately and the atomic rename makes last-writer win
    # with a complete .so either way
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17"]
    if python_ext:
        cmd.append(f"-I{sysconfig.get_paths()['include']}")
    else:
        cmd.append("-pthread")
    cmd += [src, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, out)
    except subprocess.CalledProcessError as exc:
        raise RuntimeError(
            f"g++ failed building {os.path.basename(src)}:\n"
            f"{exc.stderr.decode(errors='replace')[-2000:]}") from exc
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
