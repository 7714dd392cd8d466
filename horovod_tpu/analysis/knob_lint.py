"""Knob-drift linter (docs/analysis.md, rule family ``KNOB-*``).

The config registry (:mod:`horovod_tpu.common.config`) is supposed to
be the single surface every knob flows through; history says it
drifts: PR 10 shipped a knob that reached the registry but not the
round-0 handshake (cross-rank divergence deadlocked at the first
adaptive retrace), and several hierarchical knobs shipped that shape
the negotiated data plane without any handshake validation at all.
This pass mechanizes the cross-references:

* ``KNOB-RAW-ENV`` — a ``HOROVOD_*`` env var read outside
  ``common/config.py`` bypasses parsing, defaults and the registry.
* ``KNOB-TRACE-SEMANTICS`` — a knob read while building negotiated
  data-plane programs (``ops/xla_exec.py`` + the overlap/compression/
  quantization modules it composes) that the round-0 handshake does
  not validate: a per-rank divergence builds mismatched collectives
  and deadlocks instead of failing fast.
* ``KNOB-HANDSHAKE-MISSING`` / ``KNOB-HANDSHAKE-HELP`` — the help
  text and the handshake vector must agree about which knobs claim
  cross-rank agreement.
* ``KNOB-CACHEKEY`` — a handshake knob the in-memory program-cache
  keys cannot see can replay a stale program after a mid-run change
  (the allowlist documents the control-plane knobs that legitimately
  shape no program).
* ``KNOB-AOT-KEY`` — the AOT cache must key on ``round0_cfg()``
  itself (one agreement surface by construction).
* ``KNOB-CLI-REGISTRY`` — the launcher builds its flags from the
  registry.
* ``KNOB-DOC-MISSING`` — every registered knob has a doc row.

Everything here is AST-based: no module UNDER LINT is imported (the
analysis never executes controller/xla_exec/launcher code — their
config reads are read off the syntax tree); the only imports are the
stdlib-only registry and, transitively via the package ``__init__``,
whatever ``import horovod_tpu`` itself pulls.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field

from horovod_tpu.analysis.findings import Finding

# Help-text phrases that claim cross-rank agreement; the handshake
# vector and these markers must agree in both directions.
HANDSHAKE_MARKERS = ("round-0 handshake", "must agree on every rank")

# The negotiated-data-plane modules: any config read here shapes the
# collective programs each rank builds independently.
DATA_PLANE_MODULES = ("ops/xla_exec.py", "ops/collectives.py",
                      "ops/overlap.py", "ops/compression.py",
                      "ops/quantization.py")

_CONFIG_ALIASES = {"config", "_config"}


def _f(rule, loc, msg, hint="", severity="error") -> Finding:
    return Finding(rule=rule, severity=severity, location=loc,
                   message=msg, fix_hint=hint, pass_name="knobs")


# ---------------------------------------------------------------------------
# Per-module AST index
# ---------------------------------------------------------------------------


@dataclass
class FuncInfo:
    module: str                       # repo-relative path
    qualname: str
    node: ast.FunctionDef
    config_reads: set = field(default_factory=set)
    dynamic_get: bool = False         # config.get(<non-constant>)
    calls: list = field(default_factory=list)  # (callee expr, const str args)


@dataclass
class ModuleIndex:
    path: str                          # repo-relative
    tree: ast.AST
    funcs: dict = field(default_factory=dict)      # name -> FuncInfo
    #: EVERY FunctionDef, including ones shadowed in ``funcs`` by a
    #: same-named method elsewhere in the module — whole-module read
    #: collection must not drop a config.get hidden in a shadowed
    #: Compressor.compress.
    all_funcs: list = field(default_factory=list)
    aliases: dict = field(default_factory=dict)    # local name -> module path


def _is_config_get(call: ast.Call) -> bool:
    fn = call.func
    return (isinstance(fn, ast.Attribute)
            and fn.attr in ("get", "is_set")
            and isinstance(fn.value, ast.Name)
            and fn.value.id in _CONFIG_ALIASES)


def _const_str_args(call: ast.Call) -> list:
    return [a.value for a in call.args
            if isinstance(a, ast.Constant) and isinstance(a.value, str)]


def index_module(root: str, relpath: str) -> ModuleIndex:
    with open(os.path.join(root, relpath)) as f:
        tree = ast.parse(f.read(), filename=relpath)
    idx = ModuleIndex(path=relpath, tree=tree)
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            for alias in node.names:
                name = alias.asname or alias.name
                # "from horovod_tpu.ops import overlap as _ovl" maps
                # _ovl -> the module; "from ...compression import f"
                # maps f -> (module, f).
                idx.aliases[name] = (node.module, alias.name)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fi = FuncInfo(module=relpath, qualname=node.name, node=node)
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                if _is_config_get(sub):
                    consts = _const_str_args(sub)
                    if consts:
                        fi.config_reads.update(consts)
                    else:
                        fi.dynamic_get = True
                else:
                    fi.calls.append((sub.func, _const_str_args(sub)))
            # call RESOLUTION keys by bare name (last wins, matching
            # runtime rebinding); read COLLECTION keeps every def
            idx.funcs[node.name] = fi
            idx.all_funcs.append(fi)
    return idx


class _Modules:
    """Loaded module indexes keyed by repo-relative path, with call
    resolution across ``from X import y`` edges."""

    def __init__(self, root: str, relpaths: list):
        self.root = root
        self.by_path = {p: index_module(root, p) for p in relpaths
                        if os.path.exists(os.path.join(root, p))}
        self.by_modname = {
            p.replace("/", ".").removesuffix(".py"): idx
            for p, idx in self.by_path.items()}
        for p, idx in list(self.by_path.items()):
            pkgname = "horovod_tpu." + p.replace("horovod_tpu/", "") \
                .replace("/", ".").removesuffix(".py")
            self.by_modname[pkgname] = idx

    def resolve(self, idx: ModuleIndex, func_expr) -> "FuncInfo | None":
        if isinstance(func_expr, ast.Name):
            if func_expr.id in idx.funcs:
                return idx.funcs[func_expr.id]
            tgt = idx.aliases.get(func_expr.id)
            if tgt:
                mod = self.by_modname.get(tgt[0])
                if mod and tgt[1] in mod.funcs:
                    return mod.funcs[tgt[1]]
        elif isinstance(func_expr, ast.Attribute) \
                and isinstance(func_expr.value, ast.Name):
            tgt = idx.aliases.get(func_expr.value.id)
            if tgt:
                # module alias: "from horovod_tpu.ops import overlap
                # as _ovl" -> _ovl.configured_chunks
                modname = f"{tgt[0]}.{tgt[1]}"
                mod = self.by_modname.get(modname)
                if mod and func_expr.attr in mod.funcs:
                    return mod.funcs[func_expr.attr]
        return None

    def config_closure(self, seeds: list, knob_names: frozenset) -> set:
        """Transitive set of registry knob names read from ``seeds``
        (FuncInfo list): direct ``config.get("x")`` reads plus — for
        callees that read ``config.get(<dynamic>)`` — constant string
        arguments at the call site that name registered knobs (the
        ``_hier_topology("hierarchical_allreduce")`` idiom)."""
        seen_funcs, reads = set(), set()
        stack = list(seeds)
        while stack:
            fi = stack.pop()
            key = (fi.module, fi.qualname)
            if key in seen_funcs:
                continue
            seen_funcs.add(key)
            reads.update(fi.config_reads)
            idx = self.by_path[fi.module]
            for func_expr, const_args in fi.calls:
                callee = self.resolve(idx, func_expr)
                if callee is None:
                    continue
                if callee.dynamic_get:
                    reads.update(a for a in const_args
                                 if a in knob_names)
                stack.append(callee)
        return reads


# ---------------------------------------------------------------------------
# Raw env-read scan
# ---------------------------------------------------------------------------


def _is_os_environ(node) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "environ"
            and isinstance(node.value, ast.Name)
            and node.value.id == "os")


def _env_const(node, consts=None) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str) \
            and node.value.startswith("HOROVOD_"):
        return node.value
    if consts and isinstance(node, ast.Name):
        # `_ENV_EVENTS = "HOROVOD_FLIGHT_EVENTS"` at module level,
        # read later via the name — still a raw env read.
        return consts.get(node.id)
    return None


def scan_env_reads(path: str) -> list:
    """(lineno, env_name) for every constant-key HOROVOD_* read of
    ``os.environ`` / ``os.getenv`` in ``path`` — literal keys plus
    module-level string-constant names.  Writes (``os.environ[k] =
    v``, ``setdefault``) are exempt: exporting a value is how the
    launcher/config hand knobs to children; READING one raw is what
    bypasses the registry."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    consts = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, str) \
                and node.value.value.startswith("HOROVOD_"):
            consts[node.targets[0].id] = node.value.value
    hits = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr == "get" \
                    and _is_os_environ(fn.value) and node.args:
                name = _env_const(node.args[0], consts)
                if name:
                    hits.append((node.lineno, name))
            elif isinstance(fn, ast.Attribute) and fn.attr == "getenv" \
                    and isinstance(fn.value, ast.Name) \
                    and fn.value.id == "os" and node.args:
                name = _env_const(node.args[0], consts)
                if name:
                    hits.append((node.lineno, name))
        elif isinstance(node, ast.Subscript) \
                and isinstance(node.ctx, ast.Load) \
                and _is_os_environ(node.value):
            name = _env_const(node.slice, consts)
            if name:
                hits.append((node.lineno, name))
        elif isinstance(node, ast.Compare) \
                and len(node.ops) == 1 \
                and isinstance(node.ops[0], (ast.In, ast.NotIn)) \
                and any(_is_os_environ(c) for c in node.comparators):
            name = _env_const(node.left, consts)
            if name:
                hits.append((node.lineno, name))
    return hits


# ---------------------------------------------------------------------------
# The pass
# ---------------------------------------------------------------------------


def _package_files(pkg_root: str) -> list:
    out = []
    for dirpath, dirnames, filenames in os.walk(pkg_root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", "csrc")]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                out.append(os.path.join(dirpath, fn))
    return out


def run(package_dir: str | None = None) -> list:
    """Run the knob lint.  ``package_dir`` overrides the tree to scan
    for raw env reads (fixture trees); the registry cross-reference
    rules run only against the real package (a fixture tree has no
    registry to cross-reference)."""
    from horovod_tpu.analysis import repo_root

    root = repo_root()
    findings = []

    fixture_mode = package_dir is not None
    scan_root = package_dir or os.path.join(root, "horovod_tpu")
    config_py = os.path.join("horovod_tpu", "common", "config.py")

    # (1) raw env reads
    for path in _package_files(scan_root):
        rel = os.path.relpath(path, package_dir or root)
        if not fixture_mode and rel.replace(os.sep, "/") == \
                config_py.replace(os.sep, "/"):
            continue
        loc_rel = os.path.relpath(path, root) if not fixture_mode else rel
        try:
            hits = scan_env_reads(path)
        except SyntaxError as exc:
            findings.append(_f("KNOB-RAW-ENV", f"{loc_rel}:1",
                               f"unparseable module: {exc}"))
            continue
        for lineno, env in hits:
            findings.append(_f(
                "KNOB-RAW-ENV", f"{loc_rel}:{lineno}",
                f"raw read of {env} outside common/config.py bypasses "
                "the knob registry (parsing, defaults, CLI/config-file "
                "surfaces)",
                "route through config.get()/config.is_set() or "
                "allowlist with a justification"))
    if fixture_mode:
        return findings

    findings.extend(_registry_rules(root))
    return findings


def _registry_rules(root: str) -> list:
    from horovod_tpu.common import config as _cfg

    findings = []
    knobs = _cfg.knobs()
    knob_names = frozenset(knobs)

    mods = _Modules(root, [
        "horovod_tpu/runtime/controller.py",
        "horovod_tpu/runtime/aot_cache.py",
        "horovod_tpu/run/launcher.py",
    ] + ["horovod_tpu/" + m for m in DATA_PLANE_MODULES])

    # (2) handshake closure: every registry knob round0_cfg reads,
    # transitively through its same/cross-module helpers.
    controller = mods.by_path["horovod_tpu/runtime/controller.py"]
    r0 = controller.funcs.get("round0_cfg")
    if r0 is None:
        findings.append(_f(
            "KNOB-HANDSHAKE-MISSING", "horovod_tpu/runtime/controller.py:1",
            "round0_cfg() not found — the handshake agreement surface "
            "moved; update knob_lint's cross-reference"))
        return findings
    handshake = mods.config_closure([r0], knob_names) & knob_names

    # (3) data-plane reads: knobs consulted while building negotiated
    # programs.
    dp_seeds = [fi for m in DATA_PLANE_MODULES
                for fi in mods.by_path["horovod_tpu/" + m].all_funcs]
    dataplane = set()
    for fi in dp_seeds:
        dataplane.update(fi.config_reads)
    for fi in dp_seeds:
        idx = mods.by_path[fi.module]
        for func_expr, const_args in fi.calls:
            callee = mods.resolve(idx, func_expr)
            if callee is not None and callee.dynamic_get:
                dataplane.update(a for a in const_args
                                 if a in knob_names)
    dataplane &= knob_names

    for name in sorted(dataplane - handshake):
        findings.append(_f(
            "KNOB-TRACE-SEMANTICS",
            "horovod_tpu/runtime/controller.py:round0_cfg",
            f"knob '{name}' ({knobs[name].env}) shapes the negotiated "
            "data-plane programs but is missing from the round-0 "
            "handshake vector — a per-rank divergence builds "
            "mismatched collectives and deadlocks instead of failing "
            "fast",
            "add it to round0_cfg() (and mark the help text), or "
            "allowlist with the reason it cannot diverge"))

    # (4) help-marker <-> handshake agreement, both directions.
    for name, k in sorted(knobs.items()):
        marked = any(m in k.help.lower() for m in HANDSHAKE_MARKERS)
        if marked and name not in handshake:
            findings.append(_f(
                "KNOB-HANDSHAKE-MISSING",
                "horovod_tpu/common/config.py:registry",
                f"knob '{name}' ({k.env}) help text claims cross-rank "
                "agreement but round0_cfg() never reads it — the "
                "handshake cannot validate it",
                "add it to round0_cfg() or drop the claim from help"))
        elif name in handshake and not marked:
            findings.append(_f(
                "KNOB-HANDSHAKE-HELP",
                "horovod_tpu/common/config.py:registry",
                f"knob '{name}' ({k.env}) is validated at the round-0 "
                "handshake but its help text does not say so — "
                "operators cannot know a divergence fails the job",
                "mention 'validated at the round-0 handshake' in help",
                severity="warning"))

    # (5) program-cache key closure: key components named in
    # `key = (...)` tuples of xla_exec, one dataflow step back.
    xla = mods.by_path["horovod_tpu/ops/xla_exec.py"]
    key_seeds = _key_component_funcs(mods, xla)
    cachekey = mods.config_closure(key_seeds, knob_names) & knob_names
    for name in sorted(handshake - cachekey):
        findings.append(_f(
            "KNOB-CACHEKEY", "horovod_tpu/ops/xla_exec.py:key",
            f"handshake knob '{name}' ({knobs[name].env}) is invisible "
            "to the in-memory program-cache keys — a mid-run change "
            "could replay a program negotiated under the old value",
            "fold it into a key component (overlap_cfg/zero_cfg/"
            "_wire_compression idiom) or allowlist with the reason it "
            "shapes no program"))

    # (6) AOT cache keys on round0_cfg by construction.
    aot = mods.by_path.get("horovod_tpu/runtime/aot_cache.py")
    if aot is None or not _calls_name(aot, "round0_cfg"):
        findings.append(_f(
            "KNOB-AOT-KEY", "horovod_tpu/runtime/aot_cache.py:1",
            "the AOT executable cache no longer keys on "
            "controller.round0_cfg() — persisted programs and the "
            "handshake would drift apart",
            "derive the cfg component of the cache key from "
            "round0_cfg() itself"))

    # (7) launcher CLI flags come from the registry.
    launcher = mods.by_path.get("horovod_tpu/run/launcher.py")
    if launcher is None or not _calls_attr(launcher, "knobs"):
        findings.append(_f(
            "KNOB-CLI-REGISTRY", "horovod_tpu/run/launcher.py:1",
            "the launcher parser no longer iterates config.knobs() — "
            "registered CLI flags would silently stop existing",
            "build knob flags from the registry (run/launcher.py "
            "parser loop)"))

    # (8) every registered knob has a doc row.
    docs_text = _docs_corpus(root)
    for name, k in sorted(knobs.items()):
        if k.env not in docs_text:
            findings.append(_f(
                "KNOB-DOC-MISSING", "docs:" + k.env,
                f"registered knob '{name}' ({k.env}) appears in no "
                "docs/*.md — operators cannot discover it",
                "add a row to the relevant doc's knob table",
                severity="warning"))

    # (9) every registered knob has a READER: some string in the
    # package (outside config.py) names either the knob or its env
    # var — via config.get("name"), a dynamic-helper call
    # site, or a justified raw env read.  A knob nothing reads is
    # documentation fiction with a CLI flag (HOROVOD_EAGER_PAD_POW2
    # shipped exactly that way and survived 11 PRs).
    referenced = _referenced_strings(root)
    for name, k in sorted(knobs.items()):
        if name not in referenced and k.env not in referenced:
            findings.append(_f(
                "KNOB-DEAD", "horovod_tpu/common/config.py:registry",
                f"registered knob '{name}' ({k.env}) has no reader "
                "anywhere in the package — its CLI flag "
                "and doc row promise behavior that does not exist",
                "wire the knob up or delete the registration",
                severity="warning"))
    return findings


def _referenced_strings(root: str) -> set:
    """Every string constant in the package (minus config.py) — the
    read-evidence corpus for KNOB-DEAD."""
    out: set = set()
    paths = [p for p in _package_files(os.path.join(root, "horovod_tpu"))
             if not p.replace(os.sep, "/").endswith("common/config.py")]
    for path in paths:
        try:
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str):
                out.add(node.value)
    return out


def _key_component_funcs(mods: _Modules, xla: ModuleIndex) -> list:
    """FuncInfo seeds for every function whose result lands in a
    ``key = (...)`` program-cache tuple in xla_exec — directly
    (``zero_cfg()`` inline) or through one local assignment
    (``comp = _wire_compression(...)`` then ``key = (..., comp)``)."""
    seeds = []
    for fi in xla.funcs.values():
        assigns = {}
        key_tuples = []
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                tname = node.targets[0].id
                assigns.setdefault(tname, []).append(node.value)
                if tname == "key" and isinstance(node.value, ast.Tuple):
                    key_tuples.append(node.value)
        for tup in key_tuples:
            exprs = list(tup.elts)
            for el in tup.elts:
                if isinstance(el, ast.Name):
                    exprs.extend(assigns.get(el.id, []))
            for expr in exprs:
                for sub in ast.walk(expr):
                    if isinstance(sub, ast.Call):
                        callee = mods.resolve(xla, sub.func)
                        if callee is not None:
                            seeds.append(callee)
                            # dynamic-get helpers pick their knob from
                            # the call site ("_hier_topology(<knob>)")
                            if callee.dynamic_get:
                                for a in _const_str_args(sub):
                                    callee.config_reads.add(a)
    return seeds


def _calls_name(idx: ModuleIndex, name: str) -> bool:
    for node in ast.walk(idx.tree):
        if isinstance(node, ast.Call):
            fn = node.func
            if (isinstance(fn, ast.Name) and fn.id == name) or \
                    (isinstance(fn, ast.Attribute) and fn.attr == name):
                return True
    return False


def _calls_attr(idx: ModuleIndex, attr: str) -> bool:
    for node in ast.walk(idx.tree):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr == attr:
            return True
    return False


def _docs_corpus(root: str) -> str:
    chunks = []
    docdir = os.path.join(root, "docs")
    if os.path.isdir(docdir):
        for fn in sorted(os.listdir(docdir)):
            if fn.endswith(".md"):
                with open(os.path.join(docdir, fn)) as f:
                    chunks.append(f.read())
    for fn in ("README.md",):
        p = os.path.join(root, fn)
        if os.path.exists(p):
            with open(p) as f:
                chunks.append(f.read())
    return "\n".join(chunks)
