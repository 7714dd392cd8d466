"""The CPU-lowered program set the ``hlo`` pass lints.

Re-lowers the representative negotiated-data-plane programs on the
virtual 8-device CPU mesh (the same shapes the acceptance tests prove)
and evaluates the hlo_lint rule presets against each:

* ZeRO-2 update        — no full fused gradient buffer, bucketed RS/AG
* ZeRO-3 forward       — bucketed parameter gathers, no full buffer
* overlap schedule     — >= K permute stages, zero all-reduce
* hierarchical int8    — lossy payload on the cross hop only
* hierarchical top-k   — sparse payload on the cross hop only

Every preset also runs a POSITIVE CONTROL: the stage-1 program (which
demonstrably carries the full buffer), the overlap-off program (which
is monolithic by contract) and a deliberately flat lossy psum must be
FLAGGED.  A checker that stops seeing violations fails its own pass
(``HLO-SELFCHECK``) instead of passing vacuously — the failure mode
regex scans could never report.

Lowering only (no compile, no execution): the whole set takes seconds.
"""

from __future__ import annotations

import os
import sys

from horovod_tpu.analysis import hlo_lint as HL
from horovod_tpu.analysis.findings import Finding

_LEAVES, _LEAF = 4, 96
_PADDED = _LEAVES * _LEAF
_N, _CROSS, _LOCAL = 8, 2, 4


def _selfcheck(label: str, violated: list) -> list:
    if violated:
        return []
    return [Finding(
        rule="HLO-SELFCHECK", severity="error",
        location=f"program:{label}",
        message=f"positive control '{label}' produced zero findings — "
                "the checker can no longer see the violation class it "
                "exists to catch",
        fix_hint="the HLO parser or rule drifted from what jax lowers; "
                 "fix hlo_lint before trusting any green result",
        pass_name="hlo")]


def _ensure_backend() -> None:
    # Importing jax does NOT initialize the backend; XLA_FLAGS is read
    # at first device access, so setting it here works even though the
    # package import already pulled jax in.  Only a process whose
    # backend is ALREADY live with fewer devices (unusual embedding)
    # cannot be fixed up — fail with the recipe.
    os.environ.setdefault("HOROVOD_PLATFORM", "cpu")
    if "jax" not in sys.modules:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()
    from horovod_tpu.common.platform import ensure_platform

    ensure_platform()
    import jax

    if len(jax.devices()) < _N:
        raise RuntimeError(
            f"hlo pass needs >= {_N} devices (have {len(jax.devices())}): "
            "run in a fresh process so XLA_FLAGS can force the virtual "
            "CPU mesh")


def run() -> list:
    _ensure_backend()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.common import config as _config
    from horovod_tpu.ops import collectives as coll
    from horovod_tpu.ops import quantization as q

    mesh = Mesh(np.array(jax.devices()[:_N]), ("hvd",))
    hmesh = Mesh(np.array(jax.devices()[:_N]).reshape(_CROSS, _LOCAL),
                 ("cross", "local"))
    k = max(1, int(_config.get("zero_prefetch_chunks")))
    ok = max(1, int(_config.get("overlap_chunks")))
    findings = []

    def opt_hlo(stage: int, overlap: bool) -> str:
        params = {f"l{i}": jnp.ones((_LEAF,), jnp.float32) * (i + 1)
                  for i in range(_LEAVES)}
        opt = hvd.DistributedOptimizer(optax.sgd(0.1), axis_name="hvd",
                                       zero_stage=stage, overlap=overlap)

        def body(t):
            st = opt.init(params)
            g = jax.tree_util.tree_map(lambda p: p * t[0, 0], params)
            upd, _ = opt.update(g, st)
            return upd["l0"].reshape(1, -1)

        fn = jax.jit(shard_map(body, mesh=mesh, check_vma=False,
                               in_specs=P("hvd"), out_specs=P("hvd")))
        return fn.lower(jnp.zeros((_N, 1), jnp.float32)).as_text("hlo")

    # -- ZeRO-2 residency ------------------------------------------------
    h2 = opt_hlo(2, overlap=False)
    findings += HL.check_program(h2, HL.zero2_rules(_PADDED, k,
                                                    label="zero2-update"))
    h1 = opt_hlo(1, overlap=False)
    findings += _selfcheck(
        "zero1-full-buffer-control",
        HL.check_program(h1, [HL.no_full_buffer(_PADDED,
                                                label="zero1-control")]))

    # -- ZeRO-3 residency ------------------------------------------------
    from horovod_tpu.optim import distributed as D

    params = {f"l{i}": jnp.ones((_LEAF,), jnp.float32)
              for i in range(_LEAVES)}
    pl, treedef = jax.tree_util.tree_flatten(params)
    layout = D._shard_layout(pl, _N)
    shapes3 = tuple(tuple(l.shape) for l in pl)

    def fwd(shard_block, t):
        zp = D.Zero3Params([shard_block[0]], layout, treedef, shapes3)
        full = D.zero3_full_params(zp)
        return sum(jnp.sum(l * t[0, 0])
                   for l in jax.tree_util.tree_leaves(full)).reshape(1)

    fn3 = jax.jit(shard_map(fwd, mesh=mesh, check_vma=False,
                            in_specs=(P("hvd"), P("hvd")),
                            out_specs=P("hvd")))
    h3 = fn3.lower(jnp.zeros((_N, _PADDED // _N), jnp.float32),
                   jnp.zeros((_N, 1), jnp.float32)).as_text("hlo")
    findings += HL.check_program(h3, HL.zero3_rules(_PADDED, k,
                                                    label="zero3-forward"))

    # -- overlap schedule ------------------------------------------------
    hov = opt_hlo(0, overlap=True)
    findings += HL.check_program(hov, HL.overlap_rules(ok,
                                                       label="overlap"))
    hoff = opt_hlo(0, overlap=False)
    findings += _selfcheck(
        "overlap-off-monolithic-control",
        HL.check_program(hoff, [HL.no_collective("all-reduce",
                                                 label="overlap-control")]))

    # -- hierarchical lossy placement ------------------------------------
    old = _config.get("hierarchical_allreduce")
    _config.set_knob("hierarchical_allreduce", True)
    try:
        for mode in ("int8", "topk"):
            fnh = jax.jit(shard_map(
                lambda b, _m=mode: coll.quantized_allreduce(
                    b[0], axis_name=("cross", "local"), op=coll.Sum,
                    mode=_m),
                mesh=hmesh, check_vma=False,
                in_specs=P(("cross", "local")), out_specs=P()))
            hh = fnh.lower(
                jnp.zeros((_N, 1024), jnp.float32)).as_text("hlo")
            findings += HL.check_program(
                hh, HL.hierarchical_lossy_rules(_LOCAL,
                                                label=f"hier-{mode}"))
    finally:
        _config.set_knob("hierarchical_allreduce", old)

    # positive control: a flat (whole-world) int8 psum must be flagged
    fnc = jax.jit(shard_map(
        lambda b: q.lossy_psum(b[0].reshape(-1), "hvd", "int8", 256),
        mesh=mesh, check_vma=False, in_specs=P("hvd"), out_specs=P()))
    hc = fnc.lower(jnp.zeros((_N, 1024), jnp.float32)).as_text("hlo")
    findings += _selfcheck(
        "flat-lossy-placement-control",
        HL.check_program(hc, [HL.lossy_cross_only(
            _LOCAL, label="placement-control")]))

    # -- mesh-native dp placement (docs/mesh.md) -------------------------
    # On a dp:4,tp:2 mesh every gradient collective must ride proper dp
    # subgroups ({0,2,4,6},{1,3,5,7} on this layout), never the whole
    # 8-device world — a world-spanning reduce would average params
    # that are sharded over tp.
    _DP = _N // 2
    dmesh = Mesh(np.array(jax.devices()[:_N]).reshape(_DP, 2),
                 ("dp", "tp"))

    def mesh_opt_hlo(stage: int) -> str:
        params = {f"l{i}": jnp.ones((_LEAF,), jnp.float32) * (i + 1)
                  for i in range(_LEAVES)}
        opt = hvd.DistributedOptimizer(optax.sgd(0.1), axis_name="dp",
                                       zero_stage=stage)

        def body(t):
            st = opt.init(params)
            g = jax.tree_util.tree_map(lambda p: p * t[0, 0], params)
            upd, _ = opt.update(g, st)
            return upd["l0"].reshape(1, -1)

        fn = jax.jit(shard_map(body, mesh=dmesh, check_vma=False,
                               in_specs=P("dp"), out_specs=P("dp")))
        return fn.lower(jnp.zeros((_DP, 1), jnp.float32)).as_text("hlo")

    for stage in (0, 2):
        findings += HL.check_program(
            mesh_opt_hlo(stage),
            HL.mesh_placement_rules(_N, label=f"mesh-dp-z{stage}"))
    # positive control: the flat-world monolithic update spans all 8
    # devices, so the dp-subgroup rule must flag it
    findings += _selfcheck(
        "flat-world-placement-control",
        HL.check_program(hoff, [HL.dp_subgroups(
            _N, label="mesh-placement-control")]))

    return findings
