"""The quickest proof that the trainer still starts on the chip.

    python chip_smoke.py              # TPU machine: every stage, exit 0
    python chip_smoke.py --rehearsal  # CPU, toy sizes: control flow only

Drives the normal entry points once, at the full width of models the
repo already has: ``hvd.init()``; the ResNet-50 data-parallel trainer
exactly as ``examples/jax_synthetic_benchmark.py`` builds it
(``hvd.DistributedOptimizer`` over ``hvd.world_mesh()``); the flagship
transformer through ``make_train_step`` with XLA attention (seq 1024)
and the Pallas ring/flash path (seq 8192); every Pallas kernel the repo
owns against the jnp reference beside it; and, with more than one chip
visible, the flagship over ``dp`` / ``dp x tp`` meshes in one process
and ``python -m horovod_tpu.run -np <n>`` one process per chip.

Processes.  A chip belongs to one process at a time, so this parent
never initialises a JAX backend: the one-process stages share a worker
on purpose (``--worker single``), and the launcher stage starts only
after that worker has exited.

Every stage prints one JSON line.  A stage that raises, hangs past its
bound, produces a non-finite or non-decreasing loss, or runs a kernel
interpreted fails the run; so does a backend other than ``tpu``, also
under ``JAX_PLATFORMS=cpu``.  The last line of a passing run is
``{"ok": true, "device": {...}}``.  Seconds printed here are wall-clock
including compilation — not benchmark results.
"""

from __future__ import annotations

import argparse
import hashlib
import importlib.util
import json
import os
import signal
import subprocess
import sys
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

BUDGET_S = 1140          # whole run, compilation included (contract: 1200)
MOSAIC_CALL = "tpu_custom_call"

LM_FULL = dict(vocab=32768, d_model=768, n_heads=12, head_dim=64,
               n_layers=12, d_ff=3072)
LM_TOY = dict(vocab=256, d_model=64, n_heads=4, head_dim=16, n_layers=2,
              d_ff=128)
RESNET50_PARAMS = 25_557_032   # the ResNet-50-sized flat buffer


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


# ---------------------------------------------------------------------------
# Stage runner (inside a worker that owns the chip)
# ---------------------------------------------------------------------------

_failed: list = []


def stage(name: str, bound_s: float, fn, *args) -> None:
    """Run one stage under a watchdog.  An exception is recorded and the
    remaining stages still run (one chip call then shows every failure);
    the exit code is non-zero either way.  A hang past ``bound_s`` ends
    the process: a wedged device call cannot be interrupted in-process."""
    def expired():
        emit(stage=name, status="failed", error=f"hung past {bound_s}s")
        os._exit(3)

    dog = threading.Timer(bound_s, expired)
    dog.daemon = True
    dog.start()
    t0 = time.monotonic()
    try:
        facts = fn(*args) or {}
        emit(stage=name, status="ok", wall_s=round(time.monotonic() - t0, 1),
             **facts)
    except Exception as exc:
        traceback.print_exc()
        _failed.append(name)
        emit(stage=name, status="failed",
             wall_s=round(time.monotonic() - t0, 1),
             error=f"{type(exc).__name__}: {exc}"[:2000])
    finally:
        dog.cancel()


def check_losses(losses: list) -> dict:
    import numpy as np

    if not all(np.isfinite(v) for v in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"loss on the fixed batch did not fall: {losses}")
    return {"loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4), "steps": len(losses)}


def compiled_with_mosaic(compiled, want: bool, rehearsal: bool) -> bool:
    """Whether the compiled program's text holds the Mosaic custom call.
    On the chip a kernel stage requires it (an interpreted kernel lowers
    to plain HLO and has none); the XLA-attention step must not have it.
    The rehearsal interprets kernels by design."""
    has = MOSAIC_CALL in compiled.as_text()
    if not rehearsal and has != want:
        raise AssertionError(
            f"Mosaic custom call {'missing from' if want else 'found in'} "
            "the compiled program")
    return has


# ---------------------------------------------------------------------------
# Stages
# ---------------------------------------------------------------------------


def stage_device(rehearsal: bool) -> dict:
    import jax
    import jaxlib

    import horovod_tpu as hvd
    from horovod_tpu.common.platform import ensure_compile_cache
    from horovod_tpu.runtime.aot_cache import versions

    hvd.init()
    devs = jax.devices()
    facts = {
        "platform": devs[0].platform, "device_kind": devs[0].device_kind,
        "visible_devices": len(devs), "hvd_size": hvd.size(),
        "world_mesh_devices": int(hvd.world_mesh().devices.size),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": versions()[2],
        "compile_cache_dir": ensure_compile_cache(),
    }
    if rehearsal:
        facts["rehearsal"] = True
    elif devs[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU found: JAX's default backend is "
            f"{devs[0].platform!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r})")
    return facts


def _load_example():
    spec = importlib.util.spec_from_file_location(
        "jax_synthetic_benchmark",
        os.path.join(REPO, "examples", "jax_synthetic_benchmark.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_resnet(rehearsal: bool, warmup: int = 1, steps: int = 8):
    """The product path: ``build_trainer`` from the synthetic-benchmark
    example, warm-up, then ``steps`` steps ending in block_until_ready.
    Returns (facts, final params)."""
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd

    kw = (dict(model_name="SmallCNN", batch_size=4, image_side=32)
          if rehearsal else dict(model_name="ResNet50", batch_size=256))
    step, state, batch = _load_example().build_trainer(hvd, **kw)
    compiled = step.lower(*state, *batch, jnp.int32(0)).compile()
    losses = []
    for i in range(warmup + steps):
        *state, loss = compiled(*state, *batch, jnp.int32(i))
        losses.append(loss)
    jax.block_until_ready(state)
    losses = [float(v[0]) for v in losses]
    facts = check_losses(losses)
    facts.update(model=kw["model_name"], batch_per_chip=kw["batch_size"],
                 world=hvd.size())
    return facts, state[0]


def run_lm(rehearsal: bool, batch: int, seq: int, mesh_axes: dict,
           want_mosaic: bool, attn_impl=None, steps: int = 3) -> dict:
    """Flagship transformer through ``make_train_step`` on a
    ``make_mesh(**mesh_axes)`` mesh: 1 warm-up + ``steps`` steps."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models.transformer import (TransformerConfig,
                                                init_params,
                                                make_train_step,
                                                shard_params)
    from horovod_tpu.parallel.mesh import make_mesh

    n = int(np.prod(list(mesh_axes.values())))
    cfg = TransformerConfig(max_seq=seq, attn_impl=attn_impl,
                            **(LM_TOY if rehearsal else LM_FULL))
    mesh = make_mesh(**mesh_axes, devices=jax.devices()[:n])
    opt = optax.adamw(3e-4)
    params = shard_params(
        init_params(np.random.RandomState(0), cfg, ep=1), cfg, mesh)
    opt_state = opt.init(params)
    rng = np.random.RandomState(1)
    sh = NamedSharding(mesh, P("dp", "sp"))
    tokens, targets = (
        jax.device_put(jnp.asarray(
            rng.randint(0, cfg.vocab, (batch, seq)), jnp.int32), sh)
        for _ in range(2))
    compiled = make_train_step(cfg, mesh, opt).lower(
        params, opt_state, tokens, targets).compile()
    mosaic = compiled_with_mosaic(compiled, want_mosaic, rehearsal)
    losses = []
    for _ in range(1 + steps):
        params, opt_state, loss = compiled(params, opt_state, tokens,
                                           targets)
        losses.append(loss)
    jax.block_until_ready(params)
    facts = check_losses([float(v) for v in losses])
    facts.update(mesh=mesh_axes, batch=batch, seq=seq,
                 mosaic_custom_call=mosaic, layers=cfg.n_layers,
                 d_model=cfg.d_model)
    return facts


def _rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.linalg.norm(got - ref)
                 / max(float(np.linalg.norm(ref)), 1e-30))


def _close(got, ref, tol: float) -> float:
    """CPU-test tolerance (rtol=atol=tol, elementwise) plus a scale-free
    bound, so a long-sequence output near zero cannot hide in atol."""
    import numpy as np

    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    rel = _rel_err(got, ref)
    if not rel < tol:
        raise AssertionError(f"relative error {rel:.3e} >= {tol}")
    return round(rel, 6)


def kernel_flash(rehearsal: bool, bh: int, seq: int, d: int,
                 ref_heads: int) -> dict:
    """flash forward + both backward kernels at (bh, seq, d) bf16,
    against ``xla_block_step`` and its autodiff on the first
    ``ref_heads`` rows (the dense reference's score block is
    O(seq^2))."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.pallas_attention import (flash_bwd_dkv,
                                                  flash_bwd_dq,
                                                  flash_fwd_step)
    from horovod_tpu.parallel.ring_attention import (_block_sizes,
                                                     xla_block_step)

    rng = np.random.RandomState(0)
    q, k, v, dout = (jnp.asarray(rng.randn(bh, seq, d), jnp.bfloat16)
                     for _ in range(4))
    bq, bk = _block_sizes(seq, seq, d, q.dtype.itemsize)
    m0 = jnp.full((bh, seq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bh, seq), jnp.float32)
    o0 = jnp.zeros((bh, seq, d), jnp.float32)
    tol = 2e-2  # tests/test_pallas_attention.py, bf16 inputs

    def norm(m, l, o):
        lse = m + jnp.log(l)          # causal: every row sees >= 1 key
        return o / l[..., None], lse

    fwd = jax.jit(lambda q, k, v: norm(*flash_fwd_step(
        q, k, v, (m0, l0, o0), 0, 0, causal=True, block_q=bq,
        block_k=bk))).lower(q, k, v).compile()
    compiled_with_mosaic(fwd, True, rehearsal)
    out, lse = fwd(q, k, v)
    delta = jnp.sum(dout.astype(jnp.float32) * out, axis=-1)

    bwd_kw = dict(causal=True, block_q=bq, block_k=bk)
    dq_c = jax.jit(lambda *a: flash_bwd_dq(*a, 0, 0, **bwd_kw)).lower(
        q, k, v, dout, lse, delta).compile()
    compiled_with_mosaic(dq_c, True, rehearsal)
    dkv_c = jax.jit(lambda *a: flash_bwd_dkv(*a, 0, 0, **bwd_kw)).lower(
        q, k, v, dout, lse, delta).compile()
    compiled_with_mosaic(dkv_c, True, rehearsal)
    dq = dq_c(q, k, v, dout, lse, delta)
    dk, dv = dkv_c(q, k, v, dout, lse, delta)

    # K/V gradients sum over every query row of a head, so the
    # reference takes whole heads
    r = ref_heads
    ref = lambda q, k, v: norm(*xla_block_step(
        q, k, v, m0[:r], l0[:r], o0[:r], 0, 0, causal=True))[0]
    eout, vjp = jax.vjp(ref, q[:r], k[:r], v[:r])
    edq, edk, edv = vjp(dout[:r].astype(jnp.float32))
    return {"shape": [bh, seq, d], "block": [bq, bk],
            "fwd_rel_err": _close(out[:r], eout, tol),
            "dq_rel_err": _close(dq[:r], edq, tol),
            "dk_rel_err": _close(dk[:r], edk, tol),
            "dv_rel_err": _close(dv[:r], edv, tol)}


def _equal(got, want) -> str:
    """Bit equality, as the CPU tests assert it; a mismatch says how
    many elements differ and by how much."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    bad = got != want
    if bad.any():
        g, w = got[bad].astype(np.float64), want[bad].astype(np.float64)
        raise AssertionError(
            f"{int(bad.sum())} of {bad.size} elements differ; max abs "
            f"{np.abs(g - w).max():.3e}, max rel "
            f"{(np.abs(g - w) / np.maximum(np.abs(w), 1e-30)).max():.3e}")
    return "equal"


def kernel_quant(rehearsal: bool, n: int) -> dict:
    """int8 quantize/dequantize and int4 pack/unpack over an n-element
    flat fp32 buffer against the jnp codecs beside them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import quantization as Q

    block = Q.resolve_block_size()
    x2d, _ = Q._to_blocks(
        jnp.asarray(np.random.RandomState(0).randn(n), jnp.float32), block)
    s8 = Q.block_absmax(x2d) / 127
    s4 = Q.block_absmax(x2d) / 7
    facts = {"elements": n, "block": block}

    def check(name, fn, ref, *a):
        compiled = jax.jit(fn).lower(*a).compile()
        compiled_with_mosaic(compiled, True, rehearsal)
        got = compiled(*a)
        facts[name] = _equal(got, jax.jit(ref)(*a))
        return got

    q8 = check("quantize", lambda x, s: Q.quantize_values(x, s, 127),
               lambda x, s: Q._quantize_jnp(x, s, 127), x2d, s8)
    check("dequantize", Q.dequantize_values, Q._dequantize_jnp, q8, s8)
    p4 = check("pack4", lambda x, s: Q.quantize_pack4_values(x, s, 7),
               lambda x, s: Q._quantize_pack4_jnp(x, s, 7), x2d, s4)
    check("unpack4", Q.unpack_dequantize4_values,
          Q._unpack_dequantize4_jnp, p4, s4)
    return facts


def kernel_fused_update(rehearsal: bool, n: int) -> dict:
    """Fused SGD / momentum / Adam tail on an n-element fp32 leaf
    against the optax chain it replaces.  Multiplies and adds agree to
    the bit, as the CPU tests assert.  Adam's update also divides and
    takes a square root, which Mosaic and XLA round differently on the
    chip (a few ulp; 4.7e-7 relative at the first contact, PR 21), so
    that one leaf is held to ``ADAM_RTOL`` instead; its moments stay
    exact."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd

    ADAM_RTOL = 2e-6
    fu = hvd.fused_update
    rng = np.random.RandomState(0)
    grads = {"w": jnp.asarray(rng.randn(n), jnp.float32)}
    params = {"w": jnp.asarray(rng.randn(n), jnp.float32)}
    facts = {"elements": n}
    for name, opt in (("sgd", fu.sgd(0.1)),
                      ("momentum", fu.sgd(0.1, momentum=0.9)),
                      ("adam", fu.adam(1e-3))):
        # second-step state, so the moments are not all zero
        _, state = opt.update(grads, opt.init(params), params)
        compiled = jax.jit(
            lambda g, s, o=opt: fu.fused_update_tree(o.fused_spec, g, s)
        ).lower(grads, state).compile()
        compiled_with_mosaic(compiled, True, rehearsal)
        upd, new_state = compiled(grads, state)
        eupd, estate = jax.jit(
            lambda g, s, p, o=opt: o.update(g, s, p))(grads, state, params)
        for a, b in zip(jax.tree_util.tree_leaves(new_state),
                        jax.tree_util.tree_leaves(estate)):
            _equal(a, b)
        if name == "adam":
            np.testing.assert_allclose(np.asarray(upd["w"]),
                                       np.asarray(eupd["w"]),
                                       rtol=ADAM_RTOL, atol=0)
            facts[name] = (f"moments equal, update rel_err "
                           f"{_rel_err(upd['w'], eupd['w']):.1e}")
        else:
            facts[name] = _equal(upd["w"], eupd["w"])
    return facts


# ---------------------------------------------------------------------------
# Workers (each owns the chip for its lifetime)
# ---------------------------------------------------------------------------


def worker_single(rehearsal: bool) -> int:
    """Every one-process stage, in one process that opens the chip
    once."""
    hits = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event.endswith("/cache_hits"):
            hits["hits"] += 1
        elif event.endswith("/cache_misses"):
            hits["misses"] += 1

    stage("device", 300, stage_device, rehearsal)
    if _failed:
        return 1      # no TPU: nothing below may print a result
    import jax
    from jax import monitoring

    monitoring.register_event_listener(on_event)
    n = len(jax.devices())
    r = rehearsal
    if r:   # auto picks the jnp codecs off-TPU; ask for the kernels
        os.environ["HOROVOD_QUANT_PALLAS"] = "1"
    short, long_ = ((2, 32), (1, 128)) if r else ((16, 1024), (1, 8192))
    flat = 70_000 if r else RESNET50_PARAMS
    lm = LM_TOY if r else LM_FULL

    for b, s in (short, long_):
        stage(f"kernel:flash@b{b}xseq{s}", 300, kernel_flash, r,
              b * lm["n_heads"], s, lm["head_dim"], 2)
    stage("kernel:quant", 300, kernel_quant, r, flat)
    stage("kernel:fused_update", 300, kernel_fused_update, r, flat)
    stage("trainer:resnet50", 600, lambda: run_resnet(r)[0])
    one = dict(dp=1, pp=1, tp=1, sp=1)
    stage("trainer:lm_xla_attention", 600, run_lm, r, *short, one, False)
    # the auto pick chooses the Pallas path from the score-block size on
    # a TPU backend; the CPU rehearsal has to ask for it
    stage("trainer:lm_pallas_attention", 600, run_lm, r, *long_, one,
          True, "pallas" if r else None)
    if n >= 2:
        stage(f"mesh:dp{n}", 600, run_lm, r, max(short[0] // n, 1) * n,
              short[1], dict(dp=n, pp=1, tp=1, sp=1), False)
    else:
        emit(stage="mesh:dp", status="skipped: 1 chip visible")
    if n == 4:
        stage("mesh:dp2_tp2", 600, run_lm, r, short[0], short[1],
              dict(dp=2, pp=1, tp=2, sp=1), False)
    else:
        emit(stage="mesh:dp2_tp2",
             status=f"skipped: {n} chip{'s' if n > 1 else ''} visible")
    emit(stage="compile_cache:worker", status="ok", **hits)
    return 1 if _failed else 0


def worker_rank(rehearsal: bool) -> int:
    """One rank of ``python -m horovod_tpu.run -np <n>``: one process,
    one chip, the ResNet-50 trainer over the n-process world."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd

    def body():
        hvd.init()
        n, rank = hvd.size(), hvd.rank()
        local, world = jax.local_devices(), jax.devices()
        if not rehearsal and world[0].platform != "tpu":
            raise RuntimeError(f"no TPU found: {world[0].platform!r}")
        total = float(np.asarray(hvd.allreduce(
            jnp.asarray([float(rank)]), op=hvd.Sum, name="smoke.rank"))[0])
        if len(local) != 1 or len(world) != n \
                or total != n * (n - 1) / 2:
            raise AssertionError(
                f"rank {rank}: {len(local)} local / {len(world)} global "
                f"devices in a world of {n}, psum(rank)={total}")
        facts, params = run_resnet(rehearsal, steps=5)
        digest = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(params):
            digest.update(np.asarray(leaf.addressable_data(0)).tobytes())
        facts.update(rank=rank, local_devices=1, global_devices=n,
                     device_id=local[0].id, psum_rank=total,
                     platform=world[0].platform,
                     params_sha256=digest.hexdigest()[:16])
        return facts

    stage("launcher:rank", 600, body)
    hvd.shutdown()
    return 1 if _failed else 0


# ---------------------------------------------------------------------------
# Parent (never touches a JAX backend)
# ---------------------------------------------------------------------------

_children: list = []


def run_child(cmd: list, env: dict, bound_s: float) -> tuple:
    """Run one child to its end (or its bound), echoing its output;
    returns (exit code, the stage records it printed)."""
    proc = subprocess.Popen(cmd, env=env, cwd=REPO, stdout=subprocess.PIPE,
                            text=True, start_new_session=True)
    _children.append(proc)
    records = []

    def pump():
        for line in proc.stdout:
            print(line, end="", flush=True)
            at = line.find('{"stage"')   # hvdrun prefixes "[rank]<stdout>:"
            if at >= 0:
                try:
                    records.append(json.loads(line[at:]))
                except ValueError:
                    pass

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        rc = proc.wait(timeout=bound_s)
    except subprocess.TimeoutExpired:
        emit(stage="child", status="failed",
             error=f"{cmd[1:4]} ran past {bound_s:.0f}s")
        rc = 124
    finally:
        stop_children()
    t.join(timeout=10)
    return rc, records


def stop_children() -> None:
    for proc in _children:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        proc.wait()
    _children.clear()


def cache_entries(path: str) -> int:
    try:
        return sum(1 for f in os.listdir(path) if not f.endswith("-atime"))
    except OSError:
        return 0


def parent(rehearsal: bool) -> int:
    t_end = time.monotonic() + BUDGET_S
    # resolves the path only; importing the package starts no backend
    from horovod_tpu.common.platform import ensure_compile_cache

    cache = ensure_compile_cache()
    before = cache_entries(cache)
    env = dict(os.environ)
    flags = ["--rehearsal"] if rehearsal else []
    if rehearsal:
        env.update(JAX_PLATFORMS="cpu", HOROVOD_PLATFORM="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
    me = [sys.executable, os.path.abspath(__file__)]

    rc, records = run_child(me + ["--worker", "single"] + flags, env,
                            t_end - time.monotonic() - 240)
    device = next((r for r in records if r.get("stage") == "device"), {})
    if device.get("status") != "ok":
        print("chip_smoke: no TPU found — nothing was measured",
              file=sys.stderr)
        return rc or 1
    ok = rc == 0
    n = device["visible_devices"]

    if n >= 2:
        renv = dict(env)
        if rehearsal:
            renv["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        rc, ranks = run_child(
            [sys.executable, "-m", "horovod_tpu.run", "-np", str(n), "--"]
            + me + ["--worker", "rank"] + flags, renv,
            t_end - time.monotonic() - 20)
        ranks = [r for r in ranks if r.get("status") == "ok"]
        ids = {r["device_id"] for r in ranks}
        digests = {r["params_sha256"] for r in ranks}
        launched = (rc == 0 and len(ranks) == n and len(ids) == n
                    and len(digests) == 1)
        emit(stage=f"launcher:hvdrun_np{n}",
             status="ok" if launched else "failed", exit_code=rc,
             ranks_ok=len(ranks), device_ids=sorted(ids),
             params_identical=len(digests) == 1)
        ok = ok and launched
    else:
        emit(stage="launcher:hvdrun", status="skipped: 1 chip visible")

    emit(stage="compile_cache", status="ok", dir=cache,
         entries_before=before, entries_after=cache_entries(cache))
    if not ok:
        print("chip_smoke: FAILED — see the stage lines above",
              file=sys.stderr)
        return 1
    result = {"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": n}}
    if rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearsal", action="store_true",
                   help="toy sizes on forced CPU devices: checks this "
                        "script's control flow, proves nothing about "
                        "the chip; every line says rehearsal")
    p.add_argument("--worker", choices=["single", "rank"],
                   help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.worker == "single":
        return worker_single(args.rehearsal)
    if args.worker == "rank":
        return worker_rank(args.rehearsal)
    try:
        return parent(args.rehearsal)
    finally:
        stop_children()


if __name__ == "__main__":
    sys.exit(main())
