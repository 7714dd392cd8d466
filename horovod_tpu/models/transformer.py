"""Flagship model: decoder-only transformer LM with composable
dp / tp / sp / pp / ep parallelism, written TPU-first.

The reference ships CNN benchmark models driven by DP alone
(``examples/tensorflow2_synthetic_benchmark.py``); this model is the
framework's demonstration that every SURVEY §2.7 strategy composes in
one train step:

  * **dp** — batch sharded; gradients psum over ``dp`` (the Horovod
    core capability, here traced into the step).
  * **tp** — Megatron-style: QKV/MLP-in column-parallel, proj/MLP-out
    row-parallel with one psum per block over ``tp``.
  * **sp** — sequence sharded; ring attention over ``sp``
    (:mod:`horovod_tpu.parallel.ring_attention`).
  * **pp** — layer stack split into stages, GPipe microbatching
    (:mod:`horovod_tpu.parallel.pipeline`) when the ``pp`` axis > 1.
  * **ep** — optional Switch-MoE MLP with experts sharded over the
    ``dp`` axis (:mod:`horovod_tpu.parallel.moe`).

Everything is bf16 matmuls with fp32 accumulation/norms — MXU-native.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel.moe import moe_layer
from horovod_tpu.parallel.pipeline import gpipe, interleaved_pipeline
from horovod_tpu.parallel.ring_attention import ring_attention
from horovod_tpu.parallel.sharding import (copy_to_tp, grad_reduce_axes,
                                           reduce_from_tp,
                                           tree_map_with_specs)


@dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    d_model: int = 512
    n_heads: int = 8
    head_dim: int = 64
    n_layers: int = 8
    d_ff: int = 2048
    max_seq: int = 2048
    dtype: str = "bfloat16"
    # attention block-step impl: None = auto (pallas on TPU, xla
    # elsewhere); "xla" | "pallas" to force
    attn_impl: str | None = None
    # MoE (ep over the dp axis); 0 disables
    moe_every: int = 0
    experts_per_rank: int = 2
    pp_microbatches: int = 2  # microbatches per pipeline stage when pp>1
    # pipeline schedule when pp>1: "gpipe" (fill-drain) or "interleaved"
    # (Megatron virtual stages, pp_virtual chunks per rank — bubble
    # shrinks ~pp_virtual-fold; layer storage is round-robin permuted by
    # shard_params so each rank's contiguous pp shard holds its chunks)
    pp_schedule: str = "gpipe"
    pp_virtual: int = 1
    # rematerialize each pipeline stage in backward (jax.checkpoint):
    # activation memory stops scaling with stage internals, at one
    # extra forward per stage
    pp_remat: bool = False

    def __post_init__(self):
        if self.pp_schedule not in ("gpipe", "interleaved"):
            raise ValueError(
                f"pp_schedule must be 'gpipe' or 'interleaved', got "
                f"{self.pp_schedule!r}")
        if self.pp_schedule == "gpipe" and self.pp_virtual != 1:
            raise ValueError(
                "pp_virtual > 1 requires pp_schedule='interleaved'")
        if self.pp_virtual < 1:
            raise ValueError(f"pp_virtual must be >= 1: {self.pp_virtual}")

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


def init_params(rng: np.random.RandomState, cfg: TransformerConfig,
                ep: int = 1) -> dict:
    """Full (unsharded) parameter pytree; shard_map in_specs split the
    tp/pp dimensions at dispatch."""
    dm, hd, nh, ff, nl = (cfg.d_model, cfg.head_dim, cfg.n_heads,
                          cfg.d_ff, cfg.n_layers)

    def norm(*shape, scale):
        return (rng.randn(*shape) * scale).astype(np.float32)

    p = {
        "embed": norm(cfg.vocab, dm, scale=0.02),
        "pos": norm(cfg.max_seq, dm, scale=0.02),
        "ln_f": np.ones(dm, np.float32),
        "layers": {
            "wqkv": norm(nl, dm, 3 * nh * hd, scale=dm ** -0.5),
            "wo": norm(nl, nh * hd, dm, scale=(nh * hd) ** -0.5),
            "w1": norm(nl, dm, ff, scale=dm ** -0.5),
            "w2": norm(nl, ff, dm, scale=ff ** -0.5),
            "ln1": np.ones((nl, dm), np.float32),
            "ln2": np.ones((nl, dm), np.float32),
        },
    }
    if cfg.moe_every:
        n_moe = sum(1 for i in range(nl) if (i + 1) % cfg.moe_every == 0)
        e = ep * cfg.experts_per_rank
        p["moe"] = {
            "router": norm(n_moe, dm, e, scale=dm ** -0.5),
            "w_in": norm(n_moe, e, dm, ff, scale=dm ** -0.5),
            "w_out": norm(n_moe, e, ff, dm, scale=ff ** -0.5),
        }
    return jax.tree_util.tree_map(jnp.asarray, p)


def param_specs(cfg: TransformerConfig):
    """PartitionSpecs for shard_map in_specs: tp shards the
    column/row-parallel matrices; MoE experts shard over dp (=ep)."""
    from jax.sharding import PartitionSpec as P

    specs = {
        "embed": P(),
        "pos": P(),
        "ln_f": P(),
        # layer stacks shard over pp (each stage holds only its layers)
        # and tp (column/row parallel matrices)
        "layers": {
            "wqkv": P("pp", None, "tp"),
            "wo": P("pp", "tp", None),
            "w1": P("pp", None, "tp"),
            "w2": P("pp", "tp", None),
            "ln1": P("pp"),
            "ln2": P("pp"),
        },
    }
    if cfg.moe_every:
        specs["moe"] = {
            "router": P(),
            "w_in": P(None, "dp"),
            "w_out": P(None, "dp"),
        }
    return specs


def _rmsnorm(x, g):
    x32 = x.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)
    return ((x32 / rms) * g).astype(x.dtype)


def _block(cfg: TransformerConfig, lp, x, moe_params=None):
    """One transformer block, per-device view.  x: (b, lc, dm)."""
    b, lc, dm = x.shape
    cd = cfg.compute_dtype
    tp = lax.axis_size("tp")
    nh_local = cfg.n_heads // tp

    h = _rmsnorm(x, lp["ln1"])
    h = copy_to_tp(h, "tp")  # Megatron "f": bwd sums shard contributions
    qkv = (h.astype(cd) @ lp["wqkv"].astype(cd))
    qkv = qkv.reshape(b, lc, 3, nh_local, cfg.head_dim)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    with jax.named_scope("hvd_attn"):
        attn = ring_attention(q, k, v, "sp", causal=True,
                              impl=cfg.attn_impl)
    attn = attn.reshape(b, lc, nh_local * cfg.head_dim)
    proj = (attn.astype(cd) @ lp["wo"].astype(cd)).astype(jnp.float32)
    proj = reduce_from_tp(proj, "tp")  # Megatron "g": row-parallel reduce
    x = x + proj.astype(x.dtype)

    h = _rmsnorm(x, lp["ln2"])
    if moe_params is not None:
        tokens = h.reshape(b * lc, dm)
        out, aux = moe_layer(tokens, moe_params["router"],
                             moe_params["w_in"], moe_params["w_out"],
                             axis_name="dp")
        mlp = out.reshape(b, lc, dm).astype(jnp.float32)
    else:
        h = copy_to_tp(h, "tp")
        ff = jax.nn.gelu((h.astype(cd) @ lp["w1"].astype(cd))
                         .astype(jnp.float32)).astype(cd)
        mlp = (ff @ lp["w2"].astype(cd)).astype(jnp.float32)
        mlp = reduce_from_tp(mlp, "tp")
        aux = jnp.float32(0.0)
    x = x + mlp.astype(x.dtype)
    return x, aux


def forward(params, tokens, cfg: TransformerConfig):
    """Per-device forward inside shard_map over ('dp','pp','tp','sp').

    tokens: (b_local, lc_local) int32.  Returns (logits fp32
    (b, lc, vocab), aux_loss).
    """
    cd = cfg.compute_dtype
    sp_idx = lax.axis_index("sp")
    nstages = lax.axis_size("pp")
    b, lc = tokens.shape
    pos = sp_idx * lc + jnp.arange(lc)
    x = (params["embed"][tokens] + params["pos"][pos]).astype(cd)

    layers = params["layers"]
    moe = params.get("moe")
    local_layers = layers["ln1"].shape[0]  # n_layers / pp per stage

    if nstages == 1:
        aux = jnp.float32(0.0)
        for i in range(local_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], layers)
            mp = None
            if moe is not None and (i + 1) % cfg.moe_every == 0:
                idx = sum(1 for j in range(i + 1)
                          if (j + 1) % cfg.moe_every == 0) - 1
                mp = jax.tree_util.tree_map(lambda a: a[idx], moe)
            x, a = _block(cfg, lp, x, mp)
            aux = aux + a
    else:
        if moe is not None:
            raise NotImplementedError(
                "MoE layers under pipeline parallelism are not supported "
                "yet; use moe_every=0 when pp > 1.")

        m = cfg.pp_microbatches
        micro = x.reshape(m, b // m, lc, cfg.d_model)
        if cfg.pp_schedule == "interleaved":
            V = cfg.pp_virtual
            per = local_layers // V
            # this rank's contiguous shard holds its V chunks in slot
            # order (shard_params applied interleave_layer_order)
            stacks = jax.tree_util.tree_map(
                lambda a: a.reshape((V, per) + a.shape[1:]), layers)

            def chunk_fn(cp, h):
                def one(j, hh):
                    lp = jax.tree_util.tree_map(lambda a: a[j], cp)
                    hh, _ = _block(cfg, lp, hh)
                    return hh

                return lax.fori_loop(0, per, one, h)

            x = interleaved_pipeline(chunk_fn, stacks, micro, V, "pp",
                                     remat=cfg.pp_remat)
        else:
            def stage_fn(_, h):
                def one(j, hh):
                    lp = jax.tree_util.tree_map(lambda a: a[j], layers)
                    hh, _ = _block(cfg, lp, hh)
                    return hh

                return lax.fori_loop(0, local_layers, one, h)

            x = gpipe(stage_fn, None, micro, "pp", remat=cfg.pp_remat)
        x = x.reshape(b, lc, cfg.d_model)
        aux = jnp.float32(0.0)

    x = _rmsnorm(x, params["ln_f"])
    with jax.named_scope("hvd_loss_head"):
        logits = (x.astype(cd)
                  @ params["embed"].astype(cd).T).astype(jnp.float32)
    return logits, aux


def loss_fn(params, tokens, targets, cfg: TransformerConfig):
    """LOCAL slice of the global-mean cross entropy.

    Deliberately psum-free: local token-loss sum divided by the GLOBAL
    token count (a static number), so that one explicit psum of the
    gradients reconstructs exactly the global-mean gradient.  Putting a
    psum inside the differentiated loss would double-count — psum
    transposes to psum, inflating gradients by the data-axis size.
    Report the global loss by psumming this value outside the grad.
    """
    logits, aux = forward(params, tokens, cfg)
    with jax.named_scope("hvd_loss_head"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None],
                                   axis=-1)[..., 0]
    data_ranks = lax.axis_size("dp") * lax.axis_size("sp")
    global_tokens = jnp.float32(nll.size) * data_ranks
    return jnp.sum(nll) / global_tokens + 0.01 * aux / data_ranks


def make_train_step(cfg: TransformerConfig, mesh, optimizer,
                    steps_per_dispatch: int = 1):
    """Build the jitted SPMD train step over a ('dp','pp','tp','sp')
    mesh.

    ``steps_per_dispatch > 1`` chains that many optimizer steps on the
    same batch inside one compiled program (``lax.scan``), returning the
    last loss — for synthetic benchmarking, where each dispatch pays a
    host round-trip (cf. the reference's fixed-batch synthetic bench,
    ``examples/tensorflow2_synthetic_benchmark.py:119-132``).

    shard_map covers loss+grad (where the collectives live); the optax
    update runs outside it under the same jit, so XLA propagates the
    parameter shardings through the elementwise optimizer math — the
    "weight update sharding" pattern (cf. PAPERS.md, automatic
    cross-replica weight-update sharding).

    Returns step_fn(params, opt_state, tokens, targets) ->
    (params, opt_state, loss_scalar).  params/opt_state must be placed
    with :func:`shard_params` before the first call.
    """
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    pspecs = param_specs(cfg)
    data_spec = P("dp", "sp")

    def per_device_grads(params, tokens, targets):
        local_loss, grads = jax.value_and_grad(loss_fn)(params, tokens,
                                                        targets, cfg)
        # Reduce each gradient over the data axes it is replicated on —
        # the Horovod allreduce traced into the step.  Params sharded on
        # a data axis (MoE experts over dp) keep their shard-local
        # gradient on that axis; tp/pp shards stay local.  loss_fn is
        # local/psum-free, so this is the only cross-rank reduction of
        # the backward pass.
        def reduce(g, spec):
            axes = grad_reduce_axes(spec)
            return lax.psum(g, axes) if axes else g

        with jax.named_scope("hvd_grad_reduce"):
            grads = tree_map_with_specs(reduce, grads, pspecs)
            loss = lax.psum(local_loss, ("dp", "sp"))
        return grads, loss.reshape(1)

    grad_fn = shard_map(per_device_grads, mesh=mesh, check_vma=False,
                        in_specs=(pspecs, data_spec, data_spec),
                        out_specs=(pspecs, P()))

    # Donating params/opt_state lets XLA update weights in place
    # instead of allocating fresh buffers every step (same move as the
    # bench ResNet step, +~2% measured there); callers follow the
    # params, opt_state, loss = step(params, opt_state, ...) reassign
    # pattern, so the invalidated buffers are never re-read.
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens, targets):
        def one(carry, _):
            p, s = carry
            grads, loss = grad_fn(p, tokens, targets)
            with jax.named_scope("hvd_optimizer"):
                updates, s = optimizer.update(grads, s, p)
                p = optax.apply_updates(p, updates)
            return (p, s), loss[0]

        if steps_per_dispatch <= 1:
            (params, opt_state), loss = one((params, opt_state), None)
            return params, opt_state, loss
        (params, opt_state), losses = jax.lax.scan(
            one, (params, opt_state), None, length=steps_per_dispatch)
        return params, opt_state, losses[-1]

    return step


def interleave_layer_order(n_layers: int, pp: int, n_virtual: int):
    """Storage permutation for the interleaved pipeline: rank p's
    contiguous pp shard must hold global chunks p, p+pp, ... in slot
    order (each chunk = n_layers/(pp*n_virtual) consecutive layers)."""
    D = pp * n_virtual
    if n_layers % D:
        raise ValueError(f"n_layers {n_layers} not divisible by "
                         f"{pp} stages x {n_virtual} virtual chunks")
    per = n_layers // D
    order = []
    for p in range(pp):
        for v in range(n_virtual):
            c = v * pp + p
            order.extend(range(c * per, (c + 1) * per))
    return np.asarray(order)


def shard_params(params, cfg: TransformerConfig, mesh):
    """Place a full parameter pytree onto the mesh with the model's
    shardings (tp/pp split, everything else replicated).

    With ``pp_schedule="interleaved"`` the layer stacks are round-robin
    permuted first (`interleave_layer_order`) so each pp shard carries
    its non-adjacent chunks; checkpoints of such runs store the permuted
    order and must be reloaded under the same pp/pp_virtual config (true
    of pp-sharded layouts in general)."""
    from jax.sharding import NamedSharding

    pp = mesh.shape.get("pp", 1)
    if cfg.pp_schedule == "interleaved" and pp > 1:
        order = interleave_layer_order(cfg.n_layers, pp, cfg.pp_virtual)
        params = dict(params)
        params["layers"] = jax.tree_util.tree_map(
            lambda a: a[jnp.asarray(order)], params["layers"])
    specs = param_specs(cfg)
    return jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, specs)
