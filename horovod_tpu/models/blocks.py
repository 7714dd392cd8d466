"""The block kinds of the flagship transformer beyond the GPT-2 block:
latent attention with rotary positions, SwiGLU, the expert layer, an
untied head and the multi-token-prediction module — what the
DeepSeek-V3 family of configurations is made of.

Imported by :mod:`horovod_tpu.models.transformer` only where a
``TransformerConfig`` asks for one of them; a GPT-2-shaped configuration
builds none of these parameters and traces none of this code.

Parameters (beside ``embed``, ``ln_f`` and the ``layers`` stack of
``transformer.init_params``)::

    layers  wq_a wq_b q_norm wkv_a wkv_b kv_norm wo   "mla", a row a layer
    dense   w_gate w_up w_down                        the dense layers' SwiGLU
    moe     router bias experts{w_gate w_up w_down}   a row an expert layer;
            shared{w_gate w_up w_down}                experts (layer, held * ep, ..)
    head    (d_model, vocab)                          untied
    mtp     eh_proj ln_e ln_h ln_f layers{..} moe{..} one block, stacks of one
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.models.transformer import TransformerConfig, _rmsnorm
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.ring_attention import ring_attention
from horovod_tpu.parallel.sharding import copy_to_tp, reduce_from_tp


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_mla(norm, cfg: TransformerConfig, n: int) -> dict:
    dm, nh = cfg.d_model, cfg.n_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wq_a": norm(n, dm, cfg.q_lora_rank, scale=dm ** -0.5),
        "q_norm": np.ones((n, cfg.q_lora_rank), np.float32),
        "wq_b": norm(n, cfg.q_lora_rank, nh * qk,
                     scale=cfg.q_lora_rank ** -0.5),
        "wkv_a": norm(n, dm, cfg.kv_lora_rank + cfg.qk_rope_dim,
                      scale=dm ** -0.5),
        "kv_norm": np.ones((n, cfg.kv_lora_rank), np.float32),
        "wkv_b": norm(n, cfg.kv_lora_rank,
                      nh * (cfg.qk_nope_dim + cfg.v_head_dim),
                      scale=cfg.kv_lora_rank ** -0.5),
        "wo": norm(n, nh * cfg.v_head_dim, dm,
                   scale=(nh * cfg.v_head_dim) ** -0.5),
    }


def _init_swiglu(norm, lead: tuple, dm: int, ff: int) -> dict:
    return {"w_gate": norm(*lead, dm, ff, scale=dm ** -0.5),
            "w_up": norm(*lead, dm, ff, scale=dm ** -0.5),
            "w_down": norm(*lead, ff, dm, scale=ff ** -0.5)}


def _init_moe(norm, cfg: TransformerConfig, n: int, ep: int) -> dict:
    dm = cfg.d_model
    p = {"router": norm(n, dm, cfg.n_experts, scale=dm ** -0.5),
         # the selection bias: a buffer, drawn once and never updated
         "bias": norm(n, cfg.n_experts, scale=0.01),
         "experts": _init_swiglu(norm, (n, ep * cfg.experts_held), dm,
                                 cfg.d_expert)}
    if cfg.shared_experts:
        p["shared"] = _init_swiglu(norm, (n,), dm,
                                   cfg.shared_experts * cfg.d_expert)
    return p


def init_extra(norm, cfg: TransformerConfig, ep: int) -> dict:
    """Everything but ``embed``, ``pos``, ``ln_f`` and ``layers``."""
    dm = cfg.d_model
    p = {}
    if cfg.mlp == "swiglu" and cfg.n_dense:
        p["dense"] = _init_swiglu(norm, (cfg.n_dense,), dm, cfg.d_ff)
    if cfg.n_expert_layers:
        p["moe"] = _init_moe(norm, cfg, cfg.n_expert_layers, ep)
    if not cfg.tied_head:
        p["head"] = norm(dm, cfg.vocab, scale=dm ** -0.5)
    if cfg.mtp_depth:
        p["mtp"] = {
            "eh_proj": norm(2 * dm, dm, scale=(2 * dm) ** -0.5),
            "ln_e": np.ones(dm, np.float32),
            "ln_h": np.ones(dm, np.float32),
            "ln_f": np.ones(dm, np.float32),
            "layers": {**init_mla(norm, cfg, 1),
                       "ln1": np.ones((1, dm), np.float32),
                       "ln2": np.ones((1, dm), np.float32)},
            "moe": _init_moe(norm, cfg, 1, ep),
        }
    return p


def mla_specs(lead):
    from jax.sharding import PartitionSpec as P

    return {"wq_a": P(lead), "q_norm": P(lead), "wkv_a": P(lead),
            "kv_norm": P(lead), "wq_b": P(lead, None, "tp"),
            "wkv_b": P(lead, None, "tp"), "wo": P(lead, "tp", None)}


def _moe_specs(cfg: TransformerConfig) -> dict:
    from jax.sharding import PartitionSpec as P

    held = {"w_gate": P(None, "dp"), "w_up": P(None, "dp"),
            "w_down": P(None, "dp")}
    specs = {"router": P(), "bias": P(), "experts": held}
    if cfg.shared_experts:
        specs["shared"] = {"w_gate": P(), "w_up": P(), "w_down": P()}
    return specs


def extra_specs(cfg: TransformerConfig) -> dict:
    """Experts shard over ``dp`` (= ep); the dense SwiGLU is column /
    row parallel over ``tp``; router, bias and the shared expert are
    replicated (computed alike on every ``tp`` rank)."""
    from jax.sharding import PartitionSpec as P

    specs = {}
    if cfg.mlp == "swiglu" and cfg.n_dense:
        specs["dense"] = {"w_gate": P(None, None, "tp"),
                          "w_up": P(None, None, "tp"),
                          "w_down": P(None, "tp", None)}
    if cfg.n_expert_layers:
        specs["moe"] = _moe_specs(cfg)
    if not cfg.tied_head:
        specs["head"] = P()
    if cfg.mtp_depth:
        specs["mtp"] = {
            "eh_proj": P(), "ln_e": P(), "ln_h": P(), "ln_f": P(),
            "layers": {**mla_specs(None), "ln1": P(), "ln2": P()},
            "moe": _moe_specs(cfg)}
    return specs


# ---------------------------------------------------------------------------
# Latent attention
# ---------------------------------------------------------------------------


def rotary(x, positions, theta: float):
    """Rotary embedding over interleaved pairs ``(x[2i], x[2i+1])`` of
    the last axis, frequencies ``theta ** (-2i / d)``, no scaling.  x:
    (b, l, d) or (b, l, h, d); positions: (l,) global.  Computed in
    float32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv      # (l, d/2)
    if x.ndim == 4:
        angle = angle[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def mla(cfg: TransformerConfig, lp, h, positions):
    """Multi-head latent attention on the normalised stream ``h``
    (b, lc, dm): queries through a rank-``q_lora_rank`` latent, keys and
    values through a rank-``kv_lora_rank`` latent, and one rotary key
    part shared by all heads.  Heads shard over ``tp``.  Returns the
    f32 output projection, reduced over ``tp``."""
    b, lc, _ = h.shape
    cd = cfg.compute_dtype
    nh = cfg.n_heads // lax.axis_size("tp")
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    h = h.astype(cd)
    c_q = _rmsnorm(h @ lp["wq_a"].astype(cd), lp["q_norm"])
    kv = h @ lp["wkv_a"].astype(cd)
    c_kv = _rmsnorm(kv[..., :cfg.kv_lora_rank], lp["kv_norm"])
    k_rope = rotary(kv[..., cfg.kv_lora_rank:], positions, cfg.rope_theta)
    # Megatron "f": what follows is per head, the latents are replicated
    c_q, c_kv, k_rope = (copy_to_tp(a, "tp") for a in (c_q, c_kv, k_rope))
    q = (c_q @ lp["wq_b"].astype(cd)).reshape(b, lc, nh, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], rotary(q[..., dn:], positions, cfg.rope_theta)],
        axis=-1)
    kv = (c_kv @ lp["wkv_b"].astype(cd)).reshape(b, lc, nh, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(k_rope[:, :, None, :], (b, lc, nh, dr))], axis=-1)
    with jax.named_scope("hvd_attn"):
        attn = ring_attention(q, k, kv[..., dn:], "sp", causal=True,
                              impl=cfg.attn_impl, recomputed=cfg.remat)
    proj = (attn.reshape(b, lc, nh * dv).astype(cd)
            @ lp["wo"].astype(cd)).astype(jnp.float32)
    return reduce_from_tp(proj, "tp")


# ---------------------------------------------------------------------------
# Feed-forward kinds
# ---------------------------------------------------------------------------


def dense_swiglu(cfg: TransformerConfig, w, h):
    """The dense layers' SwiGLU, column / row parallel over ``tp``.
    Returns ``(f32 output, None)``."""
    cd = cfg.compute_dtype
    h = copy_to_tp(h, "tp")
    out = moe.swiglu(h.astype(cd),
                     jax.tree_util.tree_map(lambda a: a.astype(cd), w))
    return reduce_from_tp(out, "tp"), None


def expert_ffn(cfg: TransformerConfig, w, h):
    """The expert layer on the normalised stream; experts over ``dp``
    (= ep).  Returns ``(f32 output, pairs computed by each held
    expert)``."""
    b, lc, dm = h.shape
    with jax.named_scope("hvd_moe"):
        out, pairs = moe.moe_layer(
            h.reshape(b * lc, dm).astype(cfg.compute_dtype), w,
            top_k=cfg.experts_per_token, scale=cfg.routed_scale,
            axis_name="dp")
    return out.reshape(b, lc, dm), pairs


def ffn_of(cfg: TransformerConfig, params, layer: int):
    """``(ffn(cfg, weights, h), weights)`` of layer ``layer``."""
    if cfg.is_expert_layer(layer):
        row = layer - cfg.n_dense
        return expert_ffn, jax.tree_util.tree_map(lambda a: a[row],
                                                  params["moe"])
    return dense_swiglu, jax.tree_util.tree_map(lambda a: a[layer],
                                                params["dense"])


# ---------------------------------------------------------------------------
# Multi-token prediction
# ---------------------------------------------------------------------------


def mtp_loss(cfg: TransformerConfig, params, x, targets, block, head_nll):
    """The local slice of the depth-1 MTP cross entropy: position ``i``
    joins the embedding of token ``i + 1`` (``targets[i]``) to the main
    stack's output ``x[i]``, runs one expert block and predicts token
    ``i + 2`` through the main model's embedding and head.  The last
    position of a sequence has no such token and is masked.  ``block``
    and ``head_nll`` are the transformer's own.  Returns ``(loss,
    pairs)``; the loss is over the GLOBAL count of predicted positions,
    psum-free as ``transformer.loss_fn``."""
    mp = params["mtp"]
    cd = cfg.compute_dtype
    sp, idx = lax.axis_size("sp"), lax.axis_index("sp")
    b, lc = targets.shape
    with jax.named_scope("hvd_mtp"):
        emb = params["embed"][targets].astype(cd)
        joined = jnp.concatenate(
            [_rmsnorm(emb, mp["ln_e"]), _rmsnorm(x, mp["ln_h"])], axis=-1)
        h = joined.astype(cd) @ mp["eh_proj"].astype(cd)
        lp = jax.tree_util.tree_map(lambda a: a[0], mp["layers"])
        w = jax.tree_util.tree_map(lambda a: a[0], mp["moe"])
        h, pairs = block(lp, w, h)
        # token i + 2: the next target, the first of the next chunk at
        # a chunk's end
        first = targets[:, :1]
        if sp > 1:
            first = lax.ppermute(first, "sp",
                                 [(i, (i - 1) % sp) for i in range(sp)])
        nxt = jnp.concatenate([targets[:, 1:], first], axis=1)
        seen = (idx * lc + jnp.arange(lc)) < sp * lc - 1
        nll = head_nll(h, mp["ln_f"], nxt)
        count = b * lax.axis_size("dp") * (sp * lc - 1)
        loss = jnp.sum(jnp.where(seen[None, :], nll, 0.0)) / count
    return loss, pairs
