"""The block kinds of the flagship transformer beyond the GPT-2 block:
latent attention with rotary positions, SwiGLU, the expert layer, an
untied head and the multi-token-prediction module — what the
DeepSeek-V3 family of configurations is made of — and the layers of a
``layer_pattern``, each ONE pre-normed sub-layer with one residual: a
Mamba-2 mixer on a chunked scan, grouped-query attention without
positions, the expert layer alone — what the hybrid state-space /
attention / expert configurations (``nemotron_h``) are made of — and
gated grouped-query attention with QK-norm, under a sliding window with
rotary positions or over the whole past with none, a dense SwiGLU FFN,
and a second norm after a sub-layer — what the window / full attention
expert configurations (``afmoe``) are made of — and grouped-query
attention over a selection: an indexer scores every earlier key for each
query, the ``index_topk`` highest are kept, and softmax attention runs
over those alone (DeepSeek sparse attention on grouped-query heads, with
QK-norm and rotary positions of three components: the ``KeyeVL2``
language stack) — and a gated short convolution, ``C ⊙ conv(B ⊙ x)``
over three projections of the stream, beside grouped-query attention
with QK-norm and rotary positions over the whole causal past (the
``lfm2_moe`` stack).

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

Under a layer pattern (beside ``embed``, ``ln_f``, ``head``), a row a
layer of the kind, in the pattern's order::

    ssm     ln w_in conv_w conv_b dt_bias a_log d norm w_out      "M"
    attn    ln wq wk wv wo                                        "*"
    moe     ln router bias experts{..} shared{..}                 "E"
    swa     ln wq wk wv wg wo q_norm k_norm                       "S"
    gattn   ln wq wk wv wg wo q_norm k_norm                       "G"
    dense   ln w_gate w_up w_down                                 "D"
    dsa     ln wq wk wv wo q_norm k_norm wq_idx wk_idx ww_idx     "I"
    conv    ln w_in conv_w w_out                                  "C"
    rattn   ln wq wk wv wo q_norm k_norm                          "R"

and ``ln_post`` in every stack where the configuration has a
``post_norm``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu.models.transformer import TransformerConfig, _rmsnorm
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.ring_attention import (KEPT_NAMES, KEPT_SELECTION,
                                                 ring_attention)
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


def _init_expert(norm, cfg: TransformerConfig, lead: tuple, ff: int) -> dict:
    """An expert of ``cfg.expert_form``: the three matrices of SwiGLU,
    or ``w_up`` and ``w_down`` of a relu^2 expert (``parallel/moe.py``
    reads the form from which are there)."""
    w = _init_swiglu(norm, lead, cfg.d_model, ff)
    if cfg.expert_form == "relu2":
        del w["w_gate"]
    return w


def _init_moe(norm, cfg: TransformerConfig, n: int, ep: int) -> dict:
    dm = cfg.d_model
    p = {"router": norm(n, dm, cfg.n_experts, scale=dm ** -0.5)}
    if cfg.router == "sigmoid":
        # the selection bias: a buffer, drawn once and never updated
        p["bias"] = norm(n, cfg.n_experts, scale=0.01)
    p["experts"] = _init_expert(norm, cfg, (n, ep * cfg.experts_held),
                                cfg.d_expert)
    if cfg.shared_experts:
        p["shared"] = _init_expert(norm, cfg, (n,),
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

    names = (("w_gate",) if cfg.expert_form == "swiglu" else ()) + (
        "w_up", "w_down")
    specs = {"router": P(), "bias": P(),
             "experts": dict.fromkeys(names, P(None, "dp"))}
    if cfg.router == "softmax":
        del specs["bias"]
    if cfg.shared_experts:
        specs["shared"] = dict.fromkeys(names, P())
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


def rotary(x, positions, theta: float, halves: bool = False,
           sections: tuple = ()):
    """Rotary embedding over interleaved pairs ``(x[2i], x[2i+1])`` of
    the last axis — with ``halves`` over the pairs ``(x[i], x[i + d/2])``
    of its two halves, the half-rotation layout — frequencies
    ``theta ** (-2i / d)``, no scaling.  x: (b, l, d) or (b, l, h, d);
    positions: (l,) global.  With ``sections`` (three counts that add up
    to d / 2) positions are (3, l), a row a component, and pair ``i``
    turns by the component whose section holds it: the first
    ``sections[0]`` pairs by the first, the next ``sections[1]`` by the
    second, the rest by the third.  Computed in float32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if sections:
        # (3, l) -> (l, d/2): each pair's own component
        positions = positions[np.repeat(np.arange(3), sections)].T
        angle = positions.astype(jnp.float32) * inv
    else:
        angle = positions.astype(jnp.float32)[:, None] * inv  # (l, d/2)
    if x.ndim == 4:
        angle = angle[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if halves:
        a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                               axis=-1).astype(x.dtype)
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
    c_q = _rmsnorm(h @ lp["wq_a"].astype(cd), lp["q_norm"], cfg.norm_eps)
    kv = h @ lp["wkv_a"].astype(cd)
    c_kv = _rmsnorm(kv[..., :cfg.kv_lora_rank], lp["kv_norm"], cfg.norm_eps)
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


def expert_ffn(cfg: TransformerConfig, w, h, count_all: bool = False):
    """The expert layer on the normalised stream; experts over ``dp``
    (= ep).  Returns ``(f32 output, pairs computed by each held
    expert)``; with ``count_all`` the pairs sent to each of all the
    experts, alike on every ``dp`` rank."""
    b, lc, dm = h.shape
    with jax.named_scope("hvd_moe"):
        out, pairs = moe.moe_layer(
            h.reshape(b * lc, dm).astype(cfg.compute_dtype), w,
            top_k=cfg.experts_per_token, scale=cfg.routed_scale,
            axis_name="dp", count_all=count_all)
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
            [_rmsnorm(emb, mp["ln_e"], cfg.norm_eps),
             _rmsnorm(x, mp["ln_h"], cfg.norm_eps)], axis=-1)
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


# ---------------------------------------------------------------------------
# A layer pattern: one sub-layer a layer, the weights stacked per kind
# ---------------------------------------------------------------------------

# the stack that holds the weights of each kind of layer
STACK_OF = {"M": "ssm", "*": "attn", "E": "moe", "S": "swa", "G": "gattn",
            "D": "dense", "I": "dsa", "C": "conv", "R": "rattn"}
# the kinds that call ``ring_attention`` over ``n_heads`` query heads on
# ``n_kv_heads`` key/value heads
ATTENTION_KINDS = "*SGIR"
# the f32 result of an expert layer that a post-norm reads, under
# ``jax.ad_checkpoint.checkpoint_name``: kept by a recomputed layer
KEPT_EXPERT_OUT = "hvd_moe_out"
# the taps ``init_pattern`` gives a gated short convolution ("C"); a layer
# runs as many as its ``conv_w`` holds
CONV_TAPS = 3
# how the Mamba-2 reference code draws a state-space layer's own
# parameters: the time step log-uniform in [DT_MIN, DT_MAX] and floored,
# kept as what softplus maps onto it; the decay rate A uniform in
# A_RANGE, kept as its logarithm; the skip D = 1
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4
A_RANGE = (1.0, 16.0)


def _init_ssm(norm, rng, cfg: TransformerConfig, n: int) -> dict:
    dm, heads = cfg.d_model, cfg.ssm_heads
    inner = heads * cfg.ssm_head_dim
    conv = inner + 2 * cfg.ssm_groups * cfg.ssm_state
    # a framework's default for a convolution's weight and bias: uniform
    # in +-1 / sqrt(taps); a normal of the same variance here
    tap = (3 * cfg.ssm_conv) ** -0.5
    p = {"ln": np.ones((n, dm), np.float32),
         "w_in": norm(n, dm, inner + conv + heads, scale=dm ** -0.5),
         "conv_w": norm(n, cfg.ssm_conv, conv, scale=tap),
         "conv_b": norm(n, conv, scale=tap)}
    dt = jnp.maximum(DT_MIN * (DT_MAX / DT_MIN) ** rng.rand(n, heads),
                     DT_FLOOR)
    p["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32)
    low, high = A_RANGE
    p["a_log"] = jnp.log(low + (high - low) * rng.rand(n, heads)).astype(
        jnp.float32)
    p["d"] = np.ones((n, heads), np.float32)
    p["norm"] = np.ones((n, inner), np.float32)
    p["w_out"] = norm(n, inner, dm, scale=inner ** -0.5)
    return p


def _init_gqa(norm, cfg: TransformerConfig, n: int) -> dict:
    dm, width = cfg.d_model, cfg.n_heads * cfg.head_dim
    kv = (cfg.n_kv_heads or cfg.n_heads) * cfg.head_dim
    return {"ln": np.ones((n, dm), np.float32),
            "wq": norm(n, dm, width, scale=dm ** -0.5),
            "wk": norm(n, dm, kv, scale=dm ** -0.5),
            "wv": norm(n, dm, kv, scale=dm ** -0.5),
            "wo": norm(n, width, dm, scale=width ** -0.5)}


def _init_normed_gqa(norm, cfg: TransformerConfig, n: int) -> dict:
    """``_init_gqa`` with the gains of the two per-head norms, one of
    ``head_dim`` for all query heads and one for all key heads."""
    return {**_init_gqa(norm, cfg, n),
            "q_norm": np.ones((n, cfg.head_dim), np.float32),
            "k_norm": np.ones((n, cfg.head_dim), np.float32)}


def _init_gated_gqa(norm, cfg: TransformerConfig, n: int) -> dict:
    """``_init_normed_gqa`` with the output gate's matrix."""
    dm, width = cfg.d_model, cfg.n_heads * cfg.head_dim
    return {**_init_normed_gqa(norm, cfg, n),
            "wg": norm(n, dm, width, scale=dm ** -0.5)}


def _init_indexed_gqa(norm, cfg: TransformerConfig, n: int) -> dict:
    """``_init_normed_gqa`` with the indexer's three matrices: its
    ``index_heads`` query heads of ``index_head_dim``, its one key head,
    and a weight a query head."""
    dm = cfg.d_model
    return {**_init_normed_gqa(norm, cfg, n),
            "wq_idx": norm(n, dm, cfg.index_heads * cfg.index_head_dim,
                           scale=dm ** -0.5),
            "wk_idx": norm(n, dm, cfg.index_head_dim, scale=dm ** -0.5),
            "ww_idx": norm(n, dm, cfg.index_heads, scale=dm ** -0.5)}


def _init_conv(norm, cfg: TransformerConfig, n: int) -> dict:
    """A gated short convolution's in-projection to ``B | C | x``, its
    taps (a framework's default for a convolution's weight, uniform in
    +-1 / sqrt(taps), as a normal of the same variance) and its
    out-projection."""
    dm, taps = cfg.d_model, CONV_TAPS
    return {"ln": np.ones((n, dm), np.float32),
            "w_in": norm(n, dm, 3 * dm, scale=dm ** -0.5),
            "conv_w": norm(n, taps, dm, scale=(3 * taps) ** -0.5),
            "w_out": norm(n, dm, dm, scale=dm ** -0.5)}


def init_pattern(norm, rng, cfg: TransformerConfig, ep: int) -> dict:
    """Everything but ``embed``: a stack a kind the pattern holds, the
    final norm and the untied head.  ``rng.rand`` draws the uniform
    numbers of the state-space layers."""
    dm, kinds = cfg.d_model, cfg.layer_pattern
    p = {"ln_f": np.ones(dm, np.float32),
         "head": norm(dm, cfg.vocab, scale=dm ** -0.5)}
    if "M" in kinds:
        p["ssm"] = _init_ssm(norm, rng, cfg, kinds.count("M"))
    if "*" in kinds:
        p["attn"] = _init_gqa(norm, cfg, kinds.count("*"))
    if "E" in kinds:
        n = kinds.count("E")
        p["moe"] = {"ln": np.ones((n, dm), np.float32),
                    **_init_moe(norm, cfg, n, ep)}
    for kind in "SG":
        if kind in kinds:
            p[STACK_OF[kind]] = _init_gated_gqa(norm, cfg, kinds.count(kind))
    if "D" in kinds:
        n = kinds.count("D")
        p["dense"] = {"ln": np.ones((n, dm), np.float32),
                      **_init_swiglu(norm, (n,), dm, cfg.d_ff)}
    if "I" in kinds:
        p["dsa"] = _init_indexed_gqa(norm, cfg, kinds.count("I"))
    if "C" in kinds:
        p["conv"] = _init_conv(norm, cfg, kinds.count("C"))
    if "R" in kinds:
        p["rattn"] = _init_normed_gqa(norm, cfg, kinds.count("R"))
    if cfg.post_norm:
        for kind in set(kinds):
            p[STACK_OF[kind]]["ln_post"] = np.ones((kinds.count(kind), dm),
                                                   np.float32)
    if cfg.rescale_depth:
        # what a sub-layer writes to the stream shrinks with the depth
        # the stream is drawn for, so that a token's own row outweighs
        # what the mixers add of its neighbours (at fan-in scale alone
        # the tokens of a sequence lean the same way and share experts:
        # PERF.md section 6, PR 33)
        down = cfg.rescale_depth ** -0.5
        moe_ = p.get("moe", {})
        for holder, name in ((p.get("ssm", {}), "w_out"),
                             (p.get("attn", {}), "wo"),
                             (p.get("swa", {}), "wo"),
                             (p.get("gattn", {}), "wo"),
                             (p.get("dsa", {}), "wo"),
                             (p.get("conv", {}), "w_out"),
                             (p.get("rattn", {}), "wo"),
                             (p.get("dense", {}), "w_down"),
                             (moe_.get("experts", {}), "w_down"),
                             (moe_.get("shared", {}), "w_down")):
            if name in holder:
                holder[name] = holder[name] * down
    return p


def pattern_specs(cfg: TransformerConfig) -> dict:
    """Everything replicated but the held experts, which shard over
    ``dp`` (= ep): no kind of a pattern runs under ``pp`` or ``tp``."""
    from jax.sharding import PartitionSpec as P

    normed = ("ln", "wq", "wk", "wv", "wo", "q_norm", "k_norm")
    gated = normed + ("wg",)
    names = {"ssm": ("ln", "w_in", "conv_w", "conv_b", "dt_bias", "a_log",
                     "d", "norm", "w_out"),
             "attn": ("ln", "wq", "wk", "wv", "wo"),
             "swa": gated, "gattn": gated,
             "dense": ("ln", "w_gate", "w_up", "w_down"),
             "dsa": normed + ("wq_idx", "wk_idx", "ww_idx"),
             "conv": ("ln", "w_in", "conv_w", "w_out"), "rattn": normed}
    specs = {"head": P()}
    for kind in set(cfg.layer_pattern):
        stack = STACK_OF[kind]
        specs[stack] = ({"ln": P(), **_moe_specs(cfg)} if kind == "E"
                        else dict.fromkeys(names[stack], P()))
        if cfg.post_norm:
            specs[stack]["ln_post"] = P()
    return specs


def _whole_axes(what: str, reasons: dict) -> None:
    """Raise for the first of the mesh axes ``reasons`` names that is
    larger than 1."""
    for axis, why in reasons.items():
        if lax.axis_size(axis) > 1:
            raise NotImplementedError(
                f"{what} under {axis} > 1 is not supported: {why}")


def causal_conv(xbc, weight, bias=None):
    """A causal depthwise convolution over time as shifted
    multiply-adds in float32.  ``xbc``: (b, lc, channels); ``weight``:
    (taps, channels), tap ``k`` weighing the input ``taps - 1 - k`` steps
    back (before the first step there is nothing); ``bias``:
    (channels,), or None for a convolution without one."""
    taps, steps = weight.shape[0], xbc.shape[1]
    earlier = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    out = None if bias is None else bias.astype(jnp.float32)
    for k in range(taps):
        term = earlier[:, k:k + steps].astype(jnp.float32) * weight[k]
        out = term if out is None else out + term
    return out


def mamba(cfg: TransformerConfig, lp, h):
    """The Mamba-2 mixer on the normalised stream ``h`` (b, lc, dm):
    one in-projection split into the gate ``z``, ``x | B | C`` and the
    time steps; a causal depthwise convolution of ``ssm_conv`` taps and
    SiLU over ``x | B | C``; ``dt = softplus(dt + dt_bias)`` and ``A =
    -exp(a_log)`` in float32; the recurrence as a chunked scan
    (:mod:`horovod_tpu.ops.ssm_scan`); ``y * silu(z)``, an RMSNorm over
    each group's channels, the out-projection.  Returns ``(f32 output,
    the least logarithm of a whole chunk's decay)``."""
    from horovod_tpu.ops.ssm_scan import ssm_scan

    _whole_axes("a state-space layer", {
        "tp": "the in-projection's z | x B C | dt parts, the "
              "convolution's channels and the groups' B and C are not "
              "shared out over the tp ranks",
        "sp": "a scan over a sequence split across chips needs the state "
              "at each chip's first step, and the convolution its last "
              "inputs, passed round the ring, which is not built"})
    b, lc, _ = h.shape
    cd = cfg.compute_dtype
    heads, size = cfg.ssm_heads, cfg.ssm_head_dim
    groups, state = cfg.ssm_groups, cfg.ssm_state
    inner, bc = heads * size, groups * state
    proj = h.astype(cd) @ lp["w_in"].astype(cd)
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * bc], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"])).astype(cd)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    y, least = ssm_scan(
        xbc[..., :inner].reshape(b, lc, heads, size), dt,
        -jnp.exp(lp["a_log"]),
        xbc[..., inner:inner + bc].reshape(b, lc, groups, state),
        xbc[..., inner + bc:].reshape(b, lc, groups, state), lp["d"],
        cfg.ssm_chunk)
    y = y.reshape(b, lc, inner) * jax.nn.silu(z.astype(jnp.float32))
    y = y.reshape(b, lc, groups, inner // groups)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
    y = (y.reshape(b, lc, inner) * lp["norm"]).astype(cd)
    return (y @ lp["w_out"].astype(cd)).astype(jnp.float32), least


def _over_query_heads(t, times: int):
    """(b, lc, key/value heads, d) -> (b, lc, query heads, d): each
    key/value head ``times`` times in a row, so query head ``i`` meets
    key/value head ``i // times``."""
    return jnp.repeat(t, times, axis=2)


def _normed_heads(cfg: TransformerConfig, lp, h, positions=None):
    """``(q, k, v)`` of :func:`gqa`'s heads from the normalised stream
    ``h`` in the compute type, with an RMSNorm over each head's ``q``
    and ``k`` (one learned gain of ``head_dim`` for all query heads, one
    for all key heads) and, where ``positions`` are given, rotary
    positions (all of ``head_dim``, the half-rotation layout, in
    ``cfg.rope_sections`` where the configuration has them); ``k`` and
    ``v`` broadcast over their query heads."""
    b, lc, _ = h.shape
    cd = cfg.compute_dtype
    nh, hd = cfg.n_heads, cfg.head_dim
    nkv = cfg.n_kv_heads or nh
    h = h.astype(cd)
    q = _rmsnorm((h @ lp["wq"].astype(cd)).reshape(b, lc, nh, hd),
                 lp["q_norm"], cfg.norm_eps)
    k = _rmsnorm((h @ lp["wk"].astype(cd)).reshape(b, lc, nkv, hd),
                 lp["k_norm"], cfg.norm_eps)
    v = (h @ lp["wv"].astype(cd)).reshape(b, lc, nkv, hd)
    if positions is not None:
        q, k = (rotary(t, positions, cfg.rope_theta, halves=True,
                       sections=cfg.rope_sections) for t in (q, k))
    k, v = (_over_query_heads(t, nh // nkv) for t in (k, v))
    return q, k, v


def gqa(cfg: TransformerConfig, lp, h):
    """Grouped-query attention on the normalised stream ``h``, no
    positional encoding: ``n_heads`` query heads, query head ``i`` on
    key/value head ``i // (n_heads / n_kv_heads)``.  ``k`` and ``v`` are
    broadcast over their query heads before ``ring_attention``, whose
    kernels read one head count; the sum over a group in the backward
    pass is that broadcast's transpose.  Returns the f32 output
    projection."""
    _whole_axes("grouped-query attention", {
        "tp": "the key/value heads, fewer than the query heads, are not "
              "shared out over the tp ranks",
        "sp": "k and v are broadcast over their query heads before the "
              "ring, which would pass every key/value head round once "
              "for each of its query heads"})
    b, lc, _ = h.shape
    cd = cfg.compute_dtype
    nh, hd = cfg.n_heads, cfg.head_dim
    nkv = cfg.n_kv_heads or nh
    h = h.astype(cd)
    q = (h @ lp["wq"].astype(cd)).reshape(b, lc, nh, hd)
    k, v = (_over_query_heads(
        (h @ lp[w].astype(cd)).reshape(b, lc, nkv, hd), nh // nkv)
        for w in ("wk", "wv"))
    with jax.named_scope("hvd_attn"):
        attn = ring_attention(q, k, v, "sp", causal=True,
                              impl=cfg.attn_impl, recomputed=cfg.remat)
    return (attn.reshape(b, lc, nh * hd).astype(cd)
            @ lp["wo"].astype(cd)).astype(jnp.float32)


def gated_gqa(cfg: TransformerConfig, lp, h, positions, sliding: bool):
    """Gated grouped-query attention on the normalised stream ``h``:
    :func:`gqa`'s heads with an RMSNorm over each head's ``q`` and ``k``
    (one learned gain of ``head_dim`` for all query heads, one for all
    key heads) and the result multiplied by ``sigmoid(h W_g)`` in float32
    before the output projection.  ``sliding``: ``q`` and ``k`` take
    rotary positions (all of ``head_dim``, the half-rotation layout) and
    query ``i`` sees key ``j`` iff ``0 <= i - j < cfg.window``; else no
    positions and the whole past.  Returns the f32 output projection."""
    _whole_axes("gated grouped-query attention", {
        "tp": "the key/value heads, fewer than the query heads, and the "
              "gate's columns are not shared out over the tp ranks",
        "sp": "k and v are broadcast over their query heads before the "
              "ring, which would pass every key/value head round once "
              "for each of its query heads, and a window's whole ring "
              "steps are not skipped"})
    b, lc, _ = h.shape
    cd = cfg.compute_dtype
    h = h.astype(cd)
    q, k, v = _normed_heads(cfg, lp, h, positions if sliding else None)
    with jax.named_scope("hvd_attn"):
        attn = ring_attention(q, k, v, "sp", causal=True,
                              impl=cfg.attn_impl, recomputed=cfg.remat,
                              window=cfg.window if sliding else None)
    gate = jax.nn.sigmoid((h @ lp["wg"].astype(cd)).astype(jnp.float32))
    attn = attn.reshape(b, lc, -1).astype(jnp.float32) * gate
    return (attn.astype(cd) @ lp["wo"].astype(cd)).astype(jnp.float32)


# rows of queries whose indexer scores against every key are alive at
# once in the XLA form of the selection (the kernel's blocks are its
# own, ``pallas_index.BLOCK_Q``): (rows, index_heads, seq) products and
# (rows, seq) scores in f32, 270 MB and 17 MB a sequence of 16,384
_INDEX_ROWS = 256


def _kth_largest(keys, k: int):
    """The ``k``-th largest of the uint32 ``keys`` along the last axis,
    exactly: the largest ``t`` that at least ``k`` keys reach, built bit
    by bit from the top, a count over the row a bit."""
    def bit(i, t):
        trial = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        reach = jnp.sum(keys >= trial[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(reach >= k, trial, t)

    return lax.fori_loop(0, 32, bit,
                         jnp.zeros(keys.shape[:-1], jnp.uint32))


def index_impl(cfg: TransformerConfig, lc: int) -> str:
    """The path that makes an indexed attention layer's selection over a
    chunk of ``lc`` queries: ``"kernel"`` (``ops/pallas_index``, over the
    keys each block of queries can see) where the backend is a TPU,
    ``cfg.attn_impl`` does not force ``"xla"`` and the chunk is a whole
    number of the kernel's row blocks and key tiles; else ``"xla"``.
    The two give the same words."""
    from horovod_tpu.ops import pallas_index

    if (jax.default_backend() == "tpu" and cfg.attn_impl != "xla"
            and pallas_index.tiles_fit(lc, cfg.index_heads,
                                       cfg.index_head_dim,
                                       cfg.compute_dtype.itemsize)):
        return "kernel"
    return "xla"


def index_inputs(cfg: TransformerConfig, lp, h):
    """The indexer's three projections of the normalised stream ``h``
    (b, lc, dm), detached, in the compute type: ``q`` (b, lc,
    index_heads, index_head_dim), ``k`` (b, lc, index_head_dim) and the
    head weights ``w = (h W_w) / sqrt(index_heads)`` (b, lc,
    index_heads) in float32."""
    b, lc, _ = h.shape
    cd = cfg.compute_dtype
    heads, size = cfg.index_heads, cfg.index_head_dim
    h = lax.stop_gradient(h).astype(cd)
    q = (h @ lp["wq_idx"].astype(cd)).reshape(b, lc, heads, size)
    k = h @ lp["wk_idx"].astype(cd)                          # (b, lc, size)
    w = (h @ lp["ww_idx"].astype(cd)).astype(jnp.float32) * heads ** -0.5
    return q, k, w


def select_keys(cfg: TransformerConfig, lp, h):
    """The indexer's selection for the normalised stream ``h`` (b, lc,
    dm), packed as ``ring_attention``'s ``keep``: (b, lc, W) int32.

    ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(index_head_dim)``
    over the ``index_heads`` query heads ``j`` and the one key head, ``w
    = (h W_w) / sqrt(index_heads)``, no positions: the products of
    compute-type operands, everything after them in float32.  Query
    ``t`` keeps the ``index_topk`` keys ``s <= t`` of largest ``I[t,
    s]``, a tie going to the lower ``s``, and every key where ``t <
    index_topk``: exact (the threshold is the row's ``index_topk``-th
    largest score itself, found bit by bit over the scores in an
    order-preserving integer form).  Made by the kernel of
    ``ops/pallas_index`` where :func:`index_impl` says so, over the keys
    each block of queries can see; else here, by blocks of
    ``_INDEX_ROWS`` queries against every key (:func:`_kth_largest`), so
    that no (lc, lc) tensor exists.  No gradient passes: the indexer
    reads the stream detached, and the selection is discrete."""
    from horovod_tpu.ops.pallas_attention import pack_keep

    b, lc, _ = h.shape
    size = cfg.index_head_dim
    topk = min(cfg.index_topk, lc)
    q, k, w = index_inputs(cfg, lp, h)
    if index_impl(cfg, lc) == "kernel":
        from horovod_tpu.ops.pallas_index import index_select

        # the kernel defines no derivative: its operands carry none (the
        # projections' weights would, and the result drops it)
        q, k, w = lax.stop_gradient((jnp.swapaxes(q, 1, 2), k, w))
        return lax.stop_gradient(index_select(q, k, w, topk=topk))
    rows = min(_INDEX_ROWS, lc)
    if lc % rows:
        raise ValueError(f"the indexer scores {rows} queries at a time: "
                         f"the sequence chunk {lc} is no multiple of it")
    keys = jnp.arange(lc)

    def block(start):
        qb = lax.dynamic_slice_in_dim(q, start, rows, axis=1)
        wb = lax.dynamic_slice_in_dim(w, start, rows, axis=1)
        products = jnp.einsum("bqjd,bkd->bqjk", qb, k,
                              preferred_element_type=jnp.float32)
        scores = jnp.sum(wb[..., None] * jax.nn.relu(products),
                         axis=2) * size ** -0.5          # (b, rows, lc)
        seen = (start + jnp.arange(rows))[:, None] >= keys[None, :]
        # -0.0 and 0.0 are one score; what a query cannot see is below
        # every score
        scores = jnp.where(seen, jnp.where(scores == 0.0, 0.0, scores),
                           -jnp.inf)
        # float32 -> uint32, order kept: the sign bit set on a positive
        # number, every bit turned on a negative one
        bits = lax.bitcast_convert_type(scores, jnp.uint32)
        order = jnp.where(bits >> 31 == 0, bits | jnp.uint32(1 << 31),
                          ~bits)
        least = _kth_largest(order, topk)[..., None]
        above, level = order > least, order == least
        # of the keys level with the threshold, the first that are needed
        needed = topk - jnp.sum(above, axis=-1, keepdims=True,
                                dtype=jnp.int32)
        first = jnp.cumsum(level, axis=-1, dtype=jnp.int32) <= needed
        return pack_keep((above | (level & first)) & seen)

    words = lax.map(block, jnp.arange(0, lc, rows))   # (blocks, b, rows, W)
    return lax.stop_gradient(
        jnp.moveaxis(words, 0, 1).reshape(b, lc, words.shape[-1]))


def indexed_gqa(cfg: TransformerConfig, lp, h, positions):
    """Grouped-query attention over a selection on the normalised stream
    ``h``: :func:`select_keys` picks each query's keys, one selection for
    all heads, and softmax attention runs over those alone — :func:`gqa`'s
    heads with an RMSNorm over each head's ``q`` and ``k`` (one learned
    gain of ``head_dim`` for all query heads, one for all key heads) and
    rotary positions (all of ``head_dim``, the half-rotation layout;
    ``positions`` (3, lc) and ``cfg.rope_sections`` where the
    configuration has sections, else (lc,)).  A recomputed layer keeps
    the selection (``KEPT_SELECTION``): it is made once a step, and the
    backward kernels read the forward pass's.  Returns ``(f32 output
    projection, the packed selection (b, lc, W) int32)``."""
    _whole_axes("attention over an indexer's selection", {
        "tp": "the key/value heads, fewer than the query heads, are not "
              "shared out over the tp ranks, and every rank would score "
              "and select alike",
        "sp": "a query's selection is over the keys of the whole "
              "sequence: the indexer's keys are not gathered over the "
              "ring, and a ring step's part of a selection is not cut "
              "out of its words"})
    b, lc, _ = h.shape
    cd = cfg.compute_dtype
    with jax.named_scope("hvd_dsa_index"):
        keep = checkpoint_name(select_keys(cfg, lp, h), KEPT_SELECTION)
    # the heads' projections after the selection exists: on a TPU the
    # compiler then copies the kernel's words into VMEM behind them and
    # the forward kernel reads its masks from there, as it read the XLA
    # form's (a fusion wrote them there); from HBM it took 0.9 ms longer
    # a layer on a v5e at 16,384 tokens
    keep, h = lax.optimization_barrier((keep, h))
    q, k, v = _normed_heads(cfg, lp, h, positions)
    with jax.named_scope("hvd_attn"):
        attn = ring_attention(q, k, v, "sp", causal=True,
                              impl=cfg.attn_impl, recomputed=cfg.remat,
                              keep=keep)
    return (attn.reshape(b, lc, -1).astype(cd)
            @ lp["wo"].astype(cd)).astype(jnp.float32), keep


def normed_gqa(cfg: TransformerConfig, lp, h, positions):
    """Grouped-query attention on the normalised stream ``h`` over the
    whole causal past, ungated: :func:`gqa`'s heads with an RMSNorm over
    each head's ``q`` and ``k`` and rotary positions (all of
    ``head_dim``, the half-rotation layout; :func:`_normed_heads`).  A
    recomputed layer keeps what its kernel gave, as every attention kind
    does.  Returns the f32 output projection."""
    _whole_axes("grouped-query attention with QK-norm", {
        "tp": "the key/value heads, fewer than the query heads, are not "
              "shared out over the tp ranks",
        "sp": "k and v are broadcast over their query heads before the "
              "ring, which would pass every key/value head round once "
              "for each of its query heads"})
    b, lc, _ = h.shape
    cd = cfg.compute_dtype
    q, k, v = _normed_heads(cfg, lp, h, positions)
    with jax.named_scope("hvd_attn"):
        attn = ring_attention(q, k, v, "sp", causal=True,
                              impl=cfg.attn_impl, recomputed=cfg.remat)
    return (attn.reshape(b, lc, -1).astype(cd)
            @ lp["wo"].astype(cd)).astype(jnp.float32)


def conv_mix(cfg: TransformerConfig, lp, h):
    """The gated short convolution on the normalised stream ``h`` (b, lc,
    dm): one in-projection split ``B | C | x`` in that order;
    ``y = C * v`` with ``v_t = sum_k w_k * (B * x)_{t - taps + 1 + k}``, a
    causal depthwise convolution of ``conv_w``'s taps without bias,
    nothing before the first step; the out-projection.  ``u = B * x`` in
    the compute type, as the published model computes it; the taps' sum
    and ``C * v`` in float32, ``y`` rounded; the gates and the taps under
    the scope ``hvd_conv_mix``.  No nonlinearity but the two gates.
    Returns the f32 output projection."""
    _whole_axes("a gated short convolution", {
        "tp": "the in-projection's B | C | x parts and the convolution's "
              "channels are not shared out over the tp ranks",
        "sp": "a chunk's first outputs need the last taps - 1 inputs of "
              "the chunk before it, which would have to cross a ring "
              "boundary, and no such pass is built"})
    cd = cfg.compute_dtype
    b, c, x = jnp.split(h.astype(cd) @ lp["w_in"].astype(cd), 3, axis=-1)
    with jax.named_scope("hvd_conv_mix"):
        y = (c.astype(jnp.float32)
             * causal_conv(b * x, lp["conv_w"])).astype(cd)
    return (y @ lp["w_out"].astype(cd)).astype(jnp.float32)


def pattern_dense(cfg: TransformerConfig, lp, h):
    """The dense SwiGLU FFN as a layer of a pattern, whose stacks are
    whole on every chip.  Returns the f32 output."""
    _whole_axes("a pattern's dense FFN", {
        "tp": "a pattern's stacks are replicated, so its matrices are "
              "not split column / row over the tp ranks"})
    cd = cfg.compute_dtype
    return moe.swiglu(h.astype(cd), {name: lp[name].astype(cd) for name in
                                     ("w_gate", "w_up", "w_down")})


def pattern_layer(cfg: TransformerConfig, kind: str, lp, x, positions):
    """One layer of a pattern: ``x + f(RMSNorm(x))`` with ``f`` the
    sub-layer of ``kind`` — ``x + RMSNorm(f(RMSNorm(x)))`` where the
    configuration has a ``post_norm``; ``C`` (a gated short convolution)
    under the scope ``hvd_conv``, ``R`` (ungated attention with QK-norm
    and rotary positions) under ``hvd_rattn``.  ``positions``: (lc,)
    global, for the kinds that take rotary ones ((3, lc) where the
    configuration has ``rope_sections``).  Returns ``(x, report)``: the pairs
    an expert layer's routing sent each of all its experts, a
    state-space layer's least log-decay, an indexed attention layer's
    packed selection, else ``None``."""
    h = _rmsnorm(x, lp["ln"], cfg.norm_eps)
    report = None
    if kind == "M":
        with jax.named_scope("hvd_ssm"):
            out, report = mamba(cfg, lp, h)
    elif kind == "*":
        out = gqa(cfg, lp, h)
    elif kind == "S":
        with jax.named_scope("hvd_swa"):
            out = gated_gqa(cfg, lp, h, positions, sliding=True)
    elif kind == "G":
        with jax.named_scope("hvd_gattn"):
            out = gated_gqa(cfg, lp, h, positions, sliding=False)
    elif kind == "D":
        out = pattern_dense(cfg, lp, h)
    elif kind == "I":
        with jax.named_scope("hvd_dsa"):
            out, report = indexed_gqa(cfg, lp, h, positions)
    elif kind == "C":
        with jax.named_scope("hvd_conv"):
            out = conv_mix(cfg, lp, h)
    elif kind == "R":
        with jax.named_scope("hvd_rattn"):
            out = normed_gqa(cfg, lp, h, positions)
    else:
        out, report = expert_ffn(cfg, lp, h, count_all=True)
        if cfg.post_norm:
            out = checkpoint_name(out, KEPT_EXPERT_OUT)
    if cfg.post_norm:
        out = _rmsnorm(out, lp["ln_post"], cfg.norm_eps)
    return x + out.astype(x.dtype), report


# as ``transformer._remat_block``: a recomputed attention layer keeps
# what its kernel gave, every other layer only its input — and an expert
# layer whose result goes through a norm before the residual add keeps
# that result, 134 MB a layer of 16,384 tokens at d 2048: the norm's
# backward pass reads it, and without the name the replay would run the
# held experts' sort, gather, grouped products and scatter-add a second
# time for it (15 grouped products a layer where 12 do; without a norm
# after it nothing reads the result and the compiler drops that replay);
# and an indexed attention layer its selection, 34 MB a layer of 16,384
# tokens: the backward kernels read the bits the forward pass ran under,
# and the replay holds no indexer
_remat_layer = jax.checkpoint(
    pattern_layer, static_argnums=(0, 1),
    policy=jax.checkpoint_policies.save_only_these_names(
        *KEPT_NAMES, KEPT_EXPERT_OUT, KEPT_SELECTION))


def pattern_stack(cfg: TransformerConfig, params, x, pos):
    """Walk the pattern: layer ``i`` is of kind ``layer_pattern[i]`` and
    takes the next row of its kind's stack.  ``x``: the tokens' rows of
    the embedding (b, lc, dm), float32, which enter the stream times
    ``embed_scale`` in the compute type; ``pos``: (lc,) global
    positions, handed to the layers as three equal components where the
    configuration has ``rope_sections`` (text: a token's temporal,
    height and width positions are its index).  Returns ``(x, pos,
    reports)`` as ``transformer._stack`` does, ``reports`` a dict
    ``{"loads": [an "E" layer's each], "least_log_decay": [an "M"
    layer's each], "selections": [an "I" layer's each]}``."""
    if lax.axis_size("pp") > 1:
        raise NotImplementedError(
            "a layer pattern under pp > 1 is not supported: its weights "
            "are stacked per kind and not per layer, so a pp shard of a "
            "stack is not a stage's layers; pp > 1 runs the GPT-2 block")
    if cfg.embed_scale != 1.0:
        x = x * cfg.embed_scale
    x = x.astype(cfg.compute_dtype)
    layer = _remat_layer if cfg.remat else pattern_layer
    rows = dict.fromkeys(STACK_OF, 0)
    reports = {"loads": [], "least_log_decay": [], "selections": []}
    report_of = {"E": "loads", "M": "least_log_decay", "I": "selections"}
    positions = (jnp.broadcast_to(pos, (3,) + pos.shape)
                 if cfg.rope_sections else pos)
    for kind in cfg.layer_pattern:
        lp = jax.tree_util.tree_map(lambda a: a[rows[kind]],
                                    params[STACK_OF[kind]])
        rows[kind] += 1
        x, report = layer(cfg, kind, lp, x, positions)
        if kind in report_of:
            reports[report_of[kind]].append(report)
    return x, pos, reports
