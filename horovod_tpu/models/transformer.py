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
  * **ep** — expert layers (dropless sigmoid top-k, a share of the
    experts held on each rank) with the experts sharded over the ``dp``
    axis (:mod:`horovod_tpu.parallel.moe`).

The block is chosen by the configuration, per layer: fused-QKV heads
with learned positions or latent attention with rotary positions; a
GELU MLP, SwiGLU, or — after ``n_dense_layers`` leading dense layers —
the expert layer; a tied or an untied head; an optional multi-token
prediction module (:mod:`horovod_tpu.models.blocks`, imported only
where a configuration asks for one of these).  The defaults are the
GPT-2 block.  With a ``layer_pattern`` a layer is instead ONE
pre-normed sub-layer with one residual — a Mamba-2 mixer, grouped-query
attention without positions, gated grouped-query attention with QK-norm
under a sliding window with rotary positions or full without,
grouped-query attention over the keys an indexer selects for each
query, ungated grouped-query attention with QK-norm and rotary positions,
a gated short convolution, a dense SwiGLU FFN or an expert FFN alone —
of the kind the pattern gives it,
its weights stacked per kind (``blocks.pattern_stack``), with a second
norm after the sub-layer where the configuration states one.

Everything is bf16 matmuls with fp32 accumulation/norms — MXU-native.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel.pipeline import gpipe, interleaved_pipeline
from horovod_tpu.parallel.ring_attention import KEPT_NAMES, ring_attention
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
    # the block's kinds; the defaults are the GPT-2 block
    attention: str = "mha"   # "mha": fused QKV of head_dim, learned
    #                          positions; "mla": latent attention, rotary
    mlp: str = "gelu"        # the dense layers' MLP: "gelu" | "swiglu"
    tied_head: bool = True   # logits through embed.T; else a head matrix
    # recompute each block in the backward pass (pp = 1; pp_remat is the
    # pipeline's): a block keeps its input and, through the Pallas
    # attention path, the kernel's fp32 result and its row statistics
    # (ring_attention.KEPT_NAMES), so the forward kernel is not run again
    remat: bool = False
    # latent attention ("mla"; head_dim is not used)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    rope_theta: float = 10000.0
    # expert layers (ep over the dp axis): with n_experts > 0 every
    # layer after the first n_dense_layers routes each token to
    # experts_per_token of n_experts; a rank holds experts_held of them,
    # rank r those from r * experts_held on, and computes that share of
    # the layer
    n_experts: int = 0
    experts_held: int = 0
    experts_per_token: int = 0
    d_expert: int = 0
    shared_experts: int = 0  # a shared expert of this many x d_expert
    routed_scale: float = 1.0
    # the router's kind: "sigmoid" scores with a selection bias (a
    # buffer), or a "softmax" over all experts with none
    router: str = "sigmoid"
    n_dense_layers: int = 0
    # multi-token prediction: one module predicting token i + 2, its
    # cross entropy weighted mtp_lambda (depth 0 = none, 1)
    mtp_depth: int = 0
    mtp_lambda: float = 0.3
    # the experts' form, the shared expert's too: "swiglu" (gate, up,
    # down) | "relu2" (up, down: W_down relu(W_up h)^2)
    expert_form: str = "swiglu"
    # the epsilon of every RMSNorm
    norm_eps: float = 1e-6
    # a layer is ONE pre-normed sub-layer with one residual, of the kind
    # the pattern gives it (a string or a tuple, one letter a layer):
    # "M" a Mamba-2 mixer, "*" grouped-query attention without
    # positions, "E" the expert layer alone; "S" and "G" gated
    # grouped-query attention with an RMSNorm on each head's q and k,
    # "S" under the sliding window with rotary positions, "G" over the
    # whole past with none; "D" a dense SwiGLU FFN of d_ff; "I"
    # grouped-query attention with QK-norm and rotary positions over the
    # index_topk keys that an indexer of index_heads heads of
    # index_head_dim (one key head) scores highest for each query; "R"
    # grouped-query attention with QK-norm and rotary positions over the
    # whole causal past, ungated; "C" a gated short convolution of
    # blocks.CONV_TAPS taps, C * conv(B * x) over three projections of
    # the stream (blocks.STACK_OF is the table).  Empty: the uniform stack of
    # attention + MLP blocks above.  n_layers is the pattern's length.
    layer_pattern: tuple = ()
    # "S": query i sees key j iff 0 <= i - j < window
    window: int = 0
    # a pattern's layer is x + N_post(f(N_pre(x))): a second learned
    # RMSNorm on what the sub-layer gives, before the residual add
    post_norm: bool = False
    # a pattern's embedding rows are multiplied by this as they enter
    # the stream, and drawn at 1 / embed_scale
    embed_scale: float = 1.0
    # the depth the residual stream is drawn for: every sub-layer's
    # out-projection is divided by sqrt(rescale_depth) at initialisation
    # (a pre-norm stack's rule; the published depth where fewer layers
    # are held); 0 = fan-in scaling alone
    rescale_depth: int = 0
    n_kv_heads: int = 0      # "*": key/value heads (0 = n_heads)
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # "I": positions have three components, and of a head's head_dim / 2
    # rotary frequency pairs the first rope_sections[0] turn by the
    # first, the next rope_sections[1] by the second, the rest by the
    # third (text: the three equal).  Empty: one component
    rope_sections: tuple = ()
    # "M": ssm_heads heads of ssm_head_dim, B and C in ssm_groups groups
    # of ssm_state, a causal depthwise convolution of ssm_conv taps,
    # the scan in chunks of ssm_chunk steps
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 128
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
        if self.attention not in ("mha", "mla"):
            raise ValueError(f"attention must be 'mha' or 'mla', got "
                             f"{self.attention!r}")
        if self.mlp not in ("gelu", "swiglu"):
            raise ValueError(f"mlp must be 'gelu' or 'swiglu', got "
                             f"{self.mlp!r}")
        if self.router not in ("sigmoid", "softmax"):
            raise ValueError(f"router must be 'sigmoid' or 'softmax', got "
                             f"{self.router!r}")
        object.__setattr__(self, "rope_sections", tuple(self.rope_sections))
        if self.expert_form not in ("swiglu", "relu2"):
            raise ValueError(f"expert_form must be 'swiglu' or 'relu2', "
                             f"got {self.expert_form!r}")
        object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))
        if self.layer_pattern:
            object.__setattr__(self, "n_layers", len(self.layer_pattern))
            self._check_pattern()
        elif self.n_experts and self.mlp != "swiglu":
            raise ValueError("in the uniform stack the expert layers "
                             "follow SwiGLU dense layers: n_experts > 0 "
                             "needs mlp='swiglu'")
        if self.mtp_depth not in (0, 1):
            raise ValueError(f"mtp_depth must be 0 or 1: {self.mtp_depth}")
        if self.mtp_depth and not (self.n_experts
                                   and self.attention == "mla"):
            raise ValueError("the MTP module is a latent-attention expert "
                             "block: it needs attention='mla' and "
                             "n_experts > 0")

    def _check_pattern(self):
        """The pattern's kinds are ``blocks.STACK_OF``'s, and each kind
        has the sizes it needs."""
        from horovod_tpu.models.blocks import ATTENTION_KINDS, STACK_OF

        kinds = set(self.layer_pattern)
        if kinds - set(STACK_OF):
            raise ValueError(
                f"layer_pattern holds {', '.join(map(repr, STACK_OF))}: "
                f"{sorted(kinds - set(STACK_OF))}")
        if self.tied_head or self.mtp_depth:
            raise ValueError("a layer pattern needs tied_head=False and "
                             "mtp_depth=0")
        if "E" in kinds and not self.n_experts:
            raise ValueError("'E' layers need n_experts > 0")
        if kinds & set(ATTENTION_KINDS) and self.n_heads % (
                self.n_kv_heads or self.n_heads):
            raise ValueError(f"n_heads {self.n_heads} is no multiple of "
                             f"n_kv_heads {self.n_kv_heads}")
        if "S" in kinds and (self.window < 1 or self.head_dim % 2):
            raise ValueError("'S' layers need window >= 1 and an even "
                             "head_dim (rotary positions)")
        if "R" in kinds and self.head_dim % 2:
            raise ValueError("'R' layers need an even head_dim (rotary "
                             "positions)")
        if "I" in kinds and (min(self.index_heads, self.index_head_dim,
                                 self.index_topk) < 1 or self.head_dim % 2):
            raise ValueError("'I' layers need index_heads, index_head_dim, "
                             "index_topk and an even head_dim (rotary "
                             "positions)")
        if self.rope_sections and (len(self.rope_sections) != 3 or sum(
                self.rope_sections) != self.head_dim // 2):
            raise ValueError(
                f"rope_sections {self.rope_sections} are three counts that "
                f"add up to head_dim / 2 = {self.head_dim // 2}")
        if "M" in kinds and (self.ssm_heads < 1 or self.ssm_head_dim < 1
                             or self.ssm_state < 1
                             or self.ssm_heads % self.ssm_groups):
            raise ValueError("'M' layers need ssm_heads (a multiple of "
                             "ssm_groups), ssm_head_dim and ssm_state")

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def n_dense(self) -> int:
        """Layers with a dense MLP: all of them without experts, none
        under a layer pattern."""
        if self.layer_pattern:
            return 0
        return (min(self.n_dense_layers, self.n_layers) if self.n_experts
                else self.n_layers)

    @property
    def n_expert_layers(self) -> int:
        if self.layer_pattern:
            return self.layer_pattern.count("E")
        return self.n_layers - self.n_dense

    def is_expert_layer(self, layer: int) -> bool:
        return layer >= self.n_dense

    @property
    def gpt2_block(self) -> bool:
        """Every kind at its default: nothing of ``models/blocks.py``."""
        return (self.attention == "mha" and self.mlp == "gelu"
                and self.tied_head and not self.mtp_depth
                and not self.layer_pattern)


def init_params(rng: np.random.RandomState, cfg: TransformerConfig,
                ep: int = 1) -> dict:
    """Full (unsharded) parameter pytree; shard_map in_specs split the
    tp/pp dimensions at dispatch."""
    dm, hd, nh, ff, nl = (cfg.d_model, cfg.head_dim, cfg.n_heads,
                          cfg.d_ff, cfg.n_layers)

    def norm(*shape, scale):
        return (rng.randn(*shape) * scale).astype(np.float32)

    # A tied embedding is the head too and takes a head's small scale.
    # An untied one is drawn at the scale of what the blocks add to the
    # stream (fan-in scaled matrices write about 1 a token): at 0.02 the
    # first attention's causal mean, about 1 / sqrt(position), swamps
    # the token's own row, the tokens of a sequence collapse onto one
    # direction and every one of them picks the same experts (PERF.md
    # section 6, PR 28).
    p = {"embed": norm(cfg.vocab, dm, scale=(0.02 if cfg.tied_head
                                             else 1.0 / cfg.embed_scale))}
    if cfg.layer_pattern:
        from horovod_tpu.models import blocks

        p.update(blocks.init_pattern(norm, rng, cfg, ep))
        return jax.tree_util.tree_map(jnp.asarray, p)
    if cfg.attention == "mha":
        p["pos"] = norm(cfg.max_seq, dm, scale=0.02)
        layers = {
            "wqkv": norm(nl, dm, 3 * nh * hd, scale=dm ** -0.5),
            "wo": norm(nl, nh * hd, dm, scale=(nh * hd) ** -0.5),
        }
    else:
        from horovod_tpu.models import blocks

        layers = blocks.init_mla(norm, cfg, nl)
    if cfg.mlp == "gelu":
        layers["w1"] = norm(nl, dm, ff, scale=dm ** -0.5)
        layers["w2"] = norm(nl, ff, dm, scale=ff ** -0.5)
    layers["ln1"] = np.ones((nl, dm), np.float32)
    layers["ln2"] = np.ones((nl, dm), np.float32)
    p["ln_f"] = np.ones(dm, np.float32)
    p["layers"] = layers
    if not cfg.gpt2_block:
        from horovod_tpu.models import blocks

        p.update(blocks.init_extra(norm, cfg, ep))
    return jax.tree_util.tree_map(jnp.asarray, p)


def param_specs(cfg: TransformerConfig):
    """PartitionSpecs for shard_map in_specs: tp shards the
    column/row-parallel matrices; experts shard over dp (=ep)."""
    from jax.sharding import PartitionSpec as P

    # layer stacks shard over pp (each stage holds only its layers)
    # and tp (column/row parallel matrices)
    specs = {"embed": P(), "ln_f": P()}
    if cfg.layer_pattern:
        from horovod_tpu.models import blocks

        specs.update(blocks.pattern_specs(cfg))
        return specs
    if cfg.attention == "mha":
        specs["pos"] = P()
        layers = {"wqkv": P("pp", None, "tp"), "wo": P("pp", "tp", None)}
    else:
        from horovod_tpu.models import blocks

        layers = blocks.mla_specs("pp")
    if cfg.mlp == "gelu":
        layers["w1"] = P("pp", None, "tp")
        layers["w2"] = P("pp", "tp", None)
    layers["ln1"] = P("pp")
    layers["ln2"] = P("pp")
    specs["layers"] = layers
    if not cfg.gpt2_block:
        from horovod_tpu.models import blocks

        specs.update(blocks.extra_specs(cfg))
    return specs


def _rmsnorm(x, g, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 / rms) * g).astype(x.dtype)


def _block(cfg: TransformerConfig, lp, x, positions=None, ffn=None,
           ffn_weights=None):
    """One transformer block, per-device view.  x: (b, lc, dm).
    ``ffn`` (``models/blocks.py``: SwiGLU or the expert layer, with its
    weights) replaces the stack's own GELU MLP; ``positions`` are the
    rotary ones of latent attention.  Returns ``(x, pairs)``: the
    expert layer's pairs per held expert, else ``None``."""
    b, lc, dm = x.shape
    cd = cfg.compute_dtype

    h = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
    if cfg.attention == "mla":
        from horovod_tpu.models import blocks

        with jax.named_scope("hvd_mla"):
            proj = blocks.mla(cfg, lp, h, positions)
    else:
        tp = lax.axis_size("tp")
        nh_local = cfg.n_heads // tp
        h = copy_to_tp(h, "tp")  # Megatron "f": bwd sums shard contributions
        qkv = (h.astype(cd) @ lp["wqkv"].astype(cd))
        qkv = qkv.reshape(b, lc, 3, nh_local, cfg.head_dim)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        with jax.named_scope("hvd_attn"):
            attn = ring_attention(q, k, v, "sp", causal=True,
                                  impl=cfg.attn_impl, recomputed=cfg.remat)
        attn = attn.reshape(b, lc, nh_local * cfg.head_dim)
        proj = (attn.astype(cd) @ lp["wo"].astype(cd)).astype(jnp.float32)
        proj = reduce_from_tp(proj, "tp")  # Megatron "g": row-parallel reduce
    x = x + proj.astype(x.dtype)

    h = _rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if ffn is not None:
        mlp, pairs = ffn(cfg, ffn_weights, h)
    else:
        h = copy_to_tp(h, "tp")
        ff = jax.nn.gelu((h.astype(cd) @ lp["w1"].astype(cd))
                         .astype(jnp.float32)).astype(cd)
        mlp = (ff @ lp["w2"].astype(cd)).astype(jnp.float32)
        mlp = reduce_from_tp(mlp, "tp")
        pairs = None
    x = x + mlp.astype(x.dtype)
    return x, pairs


def _stack(params, tokens, cfg: TransformerConfig):
    """Per-device embedding and layer stack inside shard_map over
    ('dp','pp','tp','sp').  tokens: (b_local, lc_local) int32.  Returns
    ``(x (b, lc, dm) before the final norm, positions (lc,) global,
    [pairs of each expert layer])``."""
    cd = cfg.compute_dtype
    sp_idx = lax.axis_index("sp")
    nstages = lax.axis_size("pp")
    b, lc = tokens.shape
    pos = sp_idx * lc + jnp.arange(lc)
    if cfg.layer_pattern:
        from horovod_tpu.models import blocks

        return blocks.pattern_stack(cfg, params, params["embed"][tokens],
                                    pos)
    if cfg.attention == "mha":
        x = (params["embed"][tokens] + params["pos"][pos]).astype(cd)
    else:
        x = params["embed"][tokens].astype(cd)

    layers = params["layers"]
    local_layers = layers["ln1"].shape[0]  # n_layers / pp per stage
    pairs = []
    block = _remat_block if cfg.remat else _block

    if nstages == 1:
        for i in range(local_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], layers)
            if cfg.mlp == "gelu":
                ffn = weights = None
            else:
                from horovod_tpu.models import blocks

                ffn, weights = blocks.ffn_of(cfg, params, i)
            x, routed = block(cfg, lp, x, pos, ffn, weights)
            if routed is not None:
                pairs.append(routed)
    else:
        if cfg.mlp != "gelu" or cfg.attention != "mha":
            raise NotImplementedError(
                "latent attention, SwiGLU and expert layers under "
                "pipeline parallelism are not supported yet; pp > 1 "
                "runs the GPT-2 block (attention='mha', mlp='gelu').")

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
    return x, pos, pairs


# Recomputed in the backward pass where the configuration asks for it
# (``remat``): a loss head then keeps only its inputs (the stream, the
# gain, the table, the targets; its replay rebuilds the compute-type
# logits and each row's log-sum-exp, and neither pass writes an f32
# (b, lc, vocab) tensor: ``_table_nll``), and a block its
# input and what the attention kernel gave (fp32 ``out`` and ``lse``,
# under ``ring_attention.KEPT_NAMES``), 270 MB a block in the expert
# cell for a forward kernel that is not run a second time (PERF.md
# section 6, PR 32).  The plain functions are called directly otherwise:
# every Python frame between ``loss_fn`` and an operation is paid for
# again by each trace of the step (PERF.md section 6, PR 28: two frames
# more were 2 s of the GPT-2 cells' set-up on the chip's host).
_remat_block = jax.checkpoint(
    _block, static_argnums=(0, 4),
    policy=jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES))


def _logits(cfg: TransformerConfig, x, gain, table):
    """Final norm and logits through ``table`` (``embed``, transposed,
    or the untied head), (b, lc, vocab) f32: the product of the
    compute-type operands, rounded once to the compute type; the cast
    adds no bit.  For callers that want logits (:func:`forward`); the
    loss reads them in the compute type (:func:`_head_nll`)."""
    rows = _rmsnorm(x, gain, cfg.norm_eps).astype(cfg.compute_dtype)
    with jax.named_scope("hvd_loss_head"):
        return _table_logits(cfg.tied_head, rows,
                             table).astype(jnp.float32)


def _table_logits(tied: bool, rows, table):
    """``rows`` (b, lc, dm) through ``table`` ((vocab, dm) if ``tied``,
    else (dm, vocab)), both in ``rows``' type, as the result."""
    table = table.astype(rows.dtype)
    return rows @ (table.T if tied else table)


def _is_target(logits, targets):
    """(b, lc, vocab) bool: the target's place in each row."""
    return lax.broadcasted_iota(jnp.int32, logits.shape,
                                logits.ndim - 1) == targets[..., None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _table_nll(tied: bool, rows, table, targets):
    """The targets' negative log likelihood, (b, lc) f32, under the
    logits of :func:`_table_logits`, by a rule of its own in both
    passes: nothing of shape (b, lc, vocab) is written in f32 where the
    compute type is narrower (PERF.md section 6, PR 39: the f32
    ``log_softmax`` was 3.3 GB and 7.5 ms a step in ``gpt2-124m.s1024``
    for one reader, the targets' gather)."""
    return _table_nll_fwd(tied, rows, table, targets)[0]


def _table_nll_fwd(tied, rows, table, targets):
    # the row's log-sum-exp and the target's logit, in f32 arithmetic
    # inside reductions that read the compute-type logits.  Kept: the
    # rows, the table, those logits, ``lse``, the targets.
    with jax.named_scope("hvd_loss_head"):
        logits = _table_logits(tied, rows, table)
        l32 = logits.astype(jnp.float32)
        top = jnp.max(l32, axis=-1)
        lse = top + jnp.log(jnp.sum(jnp.exp(l32 - top[..., None]), axis=-1))
        hit = _is_target(logits, targets)
        picked = jnp.sum(jnp.where(hit, l32, 0.0), axis=-1)
        return lse - picked, (rows, table, logits, lse, targets)


def _table_nll_bwd(tied, kept, g):
    # d nll / d logits = softmax - [v == target], times the row's
    # cotangent, rounded to the compute type where each of the two
    # products reads it: XLA builds it inside their operands, no scatter
    # into (b, lc, vocab) zeros and no f32 copy.
    rows, table, logits, lse, targets = kept
    with jax.named_scope("hvd_loss_head"):
        hit = _is_target(logits, targets)
        dl = ((jnp.exp(logits.astype(jnp.float32) - lse[..., None])
               - hit.astype(jnp.float32)) * g[..., None]).astype(rows.dtype)
        cast = table.astype(rows.dtype)
        if tied:
            d_rows = dl @ cast
            d_table = jnp.einsum("...v,...d->vd", dl, rows)
        else:
            d_rows = dl @ cast.T
            d_table = jnp.einsum("...d,...v->dv", rows, dl)
        return d_rows, d_table.astype(table.dtype), None


_table_nll.defvjp(_table_nll_fwd, _table_nll_bwd)


def _head_nll(cfg: TransformerConfig, x, gain, table, targets):
    """The targets' negative log likelihood, (b, lc) f32: the final
    norm, differentiated by JAX's own rule, then :func:`_table_nll`.
    The backward pass keeps the normalised rows in the compute type, the
    table, the compute-type logits, each row's log-sum-exp and the
    targets; no f32 (b, lc, vocab) tensor is written in either pass."""
    rows = _rmsnorm(x, gain, cfg.norm_eps).astype(cfg.compute_dtype)
    return _table_nll(cfg.tied_head, rows, table, targets)


# :func:`_head_nll` keeping only its inputs: the replay rebuilds what
# the backward rule reads (the comment above ``_remat_block``).
_remat_head_nll = jax.checkpoint(_head_nll, static_argnums=(0,))


def forward(params, tokens, cfg: TransformerConfig):
    """Per-device forward inside shard_map over ('dp','pp','tp','sp').

    tokens: (b_local, lc_local) int32.  Returns (logits fp32
    (b, lc, vocab), [pairs per held expert of each expert layer]).
    """
    x, _, pairs = _stack(params, tokens, cfg)
    table = params["embed"] if cfg.tied_head else params["head"]
    return _logits(cfg, x, params["ln_f"], table), pairs


def loss_fn(params, tokens, targets, cfg: TransformerConfig,
            with_routing: bool = False):
    """LOCAL slice of the global-mean cross entropy (plus
    ``mtp_lambda`` times the MTP module's, where there is one).

    Deliberately psum-free: local token-loss sum divided by the GLOBAL
    token count (a static number), so that one explicit psum of the
    gradients reconstructs exactly the global-mean gradient.  Putting a
    psum inside the differentiated loss would double-count — psum
    transposes to psum, inflating gradients by the data-axis size.
    Report the global loss by psumming this value outside the grad.

    ``with_routing`` returns ``(loss, pairs)``: for every expert layer
    (the MTP module's last) the (token, expert) pairs each held expert
    computed, (layers, held) int32 (:data:`loss_and_routing`).  Under a
    layer pattern the second value is a dict of what the kinds report,
    a row a layer of the kind: ``loads`` (expert layers, n_experts)
    int32, the pairs the routing sent each of all the experts, held
    here or not (what ``moe.settle_bias`` balances; the held experts'
    columns are the pairs computed); ``least_log_decay`` (state-space
    layers,) float32, the least logarithm of a whole chunk's decay
    (:func:`record_scan`); ``selections`` (indexed attention layers, b,
    lc, W) int32, the packed selection each ran under
    (:func:`record_selection`).
    """
    x, pos, pairs = _stack(params, tokens, cfg)
    head_nll = _remat_head_nll if cfg.remat else _head_nll
    table = params["embed"] if cfg.tied_head else params["head"]
    nll = head_nll(cfg, x, params["ln_f"], table, targets)
    data_ranks = lax.axis_size("dp") * lax.axis_size("sp")
    global_tokens = jnp.float32(nll.size) * data_ranks
    loss = jnp.sum(nll) / global_tokens
    if cfg.mtp_depth:
        from horovod_tpu.models import blocks

        block = _remat_block if cfg.remat else _block
        extra, routed = blocks.mtp_loss(
            cfg, params, x, targets,
            lambda lp, w, h: block(cfg, lp, h, pos, blocks.expert_ffn, w),
            lambda h, gain, nxt: head_nll(cfg, h, gain, table, nxt))
        loss = loss + cfg.mtp_lambda * extra
        pairs.append(routed)
    if not with_routing:
        return loss
    if cfg.layer_pattern:
        return loss, {kind: jnp.stack(rows) for kind, rows in pairs.items()
                      if rows}
    return loss, (jnp.stack(pairs) if pairs
                  else jnp.zeros((0, 0), jnp.int32))


# ``jax.value_and_grad(loss_and_routing, has_aux=True)`` gives the loss,
# the routing and the gradients from one program.
loss_and_routing = functools.partial(loss_fn, with_routing=True)


def record_routing(cfg: TransformerConfig, pairs, tokens: int) -> list:
    """Write what a batch's routing sent the held experts to the flight
    ring: one ``hvd_moe_route`` record an expert layer (the MTP
    module's last) with ``pairs`` — a row of :func:`loss_and_routing`'s
    second value, summed over ``sp`` and in expert order over the
    ``dp`` ranks — ``tokens`` (in the batch), ``top_k``, ``dropped``,
    which the dropless layer keeps at 0, ``chunk_rows``, the rows
    ``moe.chunk_rows`` gives a chunk for that many tokens, and
    ``chunks``, the trips the busiest rank's pairs take: ``sum(pairs) /
    (chunks x chunk_rows)`` is the share of the rows run that held a
    pair (one rank).  Returns the records."""
    from horovod_tpu.parallel import moe
    from horovod_tpu.runtime import flight

    rows = moe.chunk_rows(int(tokens) * cfg.experts_per_token,
                          cfg.experts_held, cfg.n_experts)
    records = [dict(layer=i, pairs=[int(n) for n in row], tokens=int(tokens),
                    top_k=cfg.experts_per_token, dropped=0, chunk_rows=rows,
                    chunks=int(-(-row.reshape(-1, cfg.experts_held)
                                 .sum(axis=1).max() // rows)))
               for i, row in enumerate(np.asarray(pairs))]
    for record in records:
        flight.record("hvd_moe_route", **record)
    return records


def record_scan(cfg: TransformerConfig, least_log_decay) -> list:
    """Write one ``hvd_ssm_scan`` record a state-space layer to the
    flight ring: the chunk's length, the chunks a sequence of
    ``max_seq`` is cut into, heads and state size, and
    ``least_log_decay`` — a row of :func:`loss_and_routing`'s
    ``least_log_decay``, the least over the ``dp`` and ``sp`` ranks: the
    least logarithm of a whole chunk's decay in that batch, which says
    how near the scan's ``exp`` comes to underflow (float32's smallest
    normal number is ``exp(-87.3)``; below it a chunk's incoming state
    is simply forgotten).  Returns the records."""
    from horovod_tpu.runtime import flight

    records = [dict(layer=i, chunk=cfg.ssm_chunk,
                    chunks=-(-cfg.max_seq // cfg.ssm_chunk),
                    heads=cfg.ssm_heads, state=cfg.ssm_state,
                    least_log_decay=float(least))
               for i, least in enumerate(np.asarray(least_log_decay))]
    for record in records:
        flight.record("hvd_ssm_scan", **record)
    return records


def record_attention(cfg: TransformerConfig, batch: int) -> list:
    """Write one ``hvd_attn_window`` record an attention layer of the
    pattern to the flight ring: ``layer``, ``layer_kind``, ``window``
    (0 = none: the causal bound alone), ``seq`` (``max_seq``, one
    chip's: ``sp`` 1), the path ``impl`` and the tiles ``block_q`` x
    ``block_k`` that ``ring_attention`` picks for ``batch`` sequences
    under that window, and of one head's forward call at those tiles
    the ``grid`` steps it walks (every tile pair without a window,
    under one ``band`` K tiles a Q row:
    ``pallas_attention.walked_steps``; ``band`` 0 = no window) and the
    ``live`` / ``masked`` tile pairs (``causal_tile_counts``: those
    that do any work, those that build a mask), the edge ``sub`` of the
    sub-rows a masked tile is computed by (``sub_edge``; 0 = whole)
    and the ``computed_pairs`` the call multiplies (``computed_pairs``:
    over the window's own pairs, what the tiles leave); the same eight
    of the backward kernels, whose tiles a window may cut to its band, as
    ``bwd_block_q`` ... ``bwd_computed_pairs`` (a head's dQ call; the
    dK/dV call walks and multiplies the same at square tiles).  Returns
    the records."""
    from horovod_tpu.models.blocks import ATTENTION_KINDS
    from horovod_tpu.ops.pallas_attention import (causal_tile_counts,
                                                  computed_pairs, sub_edge,
                                                  walked_steps)
    from horovod_tpu.parallel.ring_attention import _block_sizes, auto_impl
    from horovod_tpu.runtime import flight

    seq = cfg.max_seq
    impl = cfg.attn_impl or (auto_impl(batch, cfg.n_heads, seq)
                             if jax.default_backend() == "tpu" else "xla")

    def counts(window, backward):
        bq, bk = _block_sizes(seq, seq, cfg.head_dim,
                              cfg.compute_dtype.itemsize, window=window,
                              backward=backward)
        if not (bq and bk):
            return dict(block_q=0, block_k=0, grid=0, band=0, live=0,
                        masked=0, sub=0, computed_pairs=0)
        # the one chunk's offsets are 0 and 0: multiples of the chunk,
        # as ring_attention tells the kernels
        grid, band = walked_steps(seq, seq, bq, bk, window, seq)
        _, live, masked = causal_tile_counts(seq, seq, bq, bk,
                                             window=window)
        return dict(block_q=bq, block_k=bk, grid=grid, band=band,
                    live=live, masked=masked,
                    sub=sub_edge(bq, bk, window, seq),
                    computed_pairs=computed_pairs(seq, seq, bq, bk, window,
                                                  seq))

    records = []
    for layer, kind in enumerate(cfg.layer_pattern):
        if kind not in ATTENTION_KINDS:
            continue
        window = cfg.window if kind == "S" else None
        records.append(dict(
            layer=layer, layer_kind=kind, window=window or 0, seq=seq,
            impl=impl, **counts(window, False),
            **{"bwd_" + k: v for k, v in counts(window, True).items()}))
    for record in records:
        flight.record("hvd_attn_window", **record)
    return records


def record_conv(cfg: TransformerConfig, batch: int) -> list:
    """Write one ``hvd_conv_mix`` record a gated short-convolution layer
    of the pattern to the flight ring: ``layer`` (its place in the
    pattern), ``seq`` (``max_seq``, one chip's: ``sp`` 1), ``channels``,
    ``taps`` (``blocks.CONV_TAPS``), ``impl`` (``xla``: the compiler's
    fusions compute the gates and the convolution) and
    ``required_bytes``, what one pass over ``batch`` sequences requires:
    ``B``, ``C`` and ``x`` read and ``y`` written once at the compute
    type (the taps are negligible).  Returns the records."""
    from horovod_tpu.models import blocks
    from horovod_tpu.runtime import flight

    pass_bytes = (4 * batch * cfg.max_seq * cfg.d_model
                  * cfg.compute_dtype.itemsize)
    records = [dict(layer=layer, seq=cfg.max_seq, channels=cfg.d_model,
                    taps=blocks.CONV_TAPS, impl="xla",
                    required_bytes=pass_bytes)
               for layer, kind in enumerate(cfg.layer_pattern) if kind == "C"]
    for record in records:
        flight.record("hvd_conv_mix", **record)
    return records


def record_selection(cfg: TransformerConfig, selections) -> list:
    """Write one ``hvd_dsa_select`` record an indexed attention layer to
    the flight ring from ``selections``, :func:`loss_and_routing`'s
    (layers, batch, seq, W) packed words of one chip (``sp`` 1):
    ``layer``, ``seq``, ``topk``, the ``kept_pairs`` the words hold and
    the ``causal_pairs`` of that many sequences (a head's), the
    ``operand`` the kernels read the selection from and its
    ``operand_bytes`` a layer, and of one head's forward call the path
    ``impl``, its ``block_q`` x ``block_k``, the ``tiles`` its grid
    walks and the ``live_tiles`` that do any work (all of them build a
    mask from the words); and of the indexer the path ``index_impl``
    that made the selection (``blocks.index_impl``: ``"kernel"`` or
    ``"xla"``) and the ``index_pairs`` it scored for that many sequences
    (the XLA form every key of every row, ``seq²`` a sequence; the
    kernel the key tiles up to each row block's last row, none where
    every row of a block keeps all it sees).  Returns the records."""
    from horovod_tpu.models import blocks
    from horovod_tpu.ops.pallas_attention import causal_tile_counts
    from horovod_tpu.ops.pallas_index import scored_pairs
    from horovod_tpu.parallel.ring_attention import _block_sizes, auto_impl
    from horovod_tpu.runtime import flight

    words = np.asarray(selections)
    batch, seq = words.shape[1:3]
    impl = cfg.attn_impl or (auto_impl(batch, cfg.n_heads, seq)
                             if jax.default_backend() == "tpu" else "xla")
    bq, bk = _block_sizes(seq, seq, cfg.head_dim, cfg.compute_dtype.itemsize)
    tiles, live, _ = (causal_tile_counts(seq, seq, bq, bk) if bq and bk
                      else (0, 0, 0))
    index = blocks.index_impl(cfg, seq)
    scored = (scored_pairs(seq, min(cfg.index_topk, seq)) if index == "kernel"
              else seq * seq)
    records = [dict(layer=i, seq=seq, topk=cfg.index_topk,
                    kept_pairs=int(np.unpackbits(row.view(np.uint8)).sum()),
                    causal_pairs=batch * seq * (seq + 1) // 2,
                    operand="packed_mask", operand_bytes=int(row.nbytes),
                    impl=impl, block_q=bq or 0, block_k=bk or 0,
                    tiles=tiles, live_tiles=live, index_impl=index,
                    index_pairs=batch * scored)
               for i, row in enumerate(words)]
    for record in records:
        flight.record("hvd_dsa_select", **record)
    return records


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
    # instead of allocating fresh buffers every step; callers follow the
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
