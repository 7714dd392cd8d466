"""Family ``lm_moe_mla``: a latent-attention mixture-of-experts language
model (the DeepSeek-V3 key set: JoyAI-LLM-Flash) through the flagship
path — ``TransformerConfig`` + ``init_params`` + ``shard_params`` +
``make_train_step`` on a ``make_mesh`` mesh — cut to one chip's share
of a stated deployment (the configuration file's ``deployment``).

No ``attn_impl`` is forced and no ``HOROVOD_*`` variable is set.

The configuration file's ``n_routed_experts`` is the number of experts
held here (the first that many of ``router_width``), ``vocab_size`` the
slice of the vocabulary held here, ``num_hidden_layers`` the layers kept
(``first_k_dense_replace`` dense ones, then expert layers); the MTP
module comes on top.

The plain reference reads the system's parameter tree and computes the
same two-term loss in float32 with ``jax.numpy`` only: no kernel, no
``shard_map``, a Python loop over the experts held (every expert on
every token, under a mask), attention as a masked softmax over query
blocks under ``jax.checkpoint``, given the same share.
"""

from __future__ import annotations

import math

from benchmark.families.lm_mesh import _DeviceRandn

# ---------------------------------------------------------------------------
# The comparison that decides ``correct``
# ---------------------------------------------------------------------------

# Step-0 loss, and the gradient's norm group by group, of the system
# (bf16 products with f32 accumulation, a bf16 residual stream, f32
# norms, router, logits and parameters) against the float32 reference,
# relative, on one 8,192-token sequence at the published widths.  Read
# on the chip (PR 28, PERF.md section 6): the largest the system gave
# over thirteen seeds; the least the reference itself gave over four
# seeds when computed in bfloat16 throughout (the nearest precision
# below the configuration's), which must come out as not correct; and
# the least the system gave over two seeds with the rotary left off the
# shared key (the fault the CPU tests plant, at the cell's own size):
#
#     group        system,   bfloat16 reference,  no rotary on the
#                  largest   least                shared key, least
#     loss         4.6e-5    5.6e-6               1.7e-5
#     mla          1.4e-3    3.9e-4               5.6e-3
#     dense        2.3e-4    9.3e-4               1.3e-4
#     router       2.6e-3    2.3e-2               2.4e-3
#     experts      1.1e-3    1.6e-2               1.1e-3
#     shared       2.2e-4    6.1e-5               2.0e-6
#     mtp          2.6e-3    2.8e-3               2.5e-3
#     embed_head   2.7e-4    2.9e-4               2.1e-4
#
# A lower precision shows where the top-8 is taken (a bfloat16 router
# flips selections: ``router`` and ``experts`` move by 1.6-3.0 %, where
# the system's f32 router over a bf16 stream flips under 1 % of the
# pairs, ``pairs_sent_otherwise``) and in the wide dense layer; the
# missing rotary shows in ``mla`` alone.  Those four limits lie between
# their readings, with the more room above the system's, since fresh
# seeds read higher: ``router`` 3.8 times above and 2.3 below,
# ``experts`` 3.5 and 3.9, ``dense`` 3.0 and 1.3, ``mla`` 3.6 above and
# 1.1 below (the two faulty readings are 5.6e-3 and 7.1e-3).  The other
# four have no reading from above: neither control moves them out of
# the system's own range (``mtp`` and ``embed_head`` read the same 2.5e-3
# and 2.6e-4 on every seed, a bias of the bf16 stream and not noise; at
# random weights a bf16 logit is as good as any, so the loss does not
# move either).  They stand three times above the system's largest (the
# loss six times) and are there for a left-out term, which moves them
# by tens of percent (tests/benchmark_suite/test_benchmark_moe_mla.py).
# ``b`` reaching the weights is caught whatever the limits: the bias's
# gradient, which must be exactly 0, read 2.3e-3 at the cell's size.
LOSS_RTOL = 3e-4
GROUP_RTOL = {"mla": 5e-3, "dense": 7e-4, "router": 1e-2, "experts": 4e-3,
              "shared": 7e-4, "mtp": 8e-3, "embed_head": 8e-4}


def _kwargs(config: dict, job: dict) -> dict:
    """``TransformerConfig``'s arguments from the configuration file."""
    assert config["rms_norm_eps"] == 1e-6, "transformer._rmsnorm's eps"
    assert config["scoring_func"] == "sigmoid" and config["n_group"] == 1
    assert config["norm_topk_prob"] and not config["tie_word_embeddings"]
    return dict(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"], max_seq=job["seq"],
        dtype=config["compute_dtype"], attention="mla", mlp="swiglu",
        tied_head=False, remat=True,
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        n_experts=config["router_width"],
        experts_held=config["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        shared_experts=config["n_shared_experts"],
        routed_scale=config["routed_scaling_factor"],
        n_dense_layers=config["first_k_dense_replace"],
        mtp_depth=config["num_nextn_predict_layers"], mtp_lambda=0.3)


# ---------------------------------------------------------------------------
# Operations the architecture and its kernels require, from shapes
# ---------------------------------------------------------------------------


def _macs_per_token(config: dict) -> dict:
    """Multiply-accumulates of one token's forward pass through each
    kind of part, attention's score products left out."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    mla = (d * config["q_lora_rank"] + config["q_lora_rank"] * heads * qk
           + d * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
           + config["kv_lora_rank"] * heads
           * (config["qk_nope_head_dim"] + config["v_head_dim"])
           + heads * config["v_head_dim"] * d)
    expert = 3 * d * config["moe_intermediate_size"]
    # a token's top-k choices fall on the experts held here with
    # probability held / router width each: 8 * 16 / 256 = 0.5 experts
    routed = (config["num_experts_per_tok"] * config["n_routed_experts"]
              / config["router_width"])
    return {"mla": mla, "dense": 3 * d * config["intermediate_size"],
            "moe": (d * config["router_width"]
                    + (config["n_shared_experts"] + routed) * expert),
            "eh_proj": 2 * d * d, "head": d * config["vocab_size"]}


def model_flops_per_sample(config: dict, job: dict) -> float:
    """Forward plus backward of one sequence through this chip's share:
    3 x forward, 2 FLOPs a multiply-accumulate.  Forward: the latent
    attention's five matrices in every block, SwiGLU in the dense
    layers, router + shared expert + the routed experts at the expected
    0.5 a token in the expert layers, the MTP module (its projection and
    one expert block), both heads, and causal attention at half the
    square (QK^T at 192 and PV at 128 a head).  Nothing that is
    recomputed is counted; norms, rotary, SiLU and softmax are left out.
    At seq 8192: 27.84 TFLOP (tests/benchmark_suite has the hand-worked
    value)."""
    seq, m = job["seq"], _macs_per_token(config)
    dense = config["first_k_dense_replace"]
    experts = config["num_hidden_layers"] - dense
    mtp = config["num_nextn_predict_layers"]
    blocks = dense + experts + mtp
    per_token = (blocks * m["mla"] + dense * m["dense"]
                 + (experts + mtp) * m["moe"] + mtp * m["eh_proj"]
                 + (1 + mtp) * m["head"])
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    attention = (blocks * config["num_attention_heads"]
                 * (qk + config["v_head_dim"]) * seq * (seq + 1) / 2)
    return 3.0 * 2.0 * (seq * per_token + attention)


def kernel_costs(config: dict, job: dict) -> dict:
    """What one train step requires of the flash-attention kernels and
    of the experts' grouped products, over all blocks, per chip.

    ``mla_flash``: seven causal products a block, as
    ``lm_mesh.kernel_costs`` counts them, at two head sizes: QK^T
    forward, QK^T once more backward, dS K and dS^T Q contract or emit
    192 columns, PV, dO V^T and P^T dO 128.  The two products the
    backward kernels repeat and the forward pass that recomputation
    runs again are not required and not counted.  Bytes: bf16, each
    tensor once: q, k (192), v, o (128) forward; q, k, v, o, dO in and
    dq, dk, dv out backward; plus the f32 row statistics.

    ``moe_experts``: :func:`expert_cost` of the pairs the held experts
    are expected to be sent (tokens x top-k x held / router width, in
    every expert layer); ``moe_experts_roofline`` asks it again for the
    pairs the routing records show."""
    seq, mesh = job["seq"], job["mesh"]
    heads = config["num_attention_heads"] // mesh["tp"]
    rows = job["batch_per_chip"] * heads
    blocks = (config["num_hidden_layers"]
              + config["num_nextn_predict_layers"])
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    # 2 FLOPs a multiply-accumulate over seq * (seq + 1) / 2 score pairs
    pairs = float(rows * seq * (seq + 1))
    routed = (job["batch_per_chip"] * seq * config["num_experts_per_tok"]
              * config["n_routed_experts"] / config["router_width"])
    return {
        "mla_flash": {
            "flops": blocks * pairs * (4 * qk + 3 * v),
            "bytes": blocks * (2 * rows * seq * (6 * qk + 6 * v)
                               + 2 * 4 * rows * seq)},
        "moe_experts": expert_cost(
            config, (blocks - config["first_k_dense_replace"]) * routed),
    }


def expert_cost(config: dict, pairs: float) -> dict:
    """What the held experts' grouped products require of a step that
    sends them ``pairs`` (token, expert) pairs, all expert layers (the
    MTP module's too) added up: gate, up and down of each pair, forward
    and twice that backward.  Bytes: every layer's held experts' bf16
    weights read forward and backward and their gradients written, and
    a pair's rows (input and output at the hidden size, gate and up at
    the expert width, bf16) once forward and twice backward."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    layers = (config["num_hidden_layers"] - config["first_k_dense_replace"]
              + config["num_nextn_predict_layers"])
    held = config["n_routed_experts"]
    return {"flops": 3 * 2.0 * pairs * 3 * d * f,
            "bytes": 2 * (layers * 3 * held * 3 * d * f
                          + 3 * pairs * (2 * d + 2 * f))}


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

_QUERY_BLOCK = 512


def _rmsnorm(x, gain):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * gain


def _rotary(x, theta):
    """Interleaved pairs (2i, 2i+1) of the last axis rotated by
    position x theta ** (-2i / d); x: (batch, seq, ..., d)."""
    import jax.numpy as jnp

    seq, d = x.shape[1], x.shape[-1]
    angle = (jnp.arange(seq, dtype=jnp.float32)[:, None]
             * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = angle.reshape((1, seq) + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _causal_attention(q, k, v):
    """q, k: (batch, seq, heads, 192), v: (batch, seq, heads, 128).
    Query blocks of ``_QUERY_BLOCK`` rows, each a plain masked softmax
    over all keys, recomputed in the backward pass."""
    import jax
    import jax.numpy as jnp

    seq = q.shape[1]
    block = min(_QUERY_BLOCK, seq)
    keys = jnp.arange(seq)

    @jax.checkpoint
    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(q.shape[-1])
        seen = (start + jnp.arange(block))[:, None] >= keys[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    blocks = jax.lax.map(one, jnp.arange(0, seq, block))
    return jnp.moveaxis(blocks, 0, 1).reshape(v.shape)


def _mla(config: dict, lp, x):
    import jax.numpy as jnp

    batch, seq, _ = x.shape
    heads = config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, theta = config["kv_lora_rank"], float(config["rope_theta"])
    q = (_rmsnorm(x @ lp["wq_a"], lp["q_norm"]) @ lp["wq_b"]).reshape(
        batch, seq, heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], theta)], axis=-1)
    latent = x @ lp["wkv_a"]
    k_rope = _rotary(latent[..., rank:], theta)        # one for all heads
    kv = (_rmsnorm(latent[..., :rank], lp["kv_norm"]) @ lp["wkv_b"]).reshape(
        batch, seq, heads, dn + config["v_head_dim"])
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope[:, :, None, :],
                                        (batch, seq, heads, dr))], axis=-1)
    out = _causal_attention(q, k, kv[..., dn:])
    return out.reshape(batch, seq, -1) @ lp["wo"]


def _swiglu(x, w):
    import jax

    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def _selection(config: dict, w, x):
    """``(ids, weights)`` of the top-k: sigmoid scores over the router's
    whole width, the bias in the selection only."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(x @ w["router"])
    ids = jnp.argsort(-(scores + w["bias"]), axis=-1, stable=True)[
        ..., :config["num_experts_per_tok"]]
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, (config["routed_scaling_factor"] * picked
                 / (picked.sum(-1, keepdims=True) + 1e-20))


def _experts(config: dict, w, x):
    """This chip's share of the expert layer: the experts held are the
    first of the router's width, taken one after the other, each on
    every token under its mask; what the others would add is left out.
    The shared expert is whole.  Returns ``(out, pairs sent to each held
    expert)``."""
    import jax
    import jax.numpy as jnp

    ids, weights = _selection(config, w, x)

    @jax.checkpoint      # an expert keeps nothing for the backward pass
    def part(e, weights_e):
        gate = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        return gate[..., None] * _swiglu(x, weights_e)

    def one(out, expert):
        return out + part(*expert), jnp.sum(ids == expert[0])

    return jax.lax.scan(one, _swiglu(x, w["shared"]),
                        (jnp.arange(config["n_routed_experts"]),
                         w["experts"]))


def _hidden(config: dict, params: dict, tokens, targets):
    """``(x, h, sent)``: the main stack's output before its final norm,
    the MTP block's, and the pairs every expert layer sends each held
    expert (the MTP module's last).  Every block is recomputed in the
    backward pass; the expert layers, which are alike, are scanned."""
    import jax
    import jax.numpy as jnp

    dense = config["first_k_dense_replace"]

    def row(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    @jax.checkpoint
    def dense_block(x, lp, w):
        h = x + _mla(config, lp, _rmsnorm(x, lp["ln1"]))
        return h + _swiglu(_rmsnorm(h, lp["ln2"]), w)

    @jax.checkpoint
    def expert_block(x, lp, w):
        h = x + _mla(config, lp, _rmsnorm(x, lp["ln1"]))
        out, sent = _experts(config, w, _rmsnorm(h, lp["ln2"]))
        return h + out, sent

    x = params["embed"][tokens]
    for i in range(dense):
        x = dense_block(x, row(params["layers"], i), row(params["dense"], i))
    x, sent = jax.lax.scan(
        lambda x, layer: expert_block(x, *layer), x,
        (jax.tree_util.tree_map(lambda a: a[dense:], params["layers"]),
         params["moe"]))
    mtp = params["mtp"]
    joined = jnp.concatenate(
        [_rmsnorm(params["embed"][targets], mtp["ln_e"]),
         _rmsnorm(x, mtp["ln_h"])], axis=-1)
    h, last = expert_block(joined @ mtp["eh_proj"], row(mtp["layers"], 0),
                           row(mtp["moe"], 0))
    return x, h, jnp.concatenate([sent, last[None]])


def reference_loss(config: dict, params: dict, tokens, targets,
                   dtype: str = "float32"):
    """``(loss, sent)``: CE(main) + 0.3 CE(MTP), the MTP's last position
    masked, and the pairs each held expert is sent, layer by layer.  A
    ``dtype`` other than float32 computes everything in that type (the
    lower-precision reading the limits are set against)."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)

    @jax.checkpoint
    def nll(x, gain, wanted):
        logp = jax.nn.log_softmax(_rmsnorm(x, gain) @ params["head"],
                                  axis=-1)
        return -jnp.take_along_axis(logp, wanted[..., None],
                                    axis=-1)[..., 0].astype(jnp.float32)

    with jax.default_matmul_precision(
            "highest" if dtype == "float32" else "default"):
        x, h, sent = _hidden(config, params, tokens, targets)
        loss = jnp.mean(nll(x, params["ln_f"], targets))
        ahead = nll(h[:, :-1], params["mtp"]["ln_f"], targets[:, 1:])
    return loss + 0.3 * jnp.mean(ahead), sent


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def _groups(tree: dict) -> dict:
    """The parameter tree's leaves by the part of the model they belong
    to; the selection bias, which takes no gradient, apart."""
    moe = tree["moe"]
    return {"mla": tree["layers"], "dense": tree["dense"],
            "router": moe["router"], "experts": moe["experts"],
            "shared": moe["shared"], "mtp": tree["mtp"],
            "embed_head": (tree["embed"], tree["head"], tree["ln_f"]),
            "bias": moe["bias"]}


def _group_norms(grads: dict) -> dict:
    """One program for all the norms; the tree can be freed after."""
    import jax
    import optax

    norms = jax.jit(lambda g: {name: optax.global_norm(part)
                               for name, part in _groups(g).items()})(grads)
    return {name: float(value) for name, value in norms.items()}


def compare(loss, norms: dict, ref_loss, ref_norms: dict,
            loss_rtol: float = LOSS_RTOL, group_rtol: dict = GROUP_RTOL
            ) -> dict:
    """The record of one comparison; ``ok`` decides ``correct``: the
    loss and every group's gradient norm inside its limit, and the
    selection bias's gradient exactly zero.  (The CPU tests, float32 on
    both sides, pass tighter limits.)"""
    loss, ref_loss = float(loss), float(ref_loss)
    loss_err = abs(loss - ref_loss) / abs(ref_loss)
    errs = {name: (abs(norms[name] - ref_norms[name])
                   / (ref_norms[name] or 1e-30)) for name in group_rtol}
    ok = (math.isfinite(loss) and loss_err < loss_rtol
          and all(errs[name] < limit for name, limit in group_rtol.items())
          and norms["bias"] == 0.0)
    return {"ok": bool(ok), "loss": loss, "reference_loss": ref_loss,
            "loss_rel_err": loss_err, "loss_rtol": loss_rtol,
            "grad_norm_rel_err": errs, "grad_norm_rtol": group_rtol,
            "grad_norm": norms, "reference_grad_norm": ref_norms}


class Trainer:
    """Builds the flagship trainer; ``hvd.init()`` has returned."""

    def __init__(self, config: dict, job: dict, seed: int, hvd):
        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from horovod_tpu.models import transformer
        from horovod_tpu.parallel.mesh import make_mesh

        self.config, self.job = config, job
        axes = job["mesh"]
        chips = int(np.prod(list(axes.values())))
        if chips > len(jax.devices()):
            raise RuntimeError(f"the mesh {axes} needs {chips} chips, JAX "
                               f"sees {len(jax.devices())}")
        seq = job["seq"]
        self.cfg = cfg = transformer.TransformerConfig(**_kwargs(config, job))
        self.mesh = mesh = make_mesh(**axes, devices=jax.devices()[:chips])
        # the selection bias is a buffer: no decay either
        self.opt = opt = optax.adamw(
            config["optimizer"]["learning_rate"],
            mask=lambda p: jax.tree_util.tree_map_with_path(
                lambda path, _: getattr(path[-1], "key", None) != "bias", p))
        pool = job["batch_pool"]
        rows = job["batch_per_chip"] * axes["dp"]
        data = NamedSharding(mesh, P("dp", "sp"))

        def make_pool(key):
            ids = jax.random.randint(key, (pool, rows, seq + 1), 0,
                                     cfg.vocab, jnp.int32)
            return tuple((ids[i, :, :-1], ids[i, :, 1:])
                         for i in range(pool))

        self.batches = jax.jit(make_pool, out_shardings=data)(
            jax.random.PRNGKey(seed + 1))
        self._params = transformer.shard_params(
            jax.jit(lambda key: transformer.init_params(
                _DeviceRandn(key), cfg))(jax.random.PRNGKey(seed)),
            cfg, mesh)
        self.state = None       # made by compile(), after the check
        self._step = transformer.make_train_step(cfg, mesh, opt)
        self.samples_per_step = rows
        self.units_per_sample = seq
        self.compiled = None

    def compile(self) -> None:
        if self.state is None:
            self.state = (self._params, self.opt.init(self._params))
            self._params = None
        self.compiled = self._step.lower(
            *self.state, *self.batches[0]).compile()

    def compiled_text(self) -> str:
        return self.compiled.as_text()

    def run_step(self, i: int):
        """Dispatch step ``i``; returns its loss, still on the device."""
        *state, loss = self.compiled(
            *self.state, *self.batches[i % len(self.batches)])
        self.state = tuple(state)
        return loss

    def params(self):
        return self._params if self.state is None else self.state[0]

    def gradient_program(self):
        """``(params, tokens, targets) -> (loss, pairs, gradients)``:
        the system's loss, what its routing sends each held expert, and
        its backward pass over the cell's mesh, reduced as
        ``make_train_step`` reduces them."""
        import jax
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.models import transformer
        from horovod_tpu.parallel.sharding import (grad_reduce_axes,
                                                   tree_map_with_specs)

        cfg = self.cfg
        specs = transformer.param_specs(cfg)

        def per_device(p, tok, tgt):
            (loss, pairs), grads = jax.value_and_grad(
                transformer.loss_and_routing, has_aux=True)(p, tok, tgt, cfg)
            grads = tree_map_with_specs(
                lambda g, spec: (lax.psum(g, grad_reduce_axes(spec))
                                 if grad_reduce_axes(spec) else g),
                grads, specs)
            return (lax.psum(loss, ("dp", "sp")), lax.psum(pairs, "sp"),
                    grads)

        return jax.jit(shard_map(
            per_device, mesh=self.mesh, check_vma=False,
            in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
            out_specs=(P(), P(None, "dp"), specs)))

    def reference_batch(self) -> tuple:
        n = self.job["reference_samples"]
        return tuple(a[:n] for a in self.batches[0])

    def readings(self, reference_dtype: str = "float32") -> tuple:
        """``(loss, group norms, reference loss, reference group norms,
        pairs sent by the system, pairs sent by the reference)`` on the
        first ``reference_samples`` sequences; one gradient tree alive
        at a time."""
        import functools

        import jax
        import numpy as np

        tokens, targets = self.reference_batch()
        loss, sent, grads = self.gradient_program()(self.params(), tokens,
                                                    targets)
        norms = _group_norms(grads)
        del grads
        (ref_loss, wanted), grads = jax.jit(jax.value_and_grad(
            functools.partial(reference_loss, self.config,
                              dtype=reference_dtype), has_aux=True))(
                self.params(), tokens, targets)
        ref_norms = _group_norms(grads)
        del grads
        return (loss, norms, ref_loss, ref_norms, np.asarray(sent),
                np.asarray(wanted))

    def check_reference(self) -> dict:
        """Step-0 loss and the gradient's norm group by group against
        the float32 reference; the optimizer's state is made after
        (``compile``).  Also writes the routing of that batch to the
        flight ring (``transformer.record_routing``) and counts the
        selections that differ from the reference's: one that differs
        between the bf16 stream and the float32 one moves one or two of
        the per-expert counts by one."""
        import numpy as np

        from horovod_tpu.models import transformer

        *readings, sent, wanted = self.readings()
        record = compare(*readings)
        transformer.record_routing(self.cfg, sent,
                                   self.reference_batch()[0].size)
        record["pairs_sent"] = sent.sum(axis=1).tolist()
        record["pairs_sent_otherwise"] = np.abs(sent - wanted).sum(
            axis=1).tolist()
        return record
