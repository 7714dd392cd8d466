"""Family ``lm_conv_moe``: a language model whose mixers are mostly gated
short convolutions, with a grouped-query attention layer among them, and
whose FFNs after the first are sparse experts (the ``lfm2_moe`` key set:
LFM2-24B-A2B's published stack) through the flagship path —
``TransformerConfig`` + ``init_params`` + ``shard_params`` +
``make_train_step`` on a ``make_mesh`` mesh — cut to one chip's share of
a stated deployment (the configuration file's ``deployment``).

No ``attn_impl`` is forced and no ``HOROVOD_*`` variable is set.

A published layer is two pre-normed sub-layers with a residual each.
The mixer, by the layer's type: on a ``conv`` layer ``[B | C | x] = h
W_in``, ``u = B * x``, a causal depthwise convolution of
``conv_L_cache`` taps without bias, ``y = C * conv(u)``, ``y W_out``; on
a ``full_attention`` layer grouped-query attention (32 on 8 heads of 64)
with an RMSNorm on each head's ``q`` and ``k`` and rotary positions,
causal, ungated.  Then a dense SwiGLU FFN (the first
``num_dense_layers``) or sigmoid top-4 of 64 SwiGLU experts, no shared
one.  The program runs them as a layer pattern of one sub-layer a layer
(``C`` / ``R``, then ``D`` / ``E``).  The file's ``num_experts`` is the
number of experts held here (the first that many of ``router_width``),
``vocab_size`` the slice of the vocabulary held here, ``layer_types``
the layers kept.

The plain reference reads the system's parameter tree and computes the
same loss in float32 with ``jax.numpy`` only: the convolution as an
explicit sum over its taps of shifted rows, attention as a masked
softmax over blocks of queries with the key/value head of a query head
taken by index, its own rotary and norms, a Python loop over the experts
held (every expert on every token, under a mask).  Nothing of it calls
``horovod_tpu``.
"""

from __future__ import annotations

from benchmark.families import lm_moe_mla, lm_swa_moe
from benchmark.families.lm_mesh import _DeviceRandn

# ---------------------------------------------------------------------------
# The comparison that decides ``correct``
# ---------------------------------------------------------------------------

# Step-0 loss, and the gradient's norm group by group, of the system
# (bf16 products with f32 accumulation, a bf16 residual stream and
# ``B * x``; the taps' sum, ``C * v``, norms, router and the logits'
# reductions in f32) against the float32 reference, relative, on one
# 8,192-token sequence at the published widths.  Read on the chip (PERF.md
# section 6; weights as the cell draws them, the selection bias
# settled): the largest the system gave over seventeen seeds, and the
# least and the largest the reference itself gave over four seeds when
# computed in bfloat16 throughout (the nearest precision below the
# configuration's), which must come out as not correct:
#
#     group         system,    bfloat16 reference,   limit
#                   largest    least .. largest
#     experts       1.09e-3    1.03e-2 .. 1.14e-2    3.3e-3   held
#     router        4.14e-3    1.59e-2 .. 1.93e-2    1e-2     held
#     conv          2.06e-4    3.12e-4 .. 5.13e-4    6.5e-4
#     attention     3.33e-4    9.51e-5 .. 4.46e-4    1e-3
#     dense         1.83e-4    2.53e-5 .. 7.93e-5    6e-4
#     norms         7.65e-4    1.83e-4 .. 6.60e-4    3e-3
#     embed_head    1.25e-4    1.04e-4 .. 1.59e-4    4.5e-4
#     loss          5.01e-5    2.31e-5 .. 4.41e-5    3e-4
#
# Two limits are held against the bfloat16 reference, each between its
# two readings with room on both sides, and each alone fails it on every
# one of the four seeds: ``experts`` (3.0 times above the system's largest,
# 3.1 below the reference's least) and ``router`` (2.4 and 1.6).  A lower
# precision shows where the top-4 is taken: a bfloat16 router sends 80-120
# of a layer's 4,096 held pairs otherwise, where the system's f32 router
# over a bf16 stream sends 10-61 otherwise, so ``experts`` moves by 1 %
# on every seed.  The others have no reading from above: the reference
# in bfloat16 reads below the system, or (``conv``) only 1.5 times above
# it, which leaves no room on both sides.  They stand about three times
# above the system's largest (the norms, whose gradient is under 2 % of
# the whole and reads from 2e-5 to 8e-4 by the seed, four times; the loss
# at the accepted expert cells' limit, six times), there for a left-out
# or misplaced term, which moves them by tens of percent at toy size
# (tests/benchmark_suite/test_benchmark_conv_moe.py).  The bias's gradient
# must be exactly 0.
LOSS_RTOL = 3e-4
GROUP_RTOL = {"attention": 1e-3, "conv": 6.5e-4, "dense": 6e-4,
              "router": 1e-2, "experts": 3.3e-3, "norms": 3e-3,
              "embed_head": 4.5e-4}

KIND_OF = {"conv": "C", "full_attention": "R"}


def _pattern(config: dict) -> str:
    """One sub-layer a layer: a published layer's mixer, ``C`` or ``R``
    by its type, then its FFN, ``D`` in the leading dense layers and
    ``E`` after them."""
    assert len(config["layer_types"]) == config["num_hidden_layers"]
    return "".join(
        KIND_OF[kind] + ("D" if i < config["num_dense_layers"] else "E")
        for i, kind in enumerate(config["layer_types"]))


def _head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def _kwargs(config: dict, job: dict) -> dict:
    """``TransformerConfig``'s arguments from the configuration file."""
    from horovod_tpu.models import blocks

    assert config["norm_topk_prob"] and config["use_expert_bias"]
    assert not config["conv_bias"]
    assert config["conv_L_cache"] == blocks.CONV_TAPS
    assert config["rope_parameters"]["rope_type"] == "default"
    return dict(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], head_dim=_head_dim(config),
        n_kv_heads=config["num_key_value_heads"], max_seq=job["seq"],
        d_ff=config["intermediate_size"], dtype=config["compute_dtype"],
        tied_head=False, remat=True, layer_pattern=_pattern(config),
        norm_eps=config["norm_eps"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        n_experts=config["router_width"], experts_held=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        routed_scale=config["routed_scaling_factor"])


# ---------------------------------------------------------------------------
# Operations the architecture and its kernels require, from shapes
# ---------------------------------------------------------------------------


def _layers(config: dict) -> dict:
    kinds = config["layer_types"]
    dense = min(config["num_dense_layers"], len(kinds))
    return {"conv": kinds.count("conv"), "full": kinds.count("full_attention"),
            "dense": dense, "expert": len(kinds) - dense}


def _macs_per_token(config: dict) -> dict:
    """Multiply-accumulates of one token's forward pass through each
    kind of sub-layer, attention's score products left out."""
    d, size = config["hidden_size"], _head_dim(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    # a token's top-k choices fall on the experts held here with
    # probability held / router width each: 4 * 8 / 64 = 0.5 experts
    routed = (config["num_experts_per_tok"] * config["num_experts"]
              / config["router_width"])
    return {
        # in-projection d x 3d, out-projection d x d, and the taps
        "conv": 4 * d * d + config["conv_L_cache"] * d,
        # q and o at the query heads, k and v at theirs
        "attention": d * size * (2 * heads + 2 * kv),
        "dense": 3 * d * config["intermediate_size"],
        "expert": (d * config["router_width"]
                   + routed * 3 * d * config["moe_intermediate_size"]),
        "head": d * config["vocab_size"]}


def model_flops_per_sample(config: dict, job: dict) -> float:
    """Forward plus backward of one sequence through this chip's share:
    3 x forward, 2 FLOPs a multiply-accumulate.  Forward: a convolution
    sub-layer's two projections and its taps; the attention sub-layer's
    four projections and its score and value products over half the
    square; the dense SwiGLU; router + the routed experts at the
    expected 0.5 a token; the head.  Nothing that is recomputed is
    counted; the convolution's two gates, norms, rotary, SiLU, sigmoid
    and softmax are left out.  At seq 8,192: 9.974 TFLOP a sequence,
    1.2175 GFLOP a token (tests/benchmark_suite has the hand-worked
    value)."""
    seq, m, n = job["seq"], _macs_per_token(config), _layers(config)
    per_token = (n["conv"] * m["conv"] + n["full"] * m["attention"]
                 + n["dense"] * m["dense"] + n["expert"] * m["expert"]
                 + m["head"])
    products = (n["full"] * config["num_attention_heads"] * 2
                * _head_dim(config) * seq * (seq + 1) / 2)
    return 3.0 * 2.0 * (seq * per_token + products)


def kernel_costs(config: dict, job: dict) -> dict:
    """What one train step requires of the flash-attention kernels, of
    the gated short-convolution sub-layers and of the experts' grouped
    products, over all layers, per chip.

    ``gqa_flash``: the attention layers' seven causal products over the
    query heads, as ``lm_swa_moe.kernel_costs`` counts them, at a head
    size of 64 for both.  Bytes: bf16, each tensor once: q and o at the
    query heads, k and v at the key/value heads forward; q, o, dO in and
    dq out at the query heads, k, v in and dk, dv out at the key/value
    heads backward; plus the f32 row statistics.  FLOP-bound.

    ``conv``: the gated short-convolution sub-layers whole.  FLOPs: the
    in-projection (d x 3d) forward, again in the replay, whose result the
    backward pass reads, and its two gradient products; the
    out-projection (d x d) forward and its two gradient products (the
    replay needs none of its result); ``B * x``, the taps and ``C * v``,
    7 FLOPs a channel a token forward, as many in the replay and twice
    that backward.  Bytes at the compute type, of the gates and taps
    alone: ``B``, ``C``, ``x`` read and ``y`` written forward and again in
    the replay, ``B``, ``C``, ``x`` and ``dy`` read and ``dB``, ``dC``,
    ``dx`` written backward, 15 tensors of (tokens, hidden) a layer, the
    taps negligible.  Bound by the products' FLOPs, eight times over the
    bytes at the cell's shape, wherever the compiler fuses the gates.

    ``moe_experts``: ``lm_swa_moe.expert_cost`` of the pairs the held
    experts are expected to be sent; ``moe_experts_roofline`` asks it
    again for the pairs the routing records show."""
    seq, batch, n = job["seq"], job["batch_per_chip"], _layers(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    size, d = _head_dim(config), config["hidden_size"]
    tokens = batch * seq
    itemsize = 2 if config["compute_dtype"] == "bfloat16" else 4
    routed = (tokens * config["num_experts_per_tok"]
              * config["num_experts"] / config["router_width"])
    return {
        "gqa_flash": {
            # 2 FLOPs a multiply-accumulate over seq * (seq + 1) / 2 pairs
            "flops": n["full"] * float(batch * heads * seq * (seq + 1))
            * 7 * size,
            "bytes": n["full"] * (2 * batch * seq * size
                                  * (6 * heads + 6 * kv)
                                  + 2 * 4 * batch * heads * seq)},
        "conv": {
            # four passes of the d x 3d product, three of the d x d one,
            # 2 FLOPs a multiply-accumulate; the gates and taps, 7 FLOPs
            # a pass and the backward's twice
            "flops": n["conv"] * float(tokens * d)
            * (2 * (4 * 3 * d + 3 * d) + 4 * 7),
            "bytes": n["conv"] * 15 * tokens * d * itemsize},
        "moe_experts": expert_cost(config, n["expert"] * routed),
    }


expert_cost = lm_swa_moe.expert_cost


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

_rmsnorm, _rotary, _swiglu = (lm_swa_moe._rmsnorm, lm_swa_moe._rotary,
                              lm_swa_moe._swiglu)
_STACK_OF = {"C": "conv", "R": "rattn", "D": "dense", "E": "moe"}


def _conv(config: dict, lp, h):
    """The gated short convolution: ``[B | C | x] = h W_in``, ``u = B *
    x``, ``v_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t`` (for three taps:
    tap ``k`` on the row ``taps - 1 - k`` steps back, zeros before the
    first), ``(C * v) W_out``."""
    import jax.numpy as jnp

    b_gate, c_gate, x = jnp.split(h @ lp["w_in"], 3, axis=-1)
    u = b_gate * x
    taps = config["conv_L_cache"]
    v = jnp.zeros_like(u)
    for k in range(taps):
        back = taps - 1 - k
        shifted = jnp.concatenate(
            [jnp.zeros_like(u[:, :back]), u[:, :u.shape[1] - back]], axis=1)
        v = v + lp["conv_w"][k] * shifted
    return (c_gate * v) @ lp["w_out"]


def _attention(config: dict, lp, h):
    """Causal softmax attention, ungated: query head ``i`` on key/value
    head ``i // (heads / kv heads)``, taken by index; an RMSNorm over
    each head's q and k, then rotary positions; query blocks, each a
    plain masked softmax over every key (``lm_moe_mla``'s), recomputed
    in the backward pass."""
    import jax.numpy as jnp

    batch, seq, _ = h.shape
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    size, eps = _head_dim(config), config["norm_eps"]
    theta = float(config["rope_parameters"]["rope_theta"])
    q = _rmsnorm((h @ lp["wq"]).reshape(batch, seq, heads, size),
                 lp["q_norm"], eps)
    k = _rmsnorm((h @ lp["wk"]).reshape(batch, seq, kv, size),
                 lp["k_norm"], eps)
    v = (h @ lp["wv"]).reshape(batch, seq, kv, size)
    q, k = _rotary(q, theta), _rotary(k, theta)
    of_query_head = jnp.arange(heads) // (heads // kv)
    out = lm_moe_mla._causal_attention(q, k[:, :, of_query_head],
                                       v[:, :, of_query_head])
    return out.reshape(batch, seq, heads * size) @ lp["wo"]


def _experts(config: dict, w, x):
    """This chip's share of the expert layer: sigmoid scores over the
    router's whole width, the bias in the selection only, the top-k
    renormalised (``lm_moe_mla._selection``); the experts held are the
    first of the width, taken one after the other in a Python loop, each
    on every token under its mask; what the others would add is left
    out, and there is no shared expert.  Returns ``(out, pairs sent to
    each held expert)``."""
    import jax
    import jax.numpy as jnp

    ids, weights = lm_moe_mla._selection(config, w, x)

    @jax.checkpoint      # an expert keeps nothing for the backward pass
    def part(e, weights_e):
        gate = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        return gate[..., None] * _swiglu(x, weights_e)

    out, sent = jnp.zeros_like(x), []
    for e in range(config["num_experts"]):
        out = out + part(e, jax.tree_util.tree_map(lambda a: a[e],
                                                   w["experts"]))
        sent.append(jnp.sum(ids == e))
    return out, jnp.stack(sent)


def reference_loss(config: dict, params: dict, tokens, targets,
                   dtype: str = "float32"):
    """``(loss, sent)``: the mean next-token cross entropy, and the
    pairs each held expert is sent, expert layer by expert layer.  Every
    sub-layer is recomputed in the backward pass.  A ``dtype`` other
    than float32 computes everything in that type (the lower-precision
    reading the limits are set against)."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    eps = config["norm_eps"]
    sub_layer = {
        "C": lambda lp, h: (_conv(config, lp, h), None),
        "R": lambda lp, h: (_attention(config, lp, h), None),
        "D": lambda lp, h: (_swiglu(h, lp), None),
        "E": lambda lp, h: _experts(config, lp, h)}

    def layer(kind):
        @jax.checkpoint
        def run(x, lp):
            out, sent = sub_layer[kind](lp, _rmsnorm(x, lp["ln"], eps))
            return x + out, sent

        return run

    @jax.checkpoint
    def nll(x):
        logp = jax.nn.log_softmax(
            _rmsnorm(x, params["ln_f"], eps) @ params["head"], axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None],
                                    axis=-1)[..., 0].astype(jnp.float32)

    with jax.default_matmul_precision(
            "highest" if dtype == "float32" else "default"):
        x = params["embed"][tokens]
        rows, sent = dict.fromkeys(_STACK_OF, 0), []
        for kind in _pattern(config):
            lp = jax.tree_util.tree_map(lambda a: a[rows[kind]],
                                        params[_STACK_OF[kind]])
            rows[kind] += 1
            x, pairs = layer(kind)(x, lp)
            if kind == "E":
                sent.append(pairs)
        loss = jnp.mean(nll(x))
    return loss, jnp.stack(sent)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

_FFN = ("w_gate", "w_up", "w_down")


def _groups(tree: dict) -> dict:
    """The parameter tree's leaves by the part of the model they belong
    to: the attention layer's matrices, the convolution layers' (taps
    among them), the dense FFN's; router and routed experts; every
    sub-layer's norm and the two per-head ones; embedding, head and
    final norm.  The selection bias, which takes no gradient, apart."""
    conv, attn, dense, moe = (tree["conv"], tree["rattn"], tree["dense"],
                              tree["moe"])
    return {"attention": [attn[m] for m in ("wq", "wk", "wv", "wo")],
            "conv": [conv[m] for m in ("w_in", "conv_w", "w_out")],
            "dense": [dense[m] for m in _FFN],
            "router": moe["router"], "experts": moe["experts"],
            "norms": [conv["ln"], attn["ln"], attn["q_norm"], attn["k_norm"],
                      dense["ln"], moe["ln"]],
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
    """``lm_moe_mla.compare`` with this family's limits: the loss and
    every group's gradient norm inside its limit, and the selection
    bias's gradient exactly zero."""
    return lm_moe_mla.compare(loss, norms, ref_loss, ref_norms,
                              loss_rtol=loss_rtol, group_rtol=group_rtol)


class Trainer(lm_swa_moe.Trainer):
    """Builds the flagship trainer; ``hvd.init()`` has returned.  The
    window family's trainer (its routing settled before anything is
    read, the pairs sent to every expert reported) with this family's
    configuration, reference and groups."""

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
        rate, warm_up = (config["optimizer"]["learning_rate"],
                         config["optimizer"].get("warmup_steps", 0))
        self.opt = opt = optax.adamw(
            optax.linear_schedule(0.0, rate, warm_up) if warm_up else rate,
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
        self.settled = self._settle_routing(config.get("router_settling"))

    def readings(self, reference_dtype: str = "float32") -> tuple:
        """``(loss, group norms, reference loss, reference group norms,
        pairs sent by the system, pairs sent by the reference)`` to the
        experts held, on the first ``reference_samples`` sequences; one
        gradient tree alive at a time."""
        import functools

        import jax
        import numpy as np

        tokens, targets = self.reference_batch()
        loss, loads, grads = self.gradient_program()(self.params(), tokens,
                                                     targets)
        norms = _group_norms(grads)
        del grads
        (ref_loss, wanted), grads = jax.jit(jax.value_and_grad(
            functools.partial(reference_loss, self.config,
                              dtype=reference_dtype), has_aux=True))(
                self.params(), tokens, targets)
        ref_norms = _group_norms(grads)
        del grads
        wanted = np.asarray(wanted)
        # the experts held are the first of the router's width
        sent = np.asarray(loads)[:, :wanted.shape[1]]
        return loss, norms, ref_loss, ref_norms, sent, wanted

    def check_reference(self) -> dict:
        """Step-0 loss and the gradient's norm group by group against
        the float32 reference; the optimizer's state is made after
        (``compile``).  Also writes that batch's routing, one record an
        attention sub-layer and one a convolution sub-layer to the flight
        ring (``transformer.record_routing``, ``record_attention``,
        ``record_conv``) and counts the selections that differ from the
        reference's."""
        import numpy as np

        from horovod_tpu.models import transformer

        *readings, sent, wanted = self.readings()
        record = compare(*readings)
        tokens = self.reference_batch()[0]
        transformer.record_routing(self.cfg, sent, tokens.size)
        batch = self.job["batch_per_chip"]
        record["attention_windows"] = transformer.record_attention(
            self.cfg, batch)
        record["conv_mix"] = transformer.record_conv(self.cfg, batch)
        record["router_settling"] = self.settled
        record["pairs_sent"] = sent.sum(axis=1).tolist()
        record["pairs_sent_otherwise"] = np.abs(sent - wanted).sum(
            axis=1).tolist()
        return record
