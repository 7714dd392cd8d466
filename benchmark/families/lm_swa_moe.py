"""Family ``lm_swa_moe``: a language model that mixes sliding-window
and full attention and routes its FFNs over sparse experts (the
``afmoe`` key set: Trinity-Mini's published stack) through the flagship
path — ``TransformerConfig`` + ``init_params`` + ``shard_params`` +
``make_train_step`` on a ``make_mesh`` mesh — cut to one chip's share of
a stated deployment (the configuration file's ``deployment``).

No ``attn_impl`` is forced and no ``HOROVOD_*`` variable is set.

A published layer is two sub-layers, each with a norm before and a norm
after, ``x + N_post(f(N_pre(x)))``: gated grouped-query attention with
an RMSNorm on each head's ``q`` and ``k`` — under a sliding window with
rotary positions on a ``sliding_attention`` layer, over the whole past
with no positions on a ``full_attention`` one — then a dense SwiGLU FFN
(the first ``num_dense_layers``) or sigmoid top-k SwiGLU experts beside
a shared one.  The program runs them as a layer pattern of one sub-layer
a layer (``S`` / ``G``, then ``D`` / ``E``).  The file's ``num_experts``
is the number of experts held here (the first that many of
``router_width``), ``vocab_size`` the slice of the vocabulary held here,
``layer_types`` the layers kept.

The plain reference reads the system's parameter tree and computes the
same loss in float32 with ``jax.numpy`` only: attention as a masked
softmax over blocks of queries with the mask built from positions, the
key/value head of a query head taken by index, its own rotary and
norms, a Python loop over the experts held (every expert on every
token, under a mask).  Nothing of it calls ``horovod_tpu``.
"""

from __future__ import annotations

import math

from benchmark.families import lm_hybrid_ssm, lm_moe_mla
from benchmark.families.lm_mesh import _DeviceRandn

# ---------------------------------------------------------------------------
# The comparison that decides ``correct``
# ---------------------------------------------------------------------------

# Step-0 loss, and the gradient's norm group by group, of the system
# (bf16 products with f32 accumulation, a bf16 residual stream; norms,
# router, the gate's sigmoid and the logits in f32) against the float32
# reference, relative, on one 16,384-token sequence at the published
# widths.  Read on the chip (PR 35, PERF.md section 6; weights as the
# cell draws them, the selection bias settled): the largest the system
# gave over ten seeds (the tenth with the window one key too long, which
# the cell cannot tell from the system, below), and the least the
# reference itself gave over four seeds when computed in bfloat16
# throughout (the nearest precision below the configuration's), which
# must come out as not correct:
#
#     group              system,   bfloat16 reference,
#                        largest   least (all four)
#     loss               3.4e-5    6.3e-7 (.. 1.3e-4)
#     attention_window   8.6e-4    5.3e-4 (.. 8.0e-4)
#     attention_full     1.0e-3    2.7e-4 (.. 6.7e-4)
#     dense              4.1e-4    2.1e-4 (.. 4.2e-4)
#     router             1.0e-2    1.3e-2 (2.0e-2, 2.1e-2, 2.6e-2)
#     experts            9.9e-4    1.18e-2 (.. 1.24e-2)
#     shared             1.7e-4    6.4e-4 (.. 9.6e-4)
#     norms              1.1e-3    1.4e-7 (.. 1.1e-3)
#     embed_head         4.5e-4    1.8e-4 (.. 9.0e-4)
#
# and the system with each fault the CPU tests plant, at the cell's own
# size, one seed (the groups that leave their limit):
#
#     rotary on the full layer    attention_full 8.6e-3, attention_window
#                                 3.0e-3, dense 2.6e-3, shared 2.6e-3
#     the gate dropped            every group, 1.4e-2 to 4.7e-2; loss 6.7e-4
#     the dense layer's post-norm dense 0.34, every other group 2e-2 to
#       dropped                   7e-2
#     query head i on key/value   experts 5.6e-2, router 5.1e-2,
#       head i % 4                attention_full 1.4e-2; loss 3.3e-4
#     the multiplier dropped      dense 0.31, norms 0.27, attention_window
#                                 0.26, embed_head 0.18; loss 3.6e-4
#     the window one key too long none: one key in 2,048 moves no group
#       (i - j <= W)              out of the system's own range (router
#                                 1.0e-2, norms 1.1e-3); the CPU tests see
#                                 it at a window of 16, and the kernels'
#                                 tests hold the mask position by position
#
# A lower precision shows where the top-8 is taken: a bfloat16 router
# flips selections (167-280 of a layer's 8,192 held pairs sent otherwise,
# where the system's f32 router over a bf16 stream sends 20-72
# otherwise), so ``experts`` moves by 1.2 % on every seed and the shared
# expert, which reads the same rounded stream at full width, by 0.06-0.1
# %.  Those two limits lie between their readings: ``experts`` 4 times
# above the system's largest and 3 below the reference's least,
# ``shared`` 2.4 above and 1.6 below; either alone fails the bfloat16
# reference on each of the four seeds.  ``router`` does not part the two:
# the system's own reading is the flips' (1.8e-5 to 1.0e-2 by the seed,
# half-normal at about 5e-3), the reference's least is 1.3 times its
# largest, and a limit between them would refuse a fresh seed in a
# hundred, where one run that is not correct refuses a PR; it stands with
# the groups that have no reading from above.  Those — attention, the
# dense layer, the norms, the head and the loss, which average the
# rounding of 16,384 tokens away in either precision — stand three times
# above the system's largest (``router`` 2.4 times, the loss nine times,
# at the accepted expert cells' limit) and are there for a left-out or
# misplaced term, which moves them by tens of percent at toy size
# (tests/benchmark_suite/test_benchmark_swa_moe.py) and by the table
# above at the cell's.  The bias's gradient must be exactly 0.
LOSS_RTOL = 3e-4
GROUP_RTOL = {"attention_window": 2.6e-3, "attention_full": 3e-3,
              "dense": 1.2e-3, "router": 2.4e-2, "experts": 4e-3,
              "shared": 4e-4, "norms": 3.4e-3, "embed_head": 1.4e-3}

KIND_OF = {"sliding_attention": "S", "full_attention": "G"}


def _pattern(config: dict) -> str:
    """One sub-layer a layer: a published layer's attention, ``S`` or
    ``G`` by its type, then its FFN, ``D`` in the leading dense layers
    and ``E`` after them."""
    assert len(config["layer_types"]) == config["num_hidden_layers"]
    return "".join(
        KIND_OF[kind] + ("D" if i < config["num_dense_layers"] else "E")
        for i, kind in enumerate(config["layer_types"]))


def _embed_scale(config: dict) -> float:
    return math.sqrt(config["hidden_size"]) if config["mup_enabled"] else 1.0


def _kwargs(config: dict, job: dict) -> dict:
    """``TransformerConfig``'s arguments from the configuration file."""
    assert config["score_func"] == "sigmoid" and config["route_norm"]
    assert config["n_group"] == config["topk_group"] == 1
    assert config["hidden_act"] == "silu" and config["rope_scaling"] is None
    assert not config["tie_word_embeddings"]
    return dict(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], head_dim=config["head_dim"],
        n_kv_heads=config["num_key_value_heads"], max_seq=job["seq"],
        d_ff=config["intermediate_size"], dtype=config["compute_dtype"],
        tied_head=False, remat=True, layer_pattern=_pattern(config),
        window=config["sliding_window"], post_norm=True,
        embed_scale=_embed_scale(config), norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        n_experts=config["router_width"], experts_held=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        shared_experts=config["num_shared_experts"],
        routed_scale=config["route_scale"])


# ---------------------------------------------------------------------------
# Operations the architecture and its kernels require, from shapes
# ---------------------------------------------------------------------------


def _layers(config: dict) -> dict:
    kinds = config["layer_types"]
    dense = min(config["num_dense_layers"], len(kinds))
    return {"sliding": kinds.count("sliding_attention"),
            "full": kinds.count("full_attention"),
            "dense": dense, "expert": len(kinds) - dense}


def window_pairs(seq: int, window: int) -> int:
    """(query, key) pairs of one head of one sequence that ``0 <= i - j
    < window`` leaves: the whole triangle of the first ``window``
    queries, ``window`` keys for each query after them."""
    w = min(seq, window)
    return w * (w + 1) // 2 + (seq - w) * w


def _macs_per_token(config: dict) -> dict:
    """Multiply-accumulates of one token's forward pass through each
    kind of sub-layer, attention's score products left out."""
    d, size = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    expert = 3 * d * config["moe_intermediate_size"]
    # a token's top-k choices fall on the experts held here with
    # probability held / router width each: 8 * 8 / 128 = 0.5 experts
    routed = (config["num_experts_per_tok"] * config["num_experts"]
              / config["router_width"])
    return {
        # q, the gate and o at the query heads, k and v at theirs
        "attention": d * size * (3 * heads + 2 * kv),
        "dense": 3 * d * config["intermediate_size"],
        "expert": (d * config["router_width"]
                   + (config["num_shared_experts"] + routed) * expert),
        "head": d * config["vocab_size"]}


def model_flops_per_sample(config: dict, job: dict) -> float:
    """Forward plus backward of one sequence through this chip's share:
    3 x forward, 2 FLOPs a multiply-accumulate.  Forward: an attention
    sub-layer's five projections (the gate's among them); the score and
    value products over the pairs the window leaves on a sliding layer
    and over half the square on a full one; the dense SwiGLU; router +
    shared expert + the routed experts at the expected 0.5 a token; the
    head.  Nothing that is recomputed is counted; norms, rotary, SiLU,
    sigmoid and softmax are left out.  At seq 16,384: 38.75 TFLOP
    (tests/benchmark_suite has the hand-worked value)."""
    seq, m, n = job["seq"], _macs_per_token(config), _layers(config)
    per_token = ((n["sliding"] + n["full"]) * m["attention"]
                 + n["dense"] * m["dense"] + n["expert"] * m["expert"]
                 + m["head"])
    products = config["num_attention_heads"] * 2 * config["head_dim"] * (
        n["sliding"] * window_pairs(seq, config["sliding_window"])
        + n["full"] * seq * (seq + 1) / 2)
    return 3.0 * 2.0 * (seq * per_token + products)


def kernel_costs(config: dict, job: dict) -> dict:
    """What one train step requires of the flash-attention kernels and
    of the experts' grouped products, over all layers, per chip.

    ``gqa_flash``: the full-attention layers' seven causal products over
    the query heads, as ``lm_hybrid_ssm.kernel_costs`` counts them, at a
    head size of 128 for both: what the plain kernels (``hvd_flash_fwd``
    ...) are for here.  ``swa_flash``: the same seven products of ``2 x
    head_dim`` FLOPs a (query, key) pair over the pairs the window
    leaves (:func:`window_pairs`), in the sliding layers: what the
    ``*_win`` kernels are for, whatever tiles or kernel compute it.
    Bytes of either: bf16, each tensor once: q and o at the query heads,
    k and v at the key/value heads forward; q, o, dO in and dq out at
    the query heads, k, v in and dk, dv out at the key/value heads
    backward; plus the f32 row statistics.  Both are FLOP-bound at the
    cell's sizes.

    ``moe_experts``: :func:`expert_cost` of the pairs the held experts
    are expected to be sent; ``moe_experts_roofline`` asks it again for
    the pairs the routing records show."""
    seq, batch, n = job["seq"], job["batch_per_chip"], _layers(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    size = config["head_dim"]
    moved = (2 * batch * seq * size * (6 * heads + 6 * kv)
             + 2 * 4 * batch * heads * seq)
    routed = (batch * seq * config["num_experts_per_tok"]
              * config["num_experts"] / config["router_width"])
    return {
        "gqa_flash": {
            # 2 FLOPs a multiply-accumulate over seq * (seq + 1) / 2 pairs
            "flops": n["full"] * float(batch * heads * seq * (seq + 1))
            * 7 * size,
            "bytes": n["full"] * moved},
        "swa_flash": {
            "flops": n["sliding"] * 2.0 * batch * heads
            * window_pairs(seq, config["sliding_window"]) * 7 * size,
            "bytes": n["sliding"] * moved},
        "moe_experts": expert_cost(config, n["expert"] * routed),
    }


def expert_cost(config: dict, pairs: float) -> dict:
    """What the held experts' grouped products require of a step that
    sends them ``pairs`` (token, expert) pairs, all expert layers added
    up: gate, up and down of each pair, forward and twice that backward.
    Bytes: every layer's held experts' bf16 weights read forward and
    backward and their gradients written, and a pair's rows (input and
    output at the hidden size, gate and up at the expert width, bf16)
    once forward and twice backward."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    layers, held = _layers(config)["expert"], config["num_experts"]
    return {"flops": 3 * 2.0 * pairs * 3 * d * f,
            "bytes": 2 * (layers * 3 * held * 3 * d * f
                          + 3 * pairs * (2 * d + 2 * f))}


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

_QUERY_BLOCK = 512
_STACK_OF = {"S": "swa", "G": "gattn", "D": "dense", "E": "moe"}


def _rmsnorm(x, gain, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rotary(x, theta):
    """The half-rotation layout: the pair ``(x[i], x[i + d/2])`` of the
    last axis turned by position x theta ** (-2i / d); x: (batch, seq,
    heads, d), positions 0 .. seq - 1."""
    import jax.numpy as jnp

    seq, d = x.shape[1], x.shape[-1]
    angle = (jnp.arange(seq, dtype=jnp.float32)[:, None]
             * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    cos = jnp.cos(angle)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angle)[None, :, None, :].astype(x.dtype)
    low, high = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([low * cos - high * sin, low * sin + high * cos],
                           axis=-1)


def _attention(config: dict, lp, h, window):
    """Gated softmax attention: query head ``i`` on key/value head ``i
    // (heads / kv heads)``, taken by index; an RMSNorm over each head's
    q and k; with a ``window`` rotary positions and the mask ``0 <= i -
    j < window``, else no positions and ``j <= i``.  Query blocks of
    ``_QUERY_BLOCK`` rows, each a plain masked softmax over the keys it
    can see (a span of ``block + window`` keys that holds them all),
    the mask from positions; recomputed in the backward pass."""
    import jax
    import jax.numpy as jnp

    batch, seq, _ = h.shape
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    size, eps = config["head_dim"], config["rms_norm_eps"]
    q = _rmsnorm((h @ lp["wq"]).reshape(batch, seq, heads, size),
                 lp["q_norm"], eps)
    k = _rmsnorm((h @ lp["wk"]).reshape(batch, seq, kv, size),
                 lp["k_norm"], eps)
    v = (h @ lp["wv"]).reshape(batch, seq, kv, size)
    if window is not None:
        theta = float(config["rope_theta"])
        q, k = _rotary(q, theta), _rotary(k, theta)
    of_query_head = jnp.arange(heads) // (heads // kv)
    k, v = k[:, :, of_query_head], v[:, :, of_query_head]
    block = min(_QUERY_BLOCK, seq)
    span = seq if window is None else min(seq, block + window)

    @jax.checkpoint
    def one(start):
        first = jnp.clip(start + block - span, 0, seq - span)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(k, first, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, first, span, axis=1)
        apart = ((start + jnp.arange(block))[:, None]
                 - (first + jnp.arange(span))[None, :])
        seen = apart >= 0
        if window is not None:
            seen = seen & (apart < window)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, kb) / math.sqrt(size)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vb)

    blocks = jax.lax.map(one, jnp.arange(0, seq, block))
    out = jnp.moveaxis(blocks, 0, 1).reshape(batch, seq, heads * size)
    return (out * jax.nn.sigmoid(h @ lp["wg"])) @ lp["wo"]


def _swiglu(x, w):
    import jax

    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def _selection(config: dict, w, x):
    """``(ids, weights)`` of the top-k: sigmoid scores over the router's
    whole width, the bias in the selection only, the selected scores
    renormalised and scaled."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(x @ w["router"])
    ids = jnp.argsort(-(scores + w["bias"]), axis=-1, stable=True)[
        ..., :config["num_experts_per_tok"]]
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, (config["route_scale"] * picked
                 / (picked.sum(-1, keepdims=True) + 1e-20))


def _experts(config: dict, w, x):
    """This chip's share of the expert layer: the experts held are the
    first of the router's width, taken one after the other in a Python
    loop, each on every token under its mask; what the others would add
    is left out.  The shared expert is whole.  Returns ``(out, pairs
    sent to each held expert)``."""
    import jax
    import jax.numpy as jnp

    ids, weights = _selection(config, w, x)

    @jax.checkpoint      # an expert keeps nothing for the backward pass
    def part(e, weights_e):
        gate = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        return gate[..., None] * _swiglu(x, weights_e)

    out, sent = _swiglu(x, w["shared"]), []
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
    eps, window = config["rms_norm_eps"], config["sliding_window"]
    sub_layer = {
        "S": lambda lp, h: (_attention(config, lp, h, window), None),
        "G": lambda lp, h: (_attention(config, lp, h, None), None),
        "D": lambda lp, h: (_swiglu(h, lp), None),
        "E": lambda lp, h: _experts(config, lp, h)}

    def layer(kind):
        @jax.checkpoint
        def run(x, lp):
            out, sent = sub_layer[kind](lp, _rmsnorm(x, lp["ln"], eps))
            return x + _rmsnorm(out, lp["ln_post"], eps), sent

        return run

    @jax.checkpoint
    def nll(x):
        logp = jax.nn.log_softmax(
            _rmsnorm(x, params["ln_f"], eps) @ params["head"], axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None],
                                    axis=-1)[..., 0].astype(jnp.float32)

    with jax.default_matmul_precision(
            "highest" if dtype == "float32" else "default"):
        x = params["embed"][tokens] * jnp.asarray(_embed_scale(config),
                                                  dtype)
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

_MATRICES = ("wq", "wk", "wv", "wg", "wo")
_FFN = ("w_gate", "w_up", "w_down")


def _groups(tree: dict) -> dict:
    """The parameter tree's leaves by the part of the model they belong
    to: the matrices of the window layers' attention, of the full
    layers', of the dense FFN; router, routed experts, shared expert;
    every sub-layer's norms (before, after, and the two per-head ones);
    embedding, head and final norm.  The selection bias, which takes no
    gradient, apart."""
    moe = tree["moe"]
    gains = [tree[stack][name] for stack in ("swa", "gattn", "dense", "moe")
             for name in ("ln", "ln_post", "q_norm", "k_norm")
             if name in tree[stack]]
    return {"attention_window": [tree["swa"][m] for m in _MATRICES],
            "attention_full": [tree["gattn"][m] for m in _MATRICES],
            "dense": [tree["dense"][m] for m in _FFN],
            "router": moe["router"], "experts": moe["experts"],
            "shared": moe["shared"], "norms": gains,
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


class Trainer(lm_hybrid_ssm.Trainer):
    """Builds the flagship trainer; ``hvd.init()`` has returned.  The
    hybrid family's trainer (its routing settled before anything is
    read) with this family's configuration, reference and groups; what
    the program reports is the pairs sent to every expert."""

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

    def gradient_program(self):
        """``(params, tokens, targets) -> (loss, loads, gradients)``: the
        system's loss, the pairs its routing sends each of all the
        experts, expert layer by expert layer, and its backward pass
        over the cell's mesh, reduced as ``make_train_step`` reduces
        them."""
        import jax
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.models import transformer
        from horovod_tpu.parallel.sharding import (grad_reduce_axes,
                                                   tree_map_with_specs)

        cfg = self.cfg
        specs = transformer.param_specs(cfg)

        def per_device(p, tok, tgt):
            (loss, reports), grads = jax.value_and_grad(
                transformer.loss_and_routing, has_aux=True)(p, tok, tgt, cfg)
            grads = tree_map_with_specs(
                lambda g, spec: (lax.psum(g, grad_reduce_axes(spec))
                                 if grad_reduce_axes(spec) else g),
                grads, specs)
            return (lax.psum(loss, ("dp", "sp")),
                    lax.psum(reports["loads"], "sp"), grads)

        return jax.jit(shard_map(
            per_device, mesh=self.mesh, check_vma=False,
            in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
            out_specs=(P(), P(), specs)))

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
        (``compile``).  Also writes that batch's routing and one record
        an attention sub-layer to the flight ring
        (``transformer.record_routing``, ``record_attention``) and
        counts the selections that differ from the reference's."""
        import numpy as np

        from horovod_tpu.models import transformer

        *readings, sent, wanted = self.readings()
        record = compare(*readings)
        tokens = self.reference_batch()[0]
        transformer.record_routing(self.cfg, sent, tokens.size)
        record["attention_windows"] = transformer.record_attention(
            self.cfg, self.job["batch_per_chip"])
        record["router_settling"] = self.settled
        record["pairs_sent"] = sent.sum(axis=1).tolist()
        record["pairs_sent_otherwise"] = np.abs(sent - wanted).sum(
            axis=1).tolist()
        return record
