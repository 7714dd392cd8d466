"""Family ``lm_dsa_moe``: a language model whose attention runs over the
keys an indexer selects for each query, and whose FFNs are sparse experts
behind a softmax router with no shared expert (the ``KeyeVL2`` key set:
Keye-VL-2.0-30B-A3B's language stack) through the flagship path —
``TransformerConfig`` + ``init_params`` + ``shard_params`` +
``make_train_step`` on a ``make_mesh`` mesh — cut to one chip's share of
a stated deployment (the configuration file's ``deployment``).

No ``attn_impl`` is forced and no ``HOROVOD_*`` variable is set.

A published layer is two pre-normed sub-layers with a residual each.
The first: an indexer (``sa_config``: 16 heads of 64 on one key head)
scores every earlier key for each query, ``I[t, s] = sum_j w[t, j]
relu(qI[t, j] . kI[s]) / sqrt(64)``, the 2,048 highest are kept (all of
them where there are fewer), and grouped-query attention (32 on 4 heads
of 128, an RMSNorm on each head's ``q`` and ``k``, rotary positions of
three components in sections 16 / 24 / 24) is a softmax over those keys
alone.  The second: a softmax over 128 experts, the top 8 renormalised,
SwiGLU experts of 768.  The program runs them as the layer pattern ``IE``
repeated.  The file's ``num_experts`` is the number of experts held here
(the first that many of ``router_width``), ``vocab_size`` the slice of
the vocabulary held here.

The plain reference reads the system's parameter tree and computes the
same loss in float32 with ``jax.numpy`` only, by blocks of query rows:
the indexer's scores, its own selection (the threshold from a sorted
top-k, ties to the lower key), attention as a masked softmax under a
selection, the key/value head of a query head taken by index, its own
rotary and norms, a Python loop over the experts held.  Given the
system's selection it attends under that one (the two selections differ
in keys within rounding of a row's threshold, and the loss and gradients
are compared under one) and counts what its own has in common with it.
Nothing of it calls ``horovod_tpu``.
"""

from __future__ import annotations

import math

from benchmark.families import lm_hybrid_ssm
from benchmark.families.lm_mesh import _DeviceRandn

# ---------------------------------------------------------------------------
# The comparison that decides ``correct``
# ---------------------------------------------------------------------------

# Three checks, on one 16,384-token sequence at the published widths, of
# what the timed program made at step 0.
#
# (a) every row of every layer's selection keeps exactly ``min(t + 1,
#     2048)`` keys: an integer, no limit.  A selection one key short
#     fails it in 14,337 rows a layer (read on the chip: 86,022 in six).
# (b) the system's selection (bf16 products, f32 scores) against the
#     reference's own (float32 throughout): the share of the system's
#     kept pairs that the reference keeps too, the least over the layers,
#     at or over ``SELECTION_COMMON``.  The two differ where a key lies
#     within the bf16 products' rounding of its row's threshold.
# (c) with the reference attending under the system's selection: the
#     step-0 loss and each group's gradient norm, relative, as the other
#     expert cells compare them; the indexer's gradient exactly 0 on
#     both sides.
#
# Read on the chip (PR 40, PERF.md section 6; weights as the cell draws
# them): the largest the system (bf16 products with f32 accumulation, a
# bf16 residual stream; norms, the indexer's scores, the router and the
# logits' reductions in f32) gave over ten seeds, and the least the
# reference itself gave over four seeds when computed in bfloat16
# throughout (the nearest precision below the configuration's), which
# must come out as not correct:
#
#     group         system,    bfloat16 reference,
#                   largest    least (.. largest)
#     loss          1.05e-5    1.5e-6 (.. 1.4e-5)
#     attention     3.95e-5    4.67e-4 (.. 5.6e-4)
#     router        3.88e-4    2.40e-3 (.. 3.3e-3)
#     experts       1.59e-4    2.12e-3 (.. 2.3e-3)
#     norms         3.89e-4    8.1e-4 (.. 1.7e-3)
#     embed_head    5.42e-5    3.17e-4 (.. 3.8e-4)
#     common share  0.99492 (the least: layer 5; layer 0 0.9970 to
#                   0.9971)    0.99494 (its own bf16 selection is as far
#                              from the system's as the float32 one is)
#
# Four limits lie between their readings, with the more room above the
# system's since fresh seeds read higher: ``attention`` 5 times above the
# system's largest and 2.3 below the reference's least, ``router`` 3.1
# and 2.0, ``experts`` 3.8 and 3.5, ``embed_head`` 3.0 and 2.0 (the
# system reads 4.0e-5 to 5.4e-5 on every seed: a bias of the bf16 stream,
# not noise); any one of them fails the bfloat16 reference on each of the
# four seeds.  As in the other expert cells a bfloat16 router flips
# selections (the system's f32 router over a bf16 stream sends 9-34 of a
# layer's 8,192 held pairs otherwise).  ``norms`` does not part the two
# (the gains' gradient is 4e-4 of the whole, and its relative reading
# runs from 8e-6 to 3.9e-4 by the seed) and stands three times above the
# system's largest; the loss at the accepted expert cells' limit, 29
# times above.  Check (b) does not part the precisions either — the
# system's indexer multiplies in bf16 itself — and is there for a
# selection that is not the exact top-k.  Its limit lies between the
# system's least over ten seeds (0.99492; layer 5 reads 0.99492 to
# 0.99495 on every one) and the most an approximate top-k read on the
# chip, one seed (the threshold taken from every other key, the fault
# the CPU tests plant: 0.99208 to 0.99278 over the six layers, and
# 83,948 rows of another count, so it fails (a) as well).
LOSS_RTOL = 3e-4
GROUP_RTOL = {"attention": 2e-4, "router": 1.2e-3, "experts": 6e-4,
              "norms": 1.2e-3, "embed_head": 1.6e-4}
SELECTION_COMMON = 0.994


def _layers(config: dict) -> int:
    return config["num_hidden_layers"]


def _pattern(config: dict) -> str:
    """One sub-layer a layer: a published layer's indexed attention,
    then its experts."""
    assert config["decoder_sparse_step"] == 1 and not config["mlp_only_layers"]
    return "IE" * _layers(config)


def _kwargs(config: dict, job: dict) -> dict:
    """``TransformerConfig``'s arguments from the configuration file."""
    assert config["norm_topk_prob"] and config["hidden_act"] == "silu"
    assert not (config["tie_word_embeddings"] or config["attention_bias"]
                or config["use_sliding_window"])
    assert config["rope_scaling"]["rope_type"] == "default"
    assert config["router_width"] == config["num_local_experts"]
    indexer = config["sa_config"]
    assert indexer["indexer_num_kv_heads"] == 1
    return dict(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], head_dim=config["head_dim"],
        n_kv_heads=config["num_key_value_heads"], max_seq=job["seq"],
        dtype=config["compute_dtype"], tied_head=False, remat=True,
        layer_pattern=_pattern(config), norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        rope_sections=tuple(config["rope_scaling"]["mrope_section"]),
        index_heads=indexer["indexer_num_heads"],
        index_head_dim=indexer["indexer_head_dim"],
        index_topk=indexer["topk"],
        rescale_depth=config["published"]["num_hidden_layers"],
        n_experts=config["router_width"], experts_held=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"], router="softmax")


# ---------------------------------------------------------------------------
# Operations the architecture and its kernels require, from shapes
# ---------------------------------------------------------------------------


def kept_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs of one sequence that the selection leaves:
    every earlier key of the first ``topk`` queries, ``topk`` keys for
    each query after them (the count a window of ``topk`` leaves)."""
    k = min(seq, topk)
    return k * (k + 1) // 2 + (seq - k) * k


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def _macs_per_token(config: dict) -> dict:
    """Multiply-accumulates of one token's forward pass through each
    kind of sub-layer, the products over (query, key) pairs left out."""
    d, size = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    indexer = config["sa_config"]
    # a token's top-k choices fall on the experts held here with
    # probability held / router width each: 8 * 8 / 128 = 0.5 experts
    routed = (config["num_experts_per_tok"] * config["num_experts"]
              / config["router_width"])
    return {
        # q and o at the query heads, k and v at theirs
        "attention": d * size * (2 * heads + 2 * kv),
        # the indexer's query heads, its one key head, a weight a head
        "indexer": d * (indexer["indexer_head_dim"]
                        * (indexer["indexer_num_heads"] + 1)
                        + indexer["indexer_num_heads"]),
        "expert": (d * config["router_width"]
                   + routed * 3 * d * config["moe_intermediate_size"]),
        "head": d * config["vocab_size"]}


def model_flops_per_sample(config: dict, job: dict) -> float:
    """Forward plus backward of one sequence through this chip's share,
    2 FLOPs a multiply-accumulate.  Three times the forward pass of what
    takes a gradient: an attention sub-layer's four projections, its
    score and value products over the pairs the selection leaves
    (:func:`kept_pairs`), the router and the routed experts at the
    expected 0.5 a token, the head.  Once the forward pass of the
    indexer, which takes none: its three projections and its scores
    over the causal pairs.  Nothing that is recomputed is counted;
    norms, rotary, relu, SiLU, softmax and the selection's compares are
    left out.  At seq 16,384: 27.87 TFLOP (tests/benchmark_suite has the
    hand-worked value)."""
    seq, m, n = job["seq"], _macs_per_token(config), _layers(config)
    indexer = config["sa_config"]
    trained = (seq * (n * (m["attention"] + m["expert"]) + m["head"])
               + n * config["num_attention_heads"] * 2 * config["head_dim"]
               * kept_pairs(seq, indexer["topk"]))
    once = n * (seq * m["indexer"] + indexer["indexer_num_heads"]
                * indexer["indexer_head_dim"] * causal_pairs(seq))
    return 2.0 * (3.0 * trained + once)


def kernel_costs(config: dict, job: dict) -> dict:
    """What one train step requires of attention under the selection, of
    the indexer's scores and of the experts' grouped products, over all
    layers, per chip.

    ``dsa_attend``: seven products of ``2 x head_dim`` FLOPs a (query,
    key) pair over the pairs the selection leaves (:func:`kept_pairs`),
    every query head of every layer: the required work, whatever tiles
    or kernel compute it (a masked causal call computes 4.27 times the
    pairs at 16,384 tokens).  Bytes as ``lm_swa_moe.kernel_costs``
    counts them: bf16, each tensor once, q and o at the query heads, k
    and v at the key/value heads forward; q, o, dO in and dq out at the
    query heads, k, v in and dk, dv out at the key/value heads backward;
    plus the f32 row statistics.  FLOP-bound.

    ``dsa_index``: the indexer's scores, ``2 x heads x head size`` FLOPs
    a causal pair, once a step (no gradient, and a recomputed layer
    keeps its selection).  Bytes: its bf16 queries and keys and f32
    weights read and the selection's packed bits written.  FLOP-bound.

    ``moe_experts``: :func:`expert_cost` of the pairs the held experts
    are expected to be sent; ``moe_experts_roofline`` asks it again for
    the pairs the routing records show."""
    seq, batch, n = job["seq"], job["batch_per_chip"], _layers(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    size, indexer = config["head_dim"], config["sa_config"]
    iheads, isize = indexer["indexer_num_heads"], indexer["indexer_head_dim"]
    routed = (batch * seq * config["num_experts_per_tok"]
              * config["num_experts"] / config["router_width"])
    return {
        "dsa_attend": {
            "flops": n * 2.0 * batch * heads
            * kept_pairs(seq, indexer["topk"]) * 7 * size,
            "bytes": n * (2 * batch * seq * size * (6 * heads + 6 * kv)
                          + 2 * 4 * batch * heads * seq)},
        "dsa_index": {
            "flops": n * 2.0 * batch * iheads * isize * causal_pairs(seq),
            "bytes": n * batch * (seq * (2 * isize * (iheads + 1)
                                         + 4 * iheads) + seq * seq // 8)},
        "moe_experts": expert_cost(config, n * routed),
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
    layers, held = _layers(config), config["num_experts"]
    return {"flops": 3 * 2.0 * pairs * 3 * d * f,
            "bytes": 2 * (layers * 3 * held * 3 * d * f
                          + 3 * pairs * (2 * d + 2 * f))}


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

_QUERY_BLOCK = 512
_STACK_OF = {"I": "dsa", "E": "moe"}
# the packed form the system hands its selection over in: 4,096 keys to a
# row of 128 int32 words, key s of a span in bit s // 128 of word s % 128
_SPAN, _LANES = 4096, 128


def _rmsnorm(x, gain, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rotary(x, theta, sections):
    """The half-rotation layout: the pair ``(x[i], x[i + d/2])`` of the
    last axis turned by p x theta ** (-2i / d), p the component of the
    token's position that pair ``i``'s section names — for text all
    three are its index, 0 .. seq - 1, but each pair reads its own; x:
    (batch, seq, heads, d)."""
    import jax.numpy as jnp

    seq, d = x.shape[1], x.shape[-1]
    index = jnp.arange(seq, dtype=jnp.float32)
    position = jnp.stack([index, index, index])          # (3, seq)
    component = jnp.concatenate([jnp.full(n, c, jnp.int32)
                                 for c, n in enumerate(sections)])
    angle = (position[component].T
             * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    cos = jnp.cos(angle)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angle)[None, :, None, :].astype(x.dtype)
    low, high = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([low * cos - high * sin, low * sin + high * cos],
                           axis=-1)


def _unpacked(words, seq: int):
    """(batch, rows, W) int32 -> (batch, rows, seq) bool."""
    import jax.numpy as jnp

    key = jnp.arange(seq)
    word = (key // _SPAN) * _LANES + key % _LANES
    return (words[..., word] >> (key % _SPAN // _LANES)) & 1 == 1


def _own_selection(config: dict, lp, h, start, block):
    """The keys that queries ``start .. start + block`` keep, (batch,
    block, seq) bool: the indexer's scores against every key, the
    ``topk``-th largest of a row from a sorted top-k, every key above
    it, and of the keys level with it the first that are still needed;
    every key a query can see where those are fewer than ``topk``."""
    import jax
    import jax.numpy as jnp

    indexer = config["sa_config"]
    heads, size = indexer["indexer_num_heads"], indexer["indexer_head_dim"]
    batch, seq, _ = h.shape
    topk = min(indexer["topk"], seq)
    hb = jax.lax.dynamic_slice_in_dim(h, start, block, axis=1)
    q = (hb @ lp["wq_idx"]).reshape(batch, block, heads, size)
    k = h @ lp["wk_idx"]
    w = (hb @ lp["ww_idx"]) / math.sqrt(heads)
    scores = jnp.einsum("bqj,bqjk->bqk", w, jax.nn.relu(
        jnp.einsum("bqjd,bkd->bqjk", q, k))) / math.sqrt(size)
    seen = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)[None, :]
    scores = jnp.where(seen, scores.astype(jnp.float32), -jnp.inf)
    least = jax.lax.top_k(scores, topk)[0][..., -1:]
    above, level = scores > least, scores == least
    needed = topk - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (level & (jnp.cumsum(level, axis=-1) <= needed))) & seen


def _attention(config: dict, lp, h, given):
    """Softmax attention under a selection: query head ``i`` on
    key/value head ``i // (heads / kv heads)``, taken by index; an
    RMSNorm over each head's q and k; rotary positions.  Query blocks of
    ``_QUERY_BLOCK`` rows, each a plain masked softmax over every key,
    under the ``given`` selection ((batch, seq, W) packed) or, with
    none, the block's own; recomputed in the backward pass.  Returns
    ``(out, pairs the own selection keeps, pairs it has in common with
    the given one)``."""
    import jax
    import jax.numpy as jnp

    batch, seq, _ = h.shape
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    size, eps = config["head_dim"], config["rms_norm_eps"]
    theta = float(config["rope_theta"])
    sections = config["rope_scaling"]["mrope_section"]
    q = _rmsnorm((h @ lp["wq"]).reshape(batch, seq, heads, size),
                 lp["q_norm"], eps)
    k = _rmsnorm((h @ lp["wk"]).reshape(batch, seq, kv, size),
                 lp["k_norm"], eps)
    v = (h @ lp["wv"]).reshape(batch, seq, kv, size)
    q, k = _rotary(q, theta, sections), _rotary(k, theta, sections)
    of_query_head = jnp.arange(heads) // (heads // kv)
    k, v = k[:, :, of_query_head], v[:, :, of_query_head]
    block = min(_QUERY_BLOCK, seq)
    detached = jax.lax.stop_gradient(h)

    @jax.checkpoint
    def one(start):
        own = _own_selection(config, lp, detached, start, block)
        kept = own if given is None else _unpacked(
            jax.lax.dynamic_slice_in_dim(given, start, block, axis=1), seq)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(size)
        probs = jax.nn.softmax(
            jnp.where(kept[:, None], scores, -jnp.inf), axis=-1)
        return (jnp.einsum("bhqk,bkhd->bqhd", probs, v),
                jnp.sum(own), jnp.sum(own & kept))

    blocks, own, common = jax.lax.map(one, jnp.arange(0, seq, block))
    out = jnp.moveaxis(blocks, 0, 1).reshape(batch, seq, heads * size)
    return out @ lp["wo"], jnp.sum(own), jnp.sum(common)


def _swiglu(x, w):
    import jax

    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def _experts(config: dict, w, x):
    """This chip's share of the expert layer: a softmax over the
    router's whole width, the top-k probabilities renormalised; the
    experts held are the first of the width, taken one after the other
    in a Python loop, each on every token under its mask; what the
    others would add is left out, and there is no shared expert.
    Returns ``(out, pairs sent to each held expert)``."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(x @ w["router"], axis=-1)
    ids = jnp.argsort(-probs, axis=-1, stable=True)[
        ..., :config["num_experts_per_tok"]]
    picked = jnp.take_along_axis(probs, ids, axis=-1)
    weights = picked / picked.sum(-1, keepdims=True)

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
                   dtype: str = "float32", selections=None):
    """``(loss, report)``: the mean next-token cross entropy, and
    ``report`` with ``sent`` (the pairs each held expert is sent, expert
    layer by expert layer), ``own_pairs`` (the pairs the reference's own
    selection keeps, attention layer by attention layer) and
    ``common_pairs`` (those of them that ``selections`` keeps too).
    ``selections``: (layers, batch, seq, W) packed, the system's; the
    reference attends under them.  None: under its own.  Every sub-layer
    is recomputed in the backward pass.  A ``dtype`` other than float32
    computes everything in that type (the lower-precision reading the
    limits are set against)."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    eps = config["rms_norm_eps"]

    @jax.checkpoint
    def attend(x, lp, given):
        out, own, common = _attention(config, lp, _rmsnorm(x, lp["ln"], eps),
                                      given)
        return x + out, (own, common)

    @jax.checkpoint
    def route(x, lp):
        out, sent = _experts(config, lp, _rmsnorm(x, lp["ln"], eps))
        return x + out, sent

    @jax.checkpoint
    def nll(x):
        logp = jax.nn.log_softmax(
            _rmsnorm(x, params["ln_f"], eps) @ params["head"], axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None],
                                    axis=-1)[..., 0].astype(jnp.float32)

    with jax.default_matmul_precision(
            "highest" if dtype == "float32" else "default"):
        x = params["embed"][tokens]
        rows = dict.fromkeys(_STACK_OF, 0)
        report = {"sent": [], "own_pairs": [], "common_pairs": []}
        for kind in _pattern(config):
            row = rows[kind]
            lp = jax.tree_util.tree_map(lambda a: a[row],
                                        params[_STACK_OF[kind]])
            rows[kind] += 1
            if kind == "I":
                x, (own, common) = attend(
                    x, lp, None if selections is None else selections[row])
                report["own_pairs"].append(own)
                report["common_pairs"].append(common)
            else:
                x, sent = route(x, lp)
                report["sent"].append(sent)
        loss = jnp.mean(nll(x))
    return loss, {name: jnp.stack(rows) for name, rows in report.items()}


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

_MATRICES = ("wq", "wk", "wv", "wo")
_INDEXER = ("wq_idx", "wk_idx", "ww_idx")


def _groups(tree: dict) -> dict:
    """The parameter tree's leaves by the part of the model they belong
    to: attention's matrices; the indexer's, which take no gradient;
    router and routed experts; every sub-layer's norm and the two
    per-head ones; embedding, head and final norm."""
    dsa, moe = tree["dsa"], tree["moe"]
    return {"attention": [dsa[m] for m in _MATRICES],
            "indexer": [dsa[m] for m in _INDEXER],
            "router": moe["router"], "experts": moe["experts"],
            "norms": [dsa["ln"], dsa["q_norm"], dsa["k_norm"], moe["ln"]],
            "embed_head": (tree["embed"], tree["head"], tree["ln_f"])}


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
    """The record of check (c); ``ok``: the loss and every group's
    gradient norm inside its limit, and the indexer's gradient exactly
    zero on both sides.  (The CPU tests, float32 on both sides, pass
    tighter limits.)"""
    loss, ref_loss = float(loss), float(ref_loss)
    loss_err = abs(loss - ref_loss) / abs(ref_loss)
    errs = {name: (abs(norms[name] - ref_norms[name])
                   / (ref_norms[name] or 1e-30)) for name in group_rtol}
    ok = (math.isfinite(loss) and loss_err < loss_rtol
          and all(errs[name] < limit for name, limit in group_rtol.items())
          and norms["indexer"] == 0.0 and ref_norms["indexer"] == 0.0)
    return {"ok": bool(ok), "loss": loss, "reference_loss": ref_loss,
            "loss_rel_err": loss_err, "loss_rtol": loss_rtol,
            "grad_norm_rel_err": errs, "grad_norm_rtol": group_rtol,
            "grad_norm": norms, "reference_grad_norm": ref_norms}


def selection_checks(selections, own_pairs, common_pairs, topk: int,
                     common_share: float = SELECTION_COMMON) -> dict:
    """Checks (a) and (b) of the system's ``selections`` ((layers,
    batch, seq, W) packed): the rows whose kept count is not ``min(t +
    1, topk)``, which must be none, and the least share over the layers
    of the system's kept pairs that the reference's own selection keeps
    too.  (Bits past a row's last key are 0 in the packed form, so a
    row's count is its words' set bits.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seq = selections.shape[2]
    kept = np.asarray(jax.jit(lambda words: jnp.sum(
        jax.lax.population_count(words), axis=-1))(selections))
    wrong = int((kept != np.minimum(np.arange(seq) + 1, topk)).sum())
    pairs = kept.sum(axis=(1, 2)).tolist()
    share = [float(c) / k for c, k in zip(np.asarray(common_pairs), pairs)]
    # both selections are exact: they keep as many pairs
    ok = (wrong == 0 and min(share) >= common_share
          and np.asarray(own_pairs).tolist() == pairs)
    return {"ok": bool(ok), "rows_with_another_count": wrong,
            "kept_pairs": pairs, "common_share": share,
            "common_share_limit": common_share}


class Trainer(lm_hybrid_ssm.Trainer):
    """Builds the flagship trainer; ``hvd.init()`` has returned.  The
    hybrid family's trainer with this family's configuration, reference
    and groups; the router has no bias, so nothing is settled; what the
    program reports is the pairs sent to every expert and every layer's
    packed selection."""

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
        rate, warm_up = (config["optimizer"]["learning_rate"],
                         config["optimizer"].get("warmup_steps", 0))
        self.opt = opt = optax.adamw(
            optax.linear_schedule(0.0, rate, warm_up) if warm_up else rate)
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

    def gradient_program(self):
        """``(params, tokens, targets) -> (loss, reports, gradients)``:
        the system's loss, what its layers report (``loads``, the pairs
        sent to each of all the experts; ``selections``, each attention
        layer's packed selection) and its backward pass over the cell's
        mesh, reduced as ``make_train_step`` reduces them."""
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
            reports = {"loads": lax.psum(reports["loads"], "sp"),
                       "selections": reports["selections"]}
            return lax.psum(loss, ("dp", "sp")), reports, grads

        return jax.jit(shard_map(
            per_device, mesh=self.mesh, check_vma=False,
            in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
            out_specs=(P(), {"loads": P(), "selections": P(None, "dp")},
                       specs)))

    def readings(self, reference_dtype: str = "float32") -> tuple:
        """``(loss, group norms, reference loss, reference group norms,
        what the system's layers report, what the reference reports)``
        on the first ``reference_samples`` sequences, the reference
        attending under the system's selections; one gradient tree alive
        at a time."""
        import functools

        import jax
        import numpy as np

        tokens, targets = self.reference_batch()
        loss, reports, grads = self.gradient_program()(self.params(), tokens,
                                                       targets)
        norms = _group_norms(grads)
        del grads
        (ref_loss, wanted), grads = jax.jit(jax.value_and_grad(
            functools.partial(reference_loss, self.config,
                              dtype=reference_dtype), has_aux=True))(
                self.params(), tokens, targets,
                selections=reports["selections"])
        ref_norms = _group_norms(grads)
        del grads
        wanted = {name: np.asarray(rows) for name, rows in wanted.items()}
        # the experts held are the first of the router's width
        reports["pairs"] = np.asarray(reports["loads"])[
            :, :wanted["sent"].shape[1]]
        return loss, norms, ref_loss, ref_norms, reports, wanted

    def check_reference(self) -> dict:
        """Checks (a), (b) and (c) at step 0 against the float32
        reference; the optimizer's state is made after (``compile``).
        Also writes that batch's routing and one record a selection to
        the flight ring (``transformer.record_routing``,
        ``record_selection``) and counts the expert selections that
        differ from the reference's."""
        import numpy as np

        from horovod_tpu.models import transformer

        *readings, reports, wanted = self.readings()
        record = compare(*readings)
        record["selection"] = selection_checks(
            reports["selections"], wanted["own_pairs"],
            wanted["common_pairs"], self.cfg.index_topk)
        record["ok"] = record["ok"] and record["selection"]["ok"]
        sent = reports["pairs"]
        transformer.record_routing(self.cfg, sent,
                                   self.reference_batch()[0].size)
        record["selections"] = transformer.record_selection(
            self.cfg, reports["selections"])
        record["pairs_sent"] = sent.sum(axis=1).tolist()
        record["pairs_sent_otherwise"] = np.abs(sent - wanted["sent"]).sum(
            axis=1).tolist()
        return record
