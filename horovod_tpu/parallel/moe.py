"""The expert layer: one chip's share of a fine-grained mixture of
experts, dropless.

Absent from the reference (SURVEY §2.7); TPU extension.  The layer is
told which experts it holds (``first``, and as many as its weights have
rows), routes every token over **all** ``E`` experts — sigmoid scores,
the top ``k`` of score + correction bias, the selected scores
renormalised and scaled (the DeepSeek-V3 ``noaux_tc`` rule); or, where
the layer has no such bias, a softmax over all ``E``, the top ``k`` of
the probabilities, renormalised — and adds
up what its own experts give for the (token, expert) pairs sent to
them.  A shared expert, where the weights have one, is computed whole
for every token.  What absent experts would have added is left out:
the shares of all holders add up to the whole layer
(``tests/test_pipeline_moe.py``).

No capacity and no dropped pair.  The pairs are sorted by expert, the
held ones first, and taken in chunks: a gather of the rows, the grouped
products over the experts held (``lax.ragged_dot``: three for a SwiGLU
expert, two for a relu^2 one), a scatter-add back onto the tokens.  A
chunk is sized from the shapes (:func:`chunk_rows`): ``CHUNK_ROWS``
rows in as many equal parts as still hold, with a third to spare, the
share of the ``tokens x k`` pairs that a balanced router sends the
experts held.  Every row past the pairs is gathered, selected away and
added back as zero; no load runs more rows than in chunks of
``CHUNK_ROWS``, and a balanced one fewer.  A loop with a trip
count read from the routing runs as many chunks as the held pairs
fill, so work and memory follow the pairs that exist and not the worst
case (``tokens x k`` rows when every token picks held experts), and
any routing, however skewed, is computed in full.  The backward pass
is the same loop over the chunks' own ``jax.vjp``.

The experts' form is a property of the layer's weights: three matrices
(``w_gate``, ``w_up``, ``w_down``) are SwiGLU, ``W_down (silu(W_gate x)
* W_up x)``; two (``w_up``, ``w_down``) are ``W_down relu(W_up x)^2``.
The shared expert, the backward rule (each chunk's own ``jax.vjp``) and
:func:`moe_reference` read the same.

One chip: no exchange.  Over an ``ep`` mesh axis the same share runs
between an all-gather of the tokens and a reduce-scatter of the partial
results.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common.types import HorovodTpuError

# the most rows a chunk has: what bounds its f32 temporaries
CHUNK_ROWS = 16384


def chunk_rows(pairs: int, held: int, n_experts: int) -> int:
    """Rows of a chunk of ``pairs`` sorted (token, slot) pairs where
    ``held`` of ``n_experts`` experts are held: ``CHUNK_ROWS`` rows (at
    most ``pairs``) in as many equal parts as still hold the held
    experts' expected share and a third.  A trip costs what some six
    thousand rows cost, and a grouped product only the pairs it meets
    (PERF.md section 6, PR 34), so a balanced layer is one trip; and
    the parts of one whole chunk are its rows (rounded up), so a load
    past a part runs no more rows than it would in whole chunks."""
    whole = min(CHUNK_ROWS, pairs)
    share = -(-4 * pairs * held // (3 * n_experts))
    return -(-whole // max(1, whole // share))


def route(x, router_w, bias, top_k: int, scale: float):
    """``(ids (T, k) int32, weights (T, k) f32)``: sigmoid scores over
    all experts in float32, the top ``k`` of ``score + bias`` (the bias
    selects and takes no gradient), weights ``scale * s_i / (sum of the
    selected s + 1e-20)``.  The router's kind is read from its weights,
    as the experts' form is: with no ``bias`` (None) the scores are a
    softmax over all experts and the top ``k`` are the largest of them,
    weighted alike."""
    with jax.named_scope("hvd_moe_route"):
        logits = jnp.dot(
            x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=lax.Precision.HIGHEST)
        if bias is None:
            scores = jax.nn.softmax(logits, axis=-1)
            _, ids = lax.top_k(scores, top_k)
        else:
            scores = jax.nn.sigmoid(logits)
            _, ids = lax.top_k(scores + bias.astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(scores, ids, axis=-1)
        weights = scale * picked / (
            jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return ids, weights


def _through(a, b):
    """``a b^T``: (..., m) by (n, m), f32 accumulation."""
    return lax.dot_general(a, b, (((a.ndim - 1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _over_rows(a, b):
    """``a^T b`` summed over every leading axis: (..., m) by (..., n) ->
    (m, n), f32 accumulation."""
    rows = tuple(range(a.ndim - 1))
    return lax.dot_general(a, b, ((rows, rows), ((), ())),
                           preferred_element_type=jnp.float32)


@jax.custom_vjp
def swiglu(x, w):
    """``(silu(x W_gate) * (x W_up)) W_down`` with f32 accumulation;
    the shared expert and the dense layers' MLP.  ``x`` (..., d) and
    ``w`` in the compute dtype.

    A backward rule of its own: JAX's would hand the five backward
    products f32 operands built from the two f32 (tokens x f) products
    ``gate`` and ``up``, and XLA fuses that elementwise producer into
    each product, which then reads both f32 tensors (PERF.md section
    6).  The rule rounds ``hidden``, ``d_gate`` and ``d_up`` to the
    compute dtype once, in one elementwise pass, and the products read
    those: the rounding a product at default precision gives an f32
    operand anyway, at another place.  ``relu2`` needs no rule: XLA
    fuses its one elementwise step into its one product's
    compute-dtype output."""
    return _swiglu_fwd(x, w)[0]


def _swiglu_fwd(x, w):
    gate = jnp.dot(x, w["w_gate"], preferred_element_type=jnp.float32)
    up = jnp.dot(x, w["w_up"], preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    # kept: what the backward reads; under a caller's checkpoint its
    # policy decides whether gate and up are kept or computed again
    return jnp.dot(hidden, w["w_down"],
                   preferred_element_type=jnp.float32), (x, w, gate, up)


def _swiglu_bwd(kept, g):
    x, w, gate, up = kept
    cd = x.dtype
    d_hidden = _through(g, w["w_down"]).astype(cd)
    sig = jax.nn.sigmoid(gate)
    silu = gate * sig
    hidden = (silu * up).astype(cd)
    d_gate = (d_hidden * up * sig * (1 + gate * (1 - sig))).astype(cd)
    d_up = (d_hidden * silu).astype(cd)
    # written once, in the compute dtype: without the barrier XLA fuses
    # the pass back into some of the five products (which two or three
    # depends on the surrounding step), and each such product reads the
    # f32 gate and up again, tile by tile
    hidden, d_gate, d_up = lax.optimization_barrier((hidden, d_gate, d_up))
    dx = _through(d_gate, w["w_gate"]) + _through(d_up, w["w_up"])
    grads = {"w_gate": _over_rows(x, d_gate), "w_up": _over_rows(x, d_up),
             "w_down": _over_rows(hidden, g)}
    return dx.astype(cd), {name: grads[name].astype(a.dtype)
                           for name, a in w.items()}


swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def relu2(x, w):
    """``relu(x W_up)^2 W_down`` with f32 accumulation: the shared
    expert of a two-matrix layer."""
    up = jnp.dot(x, w["w_up"], preferred_element_type=jnp.float32)
    return jnp.dot(jnp.square(jax.nn.relu(up)).astype(x.dtype), w["w_down"],
                   preferred_element_type=jnp.float32)


def pairs_per_expert(ids, first, held: int):
    """How many pairs :func:`route` sent to each of the ``held`` experts
    from ``first`` on: (held,) int32.  Every one is computed; none is
    dropped."""
    local = ids.reshape(-1, 1) - first
    return jnp.sum(local == jnp.arange(held, dtype=local.dtype), axis=0,
                   dtype=jnp.int32)


def _plan(ids, first, held: int, rows: int):
    """The (token, slot) pairs in expert order, held experts first, cut
    into chunks of ``rows``.  Returns ``(order, starts (held,), ends
    (held,))``: pair ``order[r]`` sits in row ``r``; expert ``first +
    e`` owns rows ``starts[e] .. ends[e]``; rows from ``ends[-1]`` on
    go to experts held elsewhere.  ``order`` is padded to a whole number of chunks
    (the padding lies past every pair and is never reached)."""
    local = ids.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    pad = -order.size % rows
    if pad:
        order = jnp.concatenate([order, jnp.zeros(pad, order.dtype)])
    counts = pairs_per_expert(ids, first, held)
    ends = jnp.cumsum(counts)
    return order, ends - counts, ends


def _chunk(x, w, weights, plan, index, rows: int):
    """Rows ``index * rows ..`` of the sorted pairs: ``(tokens (rows,),
    out (rows, d) f32)``, the weighted outputs of the held experts for
    those pairs, zero in rows past the last held pair."""
    order, starts, ends = plan
    top_k = weights.shape[1]
    start = index * rows
    pairs = lax.dynamic_slice_in_dim(order, start, rows)
    tokens = pairs // top_k
    live = (start + jnp.arange(rows, dtype=jnp.int32)) < ends[-1]
    sizes = (jnp.clip(ends - start, 0, rows)
             - jnp.clip(starts - start, 0, rows))
    xs = jnp.where(live[:, None], x[tokens], 0)

    def grouped(a, b):
        # a row past the last held pair belongs to no group, and on the
        # TPU neither the product nor its transpose writes it: what
        # stands there is what the buffer held, a NaN now and then, and
        # 0 x NaN is NaN (PERF.md section 6, PR 32).  Selected away,
        # here and, by this select's own transpose, in the backward pass.
        return jnp.where(live[:, None], lax.ragged_dot(
            a, b, sizes, preferred_element_type=jnp.float32), 0.0)

    if "w_gate" in w:
        hidden = (jax.nn.silu(grouped(xs, w["w_gate"]))
                  * grouped(xs, w["w_up"])).astype(x.dtype)
    else:
        hidden = jnp.square(jax.nn.relu(grouped(xs, w["w_up"]))).astype(
            x.dtype)
    scale = weights.reshape(-1)[pairs]
    return tokens, grouped(hidden, w["w_down"]) * scale[:, None]


def _live_chunks(plan, rows: int):
    return (plan[2][-1] + rows - 1) // rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def expert_share(x, w, ids, weights, first, n_experts: int):
    """``sum_i w_i E_i(x)`` over the pairs whose expert is one of the
    ``held`` from ``first`` on: (T, d) f32.  ``x``: (T, d) in the
    compute dtype; ``w``: ``{"w_gate", "w_up": (held, d, f), "w_down":
    (held, f, d)}`` in the compute dtype (no ``w_gate``: relu^2
    experts); ``ids``, ``weights``: (T, k)
    from :func:`route`; ``first``: an int or a traced scalar;
    ``n_experts``: the router's width, which sizes a chunk."""
    return _expert_share_fwd(x, w, ids, weights, first, n_experts)[0]


def _expert_share_fwd(x, w, ids, weights, first, n_experts):
    held = w["w_down"].shape[0]
    rows = chunk_rows(ids.size, held, n_experts)
    plan = _plan(ids, first, held, rows)

    def body(index, out):
        tokens, part = _chunk(x, w, weights, plan, index, rows)
        return out.at[tokens].add(part)

    out = lax.fori_loop(0, _live_chunks(plan, rows), body,
                        jnp.zeros(x.shape, jnp.float32))
    return out, (x, w, weights, plan)


def _expert_share_bwd(n_experts, res, dout):
    """The forward's loop again, each chunk through its own
    ``jax.vjp``: nothing of a chunk outlives its turn."""
    x, w, weights, plan = res
    rows = chunk_rows(weights.size, w["w_down"].shape[0], n_experts)

    def body(index, grads):
        dx, dw, dweights = grads
        (tokens, _), vjp = jax.vjp(
            lambda x_, w_, weights_: _chunk(x_, w_, weights_, plan, index,
                                            rows), x, w, weights)
        gx, gw, gweights = vjp((jnp.zeros(tokens.shape, jax.dtypes.float0),
                                dout[tokens]))
        return (dx + gx.astype(jnp.float32),
                jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), dw, gw),
                dweights + gweights)

    zeros = (jnp.zeros(x.shape, jnp.float32),
             jax.tree_util.tree_map(
                 lambda a: jnp.zeros(a.shape, jnp.float32), w),
             jnp.zeros_like(weights))
    dx, dw, dweights = lax.fori_loop(0, _live_chunks(plan, rows), body,
                                     zeros)
    return (dx.astype(x.dtype),
            jax.tree_util.tree_map(lambda g, a: g.astype(a.dtype), dw, w),
            None, dweights, None)


expert_share.defvjp(_expert_share_fwd, _expert_share_bwd)


def settle_bias(bias, loads, rate: float):
    """One round of the selection bias's balance rule (the ``noaux_tc``
    rule of the DeepSeek-V3 family): an expert that was sent more pairs
    than the mean of its layer loses ``rate``, one that was sent fewer
    gains it.  ``bias``, ``loads``: (..., E) over all experts."""
    loads = loads.astype(jnp.float32)
    return bias + rate * jnp.sign(
        jnp.mean(loads, axis=-1, keepdims=True) - loads)


def moe_layer(x, params, *, top_k: int, scale: float, first=0,
              axis_name: str | None = None, count_all: bool = False):
    """One expert layer on (T, d) tokens in the compute dtype.

    ``params``: ``router`` (d, E) and ``bias`` (E,) over all ``E``
    experts (no ``bias``: a softmax router, :func:`route`); ``experts``
    ``{"w_gate", "w_up": (held, d, f), "w_down":
    (held, f, d)}``, the experts ``first .. first + held`` of the ``E``
    (without ``w_gate`` they are relu^2 experts); ``shared`` (optional)
    one expert's weights of either form.  Over ``axis_name`` (an
    ``ep`` mesh axis of size > 1) rank ``r`` holds the experts from
    ``first + r * held`` on: the tokens are gathered, every rank
    computes its share for all of them, and a reduce-scatter returns
    to each rank its own tokens' sum.  Returns ``(out (T, d) f32,
    pairs (held,) int32)``: the routed part plus the shared expert, and
    the pairs each held expert computed (of the gathered tokens); with
    ``count_all`` the pairs the routing sent each of the ``E`` experts,
    held here or not, (E,) int32.
    """
    cd = x.dtype
    experts = jax.tree_util.tree_map(lambda a: a.astype(cd),
                                     params["experts"])
    held = experts["w_down"].shape[0]
    n_experts = params["router"].shape[1]
    ep = 1 if axis_name is None else lax.axis_size(axis_name)
    if ep * held > n_experts:
        raise HorovodTpuError(
            f"{ep} ranks x {held} experts held exceed the router's "
            f"{n_experts}")
    tokens = x
    if ep > 1:
        tokens = lax.all_gather(x, axis_name, axis=0, tiled=True)
        first = first + lax.axis_index(axis_name) * held
    ids, weights = route(tokens, params["router"], params.get("bias"),
                         top_k, scale)
    with jax.named_scope("hvd_moe_experts"):
        out = expert_share(tokens, experts, ids, weights, first, n_experts)
    if ep > 1:
        out = lax.psum_scatter(out, axis_name, scatter_dimension=0,
                               tiled=True)
    if "shared" in params:
        shared = swiglu if "w_gate" in params["shared"] else relu2
        with jax.named_scope("hvd_moe_shared"):
            out = out + shared(x, jax.tree_util.tree_map(
                lambda a: a.astype(cd), params["shared"]))
    if count_all:
        return out, lax.stop_gradient(pairs_per_expert(ids, 0, n_experts))
    return out, lax.stop_gradient(pairs_per_expert(ids, first, held))


def moe_reference(x, params, *, top_k: int, scale: float, first: int = 0,
                  held: int | None = None):
    """Plain golden model for tests: float32, a Python loop over the
    experts ``first .. first + held`` (all of ``params["experts"]``
    where ``held`` is not given), every expert on every token under a
    mask.  The shared expert is added where ``params`` has one.  The
    experts' form and the router's kind are read from the weights, as
    the layer reads them."""

    def expert(w):
        if "w_gate" in w:
            return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]
        return jnp.square(jax.nn.relu(x @ w["w_up"])) @ w["w_down"]

    if "bias" in params:
        scores = jax.nn.sigmoid(x @ params["router"])
        chosen = scores + params["bias"]
    else:
        chosen = scores = jax.nn.softmax(x @ params["router"], axis=-1)
    ids = jnp.argsort(-chosen, axis=-1, stable=True)[:, :top_k]
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    weights = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    experts = params["experts"]
    held = experts["w_down"].shape[0] if held is None else held
    out = jnp.zeros_like(x)
    for e in range(held):
        gate = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        out = out + gate[:, None] * expert(
            {name: a[e] for name, a in experts.items()})
    if "shared" in params:
        out = out + expert(params["shared"])
    return out
