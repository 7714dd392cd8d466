"""The kind of a ``layer_pattern`` that a sparse-attention expert
configuration (``KeyeVL2``) is made of: ``I``, grouped-query attention
over the keys an indexer selects for each query — the exact top-k with
its ties and short rows, QK-norm, rotary positions of three components in
sections — beside ``E`` behind a softmax router with no bias and no
shared expert.  The configuration's checks, the stacks, the mesh axes the
kind refuses, what each statement of the configuration changes in the
loss, the shares of the experts adding up, one selection a step, and the
``hvd_dsa_select`` records.  (The stack against the benchmark's plain
reference: tests/benchmark_suite/test_benchmark_dsa_moe.py.)"""

import dataclasses

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import blocks
from horovod_tpu.models.transformer import (TransformerConfig, init_params,
                                            loss_and_routing, make_train_step,
                                            param_specs, record_selection)
from horovod_tpu.ops.pallas_attention import unpack_keep
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.mesh import make_mesh

CFG = TransformerConfig(
    vocab=64, d_model=32, n_heads=4, head_dim=8, max_seq=64, dtype="float32",
    tied_head=False, layer_pattern="IEIE", n_kv_heads=2, index_heads=2,
    index_head_dim=8, index_topk=16, rope_sections=(1, 1, 2),
    rope_theta=1e7, n_experts=16, experts_held=4, experts_per_token=4,
    d_expert=16, router="softmax", rescale_depth=4)
SEQ = 64


def _data(mesh, cfg, batch=2, seq=SEQ, seed=0):
    rng = np.random.RandomState(seed)
    sh = NamedSharding(mesh, P("dp", "sp"))
    return tuple(jax.device_put(jnp.asarray(
        rng.randint(0, cfg.vocab, (batch, seq)), jnp.int32), sh)
        for _ in range(2))


def _run(cfg, params=None, grad=False):
    """``(loss, reports[, gradients])`` of one batch on one device."""
    from jax import shard_map

    mesh = make_mesh(dp=1, pp=1, tp=1, sp=1, devices=jax.devices()[:1])
    if params is None:
        params = init_params(np.random.RandomState(0), cfg)
    tokens, targets = _data(mesh, cfg)
    specs = param_specs(cfg)
    reports = {"loads": P(), "selections": P()}

    def fn(p, a, b):
        if grad:
            (loss, aux), g = jax.value_and_grad(
                loss_and_routing, has_aux=True)(p, a, b, cfg)
            return loss, aux, g
        return loss_and_routing(p, a, b, cfg)

    out = jax.jit(shard_map(
        fn, mesh=mesh, check_vma=False,
        in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
        out_specs=(P(), reports, specs) if grad else (P(), reports)))(
            params, tokens, targets)
    return (float(out[0]), *out[1:])


# ---------------------------------------------------------------------------
# Configuration, stacks, refusals
# ---------------------------------------------------------------------------


def test_the_pattern_decides_the_stacks():
    """``I`` has a stack of its own with the indexer's three matrices;
    a softmax router has no selection bias and this layer no shared
    expert; the specs name what the parameters hold."""
    params = init_params(np.random.RandomState(0), CFG)
    assert set(params) == {"embed", "ln_f", "head", "dsa", "moe"}
    assert set(params["dsa"]) == {"ln", "wq", "wk", "wv", "wo", "q_norm",
                                  "k_norm", "wq_idx", "wk_idx", "ww_idx"}
    assert params["dsa"]["wq_idx"].shape == (2, 32, 2 * 8)
    assert params["dsa"]["wk_idx"].shape == (2, 32, 8)
    assert params["dsa"]["ww_idx"].shape == (2, 32, 2)
    assert params["dsa"]["wk"].shape == (2, 32, 2 * 8)
    assert set(params["moe"]) == {"ln", "router", "experts"}
    specs = param_specs(CFG)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, params)) \
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda s: 0, specs, is_leaf=lambda s: isinstance(s, P)))
    assert blocks.STACK_OF["I"] == "dsa" and "I" in blocks.ATTENTION_KINDS
    # the sigmoid router keeps its bias, drawn where it was
    sigmoid = init_params(np.random.RandomState(0),
                          dataclasses.replace(CFG, router="sigmoid"))
    assert "bias" in sigmoid["moe"]
    np.testing.assert_array_equal(sigmoid["moe"]["router"],
                                  params["moe"]["router"])


@pytest.mark.parametrize("change,message", [
    (dict(index_topk=0), "'I' layers need"),
    (dict(index_heads=0), "'I' layers need"),
    (dict(head_dim=7, rope_sections=()), "even head_dim"),
    (dict(rope_sections=(1, 1, 1)), "add up to head_dim / 2 = 4"),
    (dict(rope_sections=(2, 2)), "three counts"),
    (dict(router="top1"), "router must be"),
    (dict(n_heads=3), "no multiple of n_kv_heads"),
])
def test_configuration_checks(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **change)


@pytest.mark.parametrize("axes,message", [
    (dict(tp=2), "selection under tp > 1"),
    (dict(sp=2), "selection under sp > 1"),
    (dict(pp=2), "a layer pattern under pp > 1"),
])
def test_the_kind_refuses_the_axes_it_is_not_built_for(axes, message):
    """``NotImplementedError`` with the reason, at trace time."""
    mesh = make_mesh(**{"dp": 1, "pp": 1, "tp": 1, "sp": 1, **axes},
                     devices=jax.devices()[:2])
    step = make_train_step(CFG, mesh, optax.sgd(0.1))
    params = init_params(np.random.RandomState(0), CFG)
    tokens, targets = _data(mesh, CFG)
    with pytest.raises(NotImplementedError, match=message) as raised:
        step(params, optax.sgd(0.1).init(params), tokens, targets)
    assert "is not supported: " in str(raised.value)


# ---------------------------------------------------------------------------
# The selection
# ---------------------------------------------------------------------------


def _scores(lp, h, heads, size):
    """The indexer's scores in numpy float64 from float32 products."""
    h = np.asarray(h, np.float64)
    q = (h @ np.asarray(lp["wq_idx"], np.float64)).reshape(
        h.shape[0], h.shape[1], heads, size)
    k = h @ np.asarray(lp["wk_idx"], np.float64)
    w = h @ np.asarray(lp["ww_idx"], np.float64) / np.sqrt(heads)
    products = np.maximum(np.einsum("bqjd,bkd->bqjk", q, k), 0.0)
    return np.einsum("bqj,bqjk->bqk", w, products) / np.sqrt(size)


def _stable_top(scores, topk):
    lq, lk = scores.shape[1:]
    causal = np.tril(np.ones((lq, lk), bool))
    order = np.argsort(-np.where(causal, scores, -np.inf), axis=-1,
                       kind="stable")[..., :topk]
    mask = np.zeros(scores.shape, bool)
    np.put_along_axis(mask, order, True, axis=-1)
    return mask & causal


def _layer(seed=0):
    rng = np.random.RandomState(seed)
    lp = {"wq_idx": rng.randn(32, 16), "wk_idx": rng.randn(32, 8),
          "ww_idx": rng.randn(32, 2)}
    return ({name: jnp.asarray(a, jnp.float32) for name, a in lp.items()},
            jnp.asarray(rng.randn(2, SEQ, 32), jnp.float32))


def test_the_selection_is_the_exact_top_k():
    """Every row keeps ``min(t + 1, 16)`` keys, none later than itself,
    and they are the keys a stable sort of the scores puts first."""
    lp, h = _layer()
    words = jax.jit(lambda lp, h: blocks.select_keys(CFG, lp, h))(lp, h)
    assert words.shape == (2, SEQ, 128) and words.dtype == jnp.int32
    mask = np.asarray(unpack_keep(words, SEQ))
    assert (mask.sum(-1) == np.minimum(np.arange(SEQ) + 1, 16)).all()
    assert not np.triu(mask, 1).any()
    want = _stable_top(_scores(lp, h, 2, 8), 16)
    assert (mask == want).all()


def test_a_tie_goes_to_the_lower_key():
    """Inputs of small whole numbers and an indexer of 4 heads of 16,
    whose two scalings are powers of two: every score is a whole number
    of eighths in any precision, rows tie at their threshold many times
    over (zeros of either sign among them), and of the keys level with
    it the first are kept."""
    cfg = dataclasses.replace(CFG, index_heads=4, index_head_dim=16)
    rng = np.random.RandomState(3)
    lp = {"wq_idx": rng.randint(-1, 2, (32, 64)),
          "wk_idx": rng.randint(-1, 2, (32, 16)),
          "ww_idx": rng.randint(-1, 2, (32, 4))}
    lp = {name: jnp.asarray(a, jnp.float32) for name, a in lp.items()}
    h = jnp.asarray(rng.randint(-1, 2, (2, SEQ, 32)), jnp.float32)
    scores = _scores(lp, h, 4, 16)
    assert (scores * 8 == np.round(scores * 8)).all()
    # ties at the threshold in a dozen rows or more
    level = [np.sum(np.sort(row[:t + 1])[::-1][15] == row[:t + 1]) > 1
             for batch in scores for t, row in enumerate(batch) if t >= 16]
    assert np.sum(level) >= 12
    words = jax.jit(lambda lp, h: blocks.select_keys(cfg, lp, h))(lp, h)
    mask = np.asarray(unpack_keep(words, SEQ))
    assert (mask == _stable_top(scores, 16)).all()
    assert (mask.sum(-1) == np.minimum(np.arange(SEQ) + 1, 16)).all()


def test_the_kth_largest_is_exact():
    """Over the whole range of uint32, duplicates and all."""
    rng = np.random.RandomState(4)
    keys = rng.randint(0, 2 ** 32, (3, 5, 200), dtype=np.uint64).astype(
        np.uint32)
    keys[0, 0, :50] = keys[0, 0, 0]
    keys[1, 1] = 0
    keys[2, 2] = 2 ** 32 - 1
    for k in (1, 7, 200):
        got = jax.jit(lambda a: blocks._kth_largest(a, k))(jnp.asarray(keys))
        want = np.sort(keys, axis=-1)[..., ::-1][..., k - 1]
        assert (np.asarray(got) == want).all(), k


def test_the_selection_passes_no_gradient_and_is_made_once_a_step():
    """The indexer's three matrices take a zero gradient; with every
    layer recomputed the differentiated step holds the indexer's scores
    once a layer (the replay reads the kept selection), and the loss and
    gradients are those of the step that recomputes nothing."""
    loss, reports, grads = _run(CFG, grad=True)
    for name in ("wq_idx", "wk_idx", "ww_idx"):
        assert float(jnp.abs(grads["dsa"][name]).max()) == 0.0, name
    assert float(jnp.abs(grads["dsa"]["wq"]).max()) > 0
    kept = np.asarray(unpack_keep(reports["selections"], SEQ))
    assert kept.shape == (2, 2, SEQ, SEQ)
    assert (kept.sum(-1) == np.minimum(np.arange(SEQ) + 1, 16)).all()
    remat = dataclasses.replace(CFG, remat=True)
    loss2, reports2, grads2 = _run(remat, grad=True)
    assert loss2 == pytest.approx(loss, rel=1e-6)
    assert (np.asarray(reports2["selections"])
            == np.asarray(reports["selections"])).all()
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(grads2)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)
    # the compiled step: one scores product a layer, not two
    mesh = make_mesh(dp=1, pp=1, tp=1, sp=1, devices=jax.devices()[:1])
    params = init_params(np.random.RandomState(0), remat)
    opt = optax.sgd(0.1)
    text = make_train_step(remat, mesh, opt).lower(
        params, opt.init(params), *_data(mesh, remat)).compile().as_text()
    products = [line for line in text.splitlines()
                if "bqjd,bkd->bqjk" in line and " dot(" in line]
    assert len(products) == 2, len(products)


# ---------------------------------------------------------------------------
# What each statement of the configuration computes
# ---------------------------------------------------------------------------


def test_each_statement_of_the_configuration_is_computed():
    """One at a time against the loss of the whole: how many keys a
    query keeps, the indexer's weights, the two per-head norms' gains,
    the sections of the rotary positions' frequencies, the router's
    kind; and keeping at least the sequence is causal attention."""
    whole = _run(CFG)[0]
    params = init_params(np.random.RandomState(0), CFG)

    def with_(stack, name, change):
        return {**params, stack: {**params[stack],
                                  name: change(params[stack][name])}}

    for cfg, changed in (
            (dataclasses.replace(CFG, index_topk=8), None),
            (CFG, with_("dsa", "ww_idx", lambda a: -a)),
            (CFG, with_("dsa", "q_norm", lambda a: 2 * a)),
            (CFG, with_("dsa", "k_norm", lambda a: a.at[:, 0].set(3.0))),
            (dataclasses.replace(CFG, rope_theta=1e4), None),
            (dataclasses.replace(CFG, experts_per_token=2), None)):
        assert abs(_run(cfg, changed)[0] - whole) > 1e-5 * whole
    # text's three components are equal: the sections move nothing
    assert _run(dataclasses.replace(CFG, rope_sections=(2, 1, 1)))[0] \
        == pytest.approx(whole, rel=1e-6)
    # every key kept: the selection binds nothing, whatever the indexer
    every = dataclasses.replace(CFG, index_topk=SEQ)
    assert _run(every)[0] == pytest.approx(
        _run(every, with_("dsa", "ww_idx", lambda a: -a))[0], rel=1e-6)
    assert abs(_run(every)[0] - whole) > 1e-5 * whole


def test_rotary_over_three_components_in_sections():
    """Positions of three unequal components against the rule written
    out pair by pair: of the d / 2 frequency pairs the first
    ``sections[0]`` turn by the first component, the next by the second,
    the rest by the third; equal components are the plain rotary."""
    rng = np.random.RandomState(5)
    x = rng.randn(2, 6, 3, 16).astype(np.float32)
    positions = np.stack([np.arange(6), 10 + 2 * np.arange(6),
                          np.array([3, 3, 4, 4, 5, 9])])
    sections, theta = (2, 3, 3), 1e7
    got = np.asarray(blocks.rotary(jnp.asarray(x), jnp.asarray(positions),
                                   theta, halves=True, sections=sections))
    want = np.empty_like(x)
    component = [0, 0, 1, 1, 1, 2, 2, 2]
    for t in range(6):
        for i in range(8):
            angle = positions[component[i], t] * theta ** (-2 * i / 16)
            a, b = x[:, t, :, i], x[:, t, :, i + 8]
            want[:, t, :, i] = a * np.cos(angle) - b * np.sin(angle)
            want[:, t, :, i + 8] = a * np.sin(angle) + b * np.cos(angle)
    np.testing.assert_allclose(got, want, atol=1e-5)
    same = jnp.broadcast_to(jnp.arange(6), (3, 6))
    np.testing.assert_allclose(
        blocks.rotary(jnp.asarray(x), same, theta, halves=True,
                      sections=sections),
        blocks.rotary(jnp.asarray(x), jnp.arange(6), theta, halves=True),
        atol=1e-6)
    # and in the interleaved layout
    np.testing.assert_allclose(
        blocks.rotary(jnp.asarray(x), same, theta, sections=sections),
        blocks.rotary(jnp.asarray(x), jnp.arange(6), theta), atol=1e-6)


def test_the_stack_hands_three_components_down(monkeypatch):
    seen = []
    whole = blocks.rotary

    def spy(x, positions, *args, **kwargs):
        seen.append((positions.shape, kwargs.get("sections")))
        return whole(x, positions, *args, **kwargs)

    monkeypatch.setattr(blocks, "rotary", spy)
    jax.clear_caches()
    _run(CFG)
    assert seen and set(seen) == {((3, SEQ), (1, 1, 2))}
    seen.clear()
    _run(dataclasses.replace(CFG, rope_sections=()))
    assert set(seen) == {((SEQ,), ())}
    jax.clear_caches()


# ---------------------------------------------------------------------------
# The softmax router
# ---------------------------------------------------------------------------


def _moe_params(n_experts=32, held=32, d=16, f=8, seed=6):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32) * 0.3
    return {"router": mk(d, n_experts),
            "experts": {"w_gate": mk(held, d, f), "w_up": mk(held, d, f),
                        "w_down": mk(held, f, d)}}


def test_softmax_routing_against_the_plain_layer():
    """No ``bias`` in the weights: a softmax over all experts, the top
    ``k`` probabilities renormalised to 1; the layer against
    ``moe_reference``, and the weights by hand."""
    params = _moe_params()
    x = jnp.asarray(np.random.RandomState(7).randn(48, 16), jnp.float32)
    ids, weights = moe.route(x, params["router"], None, 4, 1.0)
    probs = jax.nn.softmax(x @ params["router"], axis=-1)
    want = np.argsort(-np.asarray(probs), axis=-1, kind="stable")[:, :4]
    assert (np.asarray(ids) == want).all()
    picked = np.take_along_axis(np.asarray(probs), want, axis=-1)
    np.testing.assert_allclose(weights, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    out, pairs = jax.jit(lambda x, p: moe.moe_layer(x, p, top_k=4, scale=1.0))(
        x, params)
    np.testing.assert_allclose(
        out, moe.moe_reference(x, params, top_k=4, scale=1.0), atol=2e-5)
    assert int(pairs.sum()) == 48 * 4
    # the sigmoid kind still reads its bias
    biased = {**params, "bias": jnp.zeros(32)}
    other, _ = moe.moe_layer(x, biased, top_k=4, scale=1.0)
    np.testing.assert_allclose(
        other, moe.moe_reference(x, biased, top_k=4, scale=1.0), atol=2e-5)
    assert float(jnp.abs(other - out).max()) > 1e-3


def test_the_shares_add_up():
    """The parts that all 16 shares of the experts give — each chip its
    2 of 32, routing over all 32 — summed, are the uncut reference's
    expert layer: there is no shared expert to count once."""
    params = _moe_params()
    x = jnp.asarray(np.random.RandomState(8).randn(40, 16), jnp.float32)
    whole = moe.moe_reference(x, params, top_k=4, scale=1.0)
    total, pairs = jnp.zeros_like(whole), 0
    for share in range(16):
        held = {**params, "experts": jax.tree_util.tree_map(
            lambda a: a[2 * share:2 * share + 2], params["experts"])}
        out, sent = moe.moe_layer(x, held, top_k=4, scale=1.0,
                                  first=2 * share)
        total, pairs = total + out, pairs + int(sent.sum())
    np.testing.assert_allclose(total, whole, atol=3e-5)
    assert pairs == 40 * 4


# ---------------------------------------------------------------------------
# Training, records
# ---------------------------------------------------------------------------


def test_trains_over_dp_with_the_experts_shared_out():
    """Two chips, each holding 4 of the 16 experts and its own
    sequences: the loss falls."""
    mesh = make_mesh(dp=2, pp=1, tp=1, sp=1, devices=jax.devices()[:2])
    from horovod_tpu.models.transformer import shard_params

    cfg = dataclasses.replace(CFG, remat=True)
    opt = optax.adam(1e-2)
    params = shard_params(init_params(np.random.RandomState(0), cfg, ep=2),
                          cfg, mesh)
    state = opt.init(params)
    step = make_train_step(cfg, mesh, opt)
    tokens, targets = _data(mesh, cfg, batch=4)
    losses = []
    for _ in range(8):
        params, state, loss = step(params, state, tokens, targets)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.1


def test_selection_records():
    """One ``hvd_dsa_select`` record a layer from the packed words: the
    pairs kept and a causal call's, the operand and its bytes, the
    tiles."""
    from horovod_tpu.runtime import flight

    _, reports = _run(CFG)
    before = len([e for e in flight.recorder().snapshot()
                  if e["kind"] == "hvd_dsa_select"])
    records = record_selection(CFG, reports["selections"])
    assert [r["layer"] for r in records] == [0, 1]
    for record in records:
        assert record["seq"] == SEQ and record["topk"] == 16
        # two sequences: 16 x 17 / 2 + 48 x 16 pairs each
        assert record["kept_pairs"] == 2 * 904
        assert record["causal_pairs"] == 2 * SEQ * (SEQ + 1) // 2
        assert record["operand"] == "packed_mask"
        assert record["operand_bytes"] == 2 * SEQ * 128 * 4
        assert record["impl"] == "xla"
    assert len([e for e in flight.recorder().snapshot()
                if e["kind"] == "hvd_dsa_select"]) == before + 2


def test_selection_record_at_the_benchmarks_sizes(monkeypatch):
    """16,384 tokens, 32 heads of 128 in bf16 on a TPU: the kernels in
    1024 x 1024 tiles, 136 of 256 tile pairs live, 33.5 MB of packed
    bits a layer, 0.2344 of a causal call's pairs kept."""
    cfg = TransformerConfig(
        vocab=64, d_model=2048, n_heads=32, head_dim=128, n_kv_heads=4,
        max_seq=16384, tied_head=False, layer_pattern="I", index_heads=16,
        index_head_dim=64, index_topk=2048, rope_sections=(16, 24, 24))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows = np.minimum(np.arange(16384) + 1, 2048)
    # a selection of the right counts, its bits anywhere: word w of row
    # t holds min(32, what is left) bits
    counts = np.clip(rows[:, None] - 32 * np.arange(512)[None, :], 0, 32)
    words = (np.left_shift(np.uint64(1), counts.astype(np.uint64))
             - 1).astype(np.uint32).view(np.int32)[None, None]
    record, = record_selection(cfg, words)
    assert record["kept_pairs"] == 31_458_304
    assert record["causal_pairs"] == 134_225_920
    assert record["kept_pairs"] / record["causal_pairs"] \
        == pytest.approx(0.2344, abs=1e-4)
    assert record["operand_bytes"] == 16384 * 512 * 4 == 33_554_432
    assert (record["impl"], record["block_q"], record["block_k"],
            record["tiles"], record["live_tiles"]) \
        == ("pallas", 1024, 1024, 256, 136)


def test_transformer_lm_example_takes_the_indexed_kind():
    """The user's entry point outside the harness: ``--layer-pattern
    IEIE`` with ``--experts`` on a dp mesh."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, HOROVOD_PLATFORM="cpu", HOROVOD_SIZE="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "examples", "transformer_lm.py"),
         "--dp", "2", "--steps", "2", "--d-model", "32", "--seq", "16",
         "--batch", "4", "--layer-pattern", "IEIE", "--experts", "2"],
        env=env, cwd=repo, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-3000:])
    assert "loss" in proc.stdout.lower()


def test_docs_name_the_scopes_the_kernels_and_the_record():
    """``docs/perf.md`` says where each new name is set."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "docs", "perf.md"), encoding="utf-8") as f:
        text = f.read()
    for name in ("hvd_dsa", "hvd_dsa_index", "hvd_flash_fwd_sel",
                 "hvd_flash_bwd_dq_sel", "hvd_flash_bwd_dkv_sel",
                 "hvd_dsa_select", "hvd_dsa_keep", "blocks.indexed_gqa"):
        assert f"`{name}`" in text, name
