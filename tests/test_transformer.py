"""Flagship transformer: composed dp*tp*sp (+pp, +ep) training on the
8-device mesh.  This is the capability the reference never had (DP
only) exercised end to end: loss decreases under every mesh layout and
the layouts agree with each other.
"""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import (TransformerConfig, init_params,
                                            make_train_step, shard_params)
from horovod_tpu.parallel.mesh import make_mesh

CFG = TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                        n_layers=4, d_ff=64, max_seq=64)


def _data(mesh, batch=8, seq=32, seed=0):
    rng = np.random.RandomState(seed)
    tokens = jnp.asarray(rng.randint(0, CFG.vocab, (batch, seq)),
                         dtype=jnp.int32)
    targets = jnp.asarray(rng.randint(0, CFG.vocab, (batch, seq)),
                          dtype=jnp.int32)
    sh = NamedSharding(mesh, P("dp", "sp"))
    return jax.device_put(tokens, sh), jax.device_put(targets, sh)


def _train(cfg, mesh, steps=8, seed=0):
    params = init_params(np.random.RandomState(seed), cfg,
                         ep=mesh.shape["dp"])
    params = shard_params(params, cfg, mesh)
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    step = make_train_step(cfg, mesh, opt)
    tokens, targets = _data(mesh)
    losses = []
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    return losses


@pytest.mark.slow  # tier-1 runtime trim: heaviest cold-compile/subprocess tests;
# ci.sh's full (unfiltered) suite still runs them
def test_dp_tp_sp_training_loss_decreases():
    mesh = make_mesh(dp=2, pp=1, tp=2, sp=2)
    losses = _train(CFG, mesh)
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0] - 0.1, losses


@pytest.mark.slow  # tier-1 runtime trim: heaviest cold-compile/subprocess tests;
# ci.sh's full (unfiltered) suite still runs them
def test_steps_per_dispatch_matches_single_step():
    """k chained steps in one program (steps_per_dispatch, the
    dispatch-amortizing bench mode) must walk the same trajectory as k
    separate dispatches."""
    mesh = make_mesh(dp=2, pp=1, tp=2, sp=2)

    def run(spd, calls):
        params = init_params(np.random.RandomState(0), cfg=CFG,
                             ep=mesh.shape["dp"])
        params = shard_params(params, CFG, mesh)
        opt = optax.sgd(1e-2)  # stateless-ish, deterministic
        opt_state = opt.init(params)
        step = make_train_step(CFG, mesh, opt, steps_per_dispatch=spd)
        tokens, targets = _data(mesh)
        for _ in range(calls):
            params, opt_state, loss = step(params, opt_state, tokens,
                                           targets)
        return float(loss), params

    loss_a, params_a = run(spd=1, calls=4)
    loss_b, params_b = run(spd=4, calls=1)
    assert np.isclose(loss_a, loss_b, rtol=1e-4), (loss_a, loss_b)
    flat_a = jax.tree_util.tree_leaves(params_a)
    flat_b = jax.tree_util.tree_leaves(params_b)
    for a, b in zip(flat_a, flat_b):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.slow  # ~15 s pipeline training compile (ci.sh full suite)
def test_pipeline_parallel_training():
    mesh = make_mesh(dp=1, pp=2, tp=2, sp=2)
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=4, d_ff=64, max_seq=64,
                            pp_microbatches=2)
    losses = _train(cfg, mesh)
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0] - 0.1, losses


@pytest.mark.slow  # tier-1 runtime trim: heaviest cold-compile/subprocess tests;
# ci.sh's full (unfiltered) suite still runs them
def test_pipeline_interleaved_matches_gpipe():
    """Interleaved schedule (pp_virtual=2) is the same math as GPipe —
    identical loss trajectory on the same model/data — with a V-fold
    smaller bubble (schedule length asserted in test_pipeline_moe).
    n_layers=8 puts TWO layers in every chunk (per=2), covering the
    within-chunk fori_loop and the layer storage permutation at
    per > 1."""
    mesh = make_mesh(dp=1, pp=2, tp=2, sp=2)
    base = dict(vocab=64, d_model=32, n_heads=4, head_dim=8,
                n_layers=8, d_ff=64, max_seq=64, pp_microbatches=2)
    l_gpipe = _train(TransformerConfig(**base), mesh, steps=4)
    l_inter = _train(TransformerConfig(**base, pp_schedule="interleaved",
                                       pp_virtual=2), mesh, steps=4)
    assert np.isfinite(l_inter).all(), l_inter
    assert l_inter[-1] < l_inter[0] - 0.1, l_inter
    np.testing.assert_allclose(l_inter, l_gpipe, rtol=2e-2)


@pytest.mark.slow  # tier-1 runtime trim: heaviest cold-compile/subprocess tests;
# ci.sh's full (unfiltered) suite still runs them
def test_moe_expert_parallel_training():
    """The GPT-2 attention block with SwiGLU and expert layers after a
    leading dense one, 8 experts over dp = ep = 4."""
    mesh = make_mesh(dp=4, pp=1, tp=1, sp=2)
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=4, d_ff=64, max_seq=64, mlp="swiglu",
                            n_experts=8, experts_held=2,
                            experts_per_token=2, d_expert=16,
                            shared_experts=1, n_dense_layers=1)
    losses = _train(cfg, mesh)
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0] - 0.1, losses


@pytest.mark.slow  # tier-1 runtime trim: heaviest cold-compile/subprocess tests;
# ci.sh's full (unfiltered) suite still runs them
def test_layouts_agree():
    """Same model/data, different mesh layouts -> same loss trajectory
    (SPMD correctness of the tp/sp decomposition)."""
    l_dp = _train(CFG, make_mesh(dp=8, pp=1, tp=1, sp=1), steps=3)
    l_tpsp = _train(CFG, make_mesh(dp=2, pp=1, tp=2, sp=2), steps=3)
    np.testing.assert_allclose(l_dp, l_tpsp, rtol=2e-2)


def _train_sgd(cfg, mesh, steps):
    """Scale-sensitive trainer: plain SGD exposes any world-size factor
    in the gradients that adam's normalization would hide."""
    params = init_params(np.random.RandomState(0), cfg,
                         ep=mesh.shape["dp"])
    params = shard_params(params, cfg, mesh)
    opt = optax.sgd(0.5)
    opt_state = opt.init(params)
    step = make_train_step(cfg, mesh, opt)
    tokens, targets = _data(mesh)
    losses = []
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    return losses


@pytest.mark.slow  # ~15 s compile; parity also covered per-op (ci.sh full)
def test_gradient_scale_matches_single_device():
    """Distributed gradients must equal the single-device global-mean
    gradient exactly — no dp/sp/tp world-size inflation (the Megatron
    f/g transpose discipline + psum-free local loss)."""
    import jax

    golden = _train_sgd(CFG, make_mesh(dp=1, pp=1, tp=1, sp=1,
                                       devices=jax.devices()[:1]), 3)
    distributed = _train_sgd(CFG, make_mesh(dp=2, pp=1, tp=2, sp=2), 3)
    # rtol bounds bf16 reduction-order noise while still failing on any
    # world-size factor (which would be 2x-8x)
    np.testing.assert_allclose(distributed, golden, rtol=1e-2)


def test_bad_pp_schedule_config_raises():
    base = dict(vocab=64, d_model=32, n_heads=4, head_dim=8,
                n_layers=4, d_ff=64, max_seq=64)
    with pytest.raises(ValueError):
        TransformerConfig(**base, pp_schedule="1f1b")
    with pytest.raises(ValueError):
        TransformerConfig(**base, pp_virtual=2)  # gpipe + virtual>1


def test_moe_under_pp_raises():
    """Expert layers (and the other kinds of ``models/blocks.py``) have
    no pipeline path yet: refused, not run wrong."""
    mesh = make_mesh(dp=2, pp=2, tp=1, sp=2)
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=4, d_ff=64, max_seq=64, mlp="swiglu",
                            n_experts=4, experts_held=2,
                            experts_per_token=2, d_expert=16,
                            n_dense_layers=1)
    with pytest.raises(NotImplementedError, match="pipeline"):
        _train(cfg, mesh, steps=1)


def test_bad_kind_config_raises():
    base = dict(vocab=64, d_model=32, n_heads=4, head_dim=8,
                n_layers=4, d_ff=64, max_seq=64)
    with pytest.raises(ValueError):
        TransformerConfig(**base, attention="gqa")
    with pytest.raises(ValueError):
        TransformerConfig(**base, mlp="relu")
    with pytest.raises(ValueError):       # expert layers are SwiGLU
        TransformerConfig(**base, n_experts=4)
    with pytest.raises(ValueError):       # the MTP block is an expert block
        TransformerConfig(**base, mtp_depth=1)


# A small model of every new kind: latent attention with a rotary key
# shared by the heads, a leading dense SwiGLU layer, expert layers with a
# shared expert, an untied head, the MTP module, blocks recomputed.
LATENT = TransformerConfig(
    vocab=64, d_model=32, n_heads=4, n_layers=3, d_ff=48, max_seq=64,
    attention="mla", mlp="swiglu", tied_head=False, remat=True,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4,
    v_head_dim=8, n_experts=8, experts_held=8, experts_per_token=3,
    d_expert=16, shared_experts=1, routed_scale=2.5, n_dense_layers=1,
    mtp_depth=1, dtype="float32")


def _train_latent(mesh, steps=3):
    import dataclasses

    ep = mesh.shape["dp"]
    cfg = dataclasses.replace(LATENT, experts_held=LATENT.n_experts // ep)
    params = shard_params(init_params(np.random.RandomState(0), cfg, ep=ep),
                          cfg, mesh)
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    step = make_train_step(cfg, mesh, opt)
    ids = np.random.RandomState(1).randint(0, cfg.vocab, (8, 33))
    sh = NamedSharding(mesh, P("dp", "sp"))
    tokens = jax.device_put(jnp.asarray(ids[:, :-1], jnp.int32), sh)
    targets = jax.device_put(jnp.asarray(ids[:, 1:], jnp.int32), sh)
    losses = []
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    return losses


def test_latent_expert_model_layouts_agree():
    """The same model and data on one device and over dp (= ep: the
    experts' exchange) x tp (heads and SwiGLU split, the shared rotary
    key's gradient summed) x sp (ring attention at 12 / 8 head sizes,
    global rotary positions, the MTP's token from the next chunk): the
    same losses, in float32 to rounding.  So the share test is also the
    test of ep > 1 inside the model."""
    one = _train_latent(make_mesh(dp=1, pp=1, tp=1, sp=1,
                                  devices=jax.devices()[:1]))
    assert one[-1] < one[0] - 0.1, one
    np.testing.assert_allclose(
        _train_latent(make_mesh(dp=2, pp=1, tp=2, sp=2)), one, rtol=2e-5)


def test_gpt2_shape_builds_and_traces_nothing_of_the_new_kinds(monkeypatch):
    """A GPT-2-shaped configuration has the parameter tree it always
    had — no expert, rotary, latent or MTP parameter — and tracing its
    step calls nothing of ``models/blocks.py`` or ``parallel/moe.py``:
    its set-up path does not pay for the kinds it does not use."""
    from horovod_tpu.models import blocks
    from horovod_tpu.parallel import moe

    assert CFG.gpt2_block
    params = init_params(np.random.RandomState(0), CFG)
    assert set(params) == {"embed", "pos", "ln_f", "layers"}
    assert set(params["layers"]) == {"wqkv", "wo", "w1", "w2", "ln1", "ln2"}

    def refuse(*_, **__):
        raise AssertionError("a GPT-2-shaped step traced a new kind")

    for module in (blocks, moe):
        for name, value in vars(module).items():
            if callable(value) and getattr(value, "__module__",
                                           None) == module.__name__:
                monkeypatch.setattr(module, name, refuse)
    mesh = make_mesh(dp=2, pp=1, tp=1, sp=1, devices=jax.devices()[:2])
    placed = shard_params(params, CFG, mesh)
    opt = optax.adam(1e-2)
    make_train_step(CFG, mesh, opt).lower(placed, opt.init(placed),
                                          *_data(mesh))


def test_the_compiled_step_names_its_parts():
    """docs/perf.md: the scopes of the flagship step reach the compiled
    program's ``op_name``s — attention and the loss head in both passes
    (JAX writes ``jvp(..)`` and ``transpose(jvp(..))`` around them), the
    gradient reduction and the optimizer bare, after the gradients."""
    import re

    mesh = make_mesh(dp=2, pp=1, tp=1, sp=1, devices=jax.devices()[:2])
    params = shard_params(init_params(np.random.RandomState(0), CFG,
                                      ep=2), CFG, mesh)
    opt = optax.adam(1e-2)
    text = make_train_step(CFG, mesh, opt).lower(
        params, opt.init(params), *_data(mesh)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))

    def shown(*parts):
        return any(all(p in n for p in parts) for n in names)

    for scope in ("hvd_attn", "hvd_loss_head"):
        assert shown("jvp(", scope) and shown("transpose(jvp(", scope), scope
    assert shown("hvd_grad_reduce/psum")
    assert shown("hvd_optimizer/")
    for n in names:      # neither is inside the differentiated function
        if "hvd_grad_reduce" in n or "hvd_optimizer" in n:
            assert "jvp(" not in n, n
    # the head's own rule: the products and the row reductions of both
    # passes, and no gather out of the logits (tests/test_loss_head.py)
    assert shown("jvp(hvd_loss_head)/reduce_max")
    assert shown("transpose(jvp(hvd_loss_head))", "dot_general")
    assert not shown("hvd_loss_head", "gather")
    assert not shown("hvd_attn", "hvd_loss_head")


def test_the_new_kinds_name_their_parts_and_record_their_routing(monkeypatch):
    """docs/perf.md: ``hvd_mla`` (with ``hvd_attn`` inside), ``hvd_moe``
    (with ``hvd_moe_route`` / ``_experts`` / ``_shared`` inside) and
    ``hvd_mtp`` reach the compiled step's ``op_name``s in both passes,
    the recomputed forward among the backward's; ``record_routing``
    writes one flight record an expert layer, the MTP module's last,
    whose pairs add up to tokens x k with the whole router held, and
    which says how many rows a chunk had and how many chunks the pairs
    took: fewer than one chunk's rows held no pair."""
    import re

    from jax import shard_map

    from horovod_tpu.models.transformer import (loss_and_routing,
                                                param_specs, record_routing)
    from horovod_tpu.runtime import flight

    mesh = make_mesh(dp=1, pp=1, tp=1, sp=1, devices=jax.devices()[:1])
    params = shard_params(init_params(np.random.RandomState(0), LATENT),
                          LATENT, mesh)
    opt = optax.adam(1e-2)
    tokens, targets = _data(mesh, batch=2)
    text = make_train_step(LATENT, mesh, opt).lower(
        params, opt.init(params), tokens, targets).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))

    def shown(*parts):
        return any(all(p in n for p in parts) for n in names)

    for scope in ("hvd_mla", "hvd_moe", "hvd_mtp"):
        assert shown("jvp(", scope) and shown("transpose(jvp(", scope), scope
    assert shown("hvd_mla/hvd_attn")
    for inner in ("hvd_moe_route", "hvd_moe_experts", "hvd_moe_shared"):
        assert shown("hvd_moe/" + inner), inner
    assert shown("hvd_mtp", "hvd_mla") and shown("hvd_mtp", "hvd_loss_head")
    assert shown("checkpoint") or shown("remat")      # blocks recomputed

    flight.reset()
    data = P("dp", "sp")
    pairs = jax.jit(shard_map(
        lambda p, tok, tgt: loss_and_routing(p, tok, tgt, LATENT)[1],
        mesh=mesh, check_vma=False, in_specs=(param_specs(LATENT), data, data),
        out_specs=P(None, "dp")))(params, tokens, targets)
    records = record_routing(LATENT, pairs, tokens.size)
    ring = [e for e in flight.recorder().snapshot()
            if e["kind"] == "hvd_moe_route"]
    assert [e["layer"] for e in ring] == [0, 1, 2] == [
        r["layer"] for r in records]
    for event in ring:
        assert event["dropped"] == 0 and len(event["pairs"]) == 8
        assert sum(event["pairs"]) == tokens.size * LATENT.experts_per_token
        run = event["chunks"] * event["chunk_rows"]
        assert sum(event["pairs"]) <= run < (sum(event["pairs"])
                                             + event["chunk_rows"])
    # two ranks' halves of a router of 8, 5 tokens: the busiest rank's
    # 9 pairs take 3 chunks of 4 rows; the rule's own rows otherwise
    import dataclasses

    from horovod_tpu.parallel import moe

    cfg = dataclasses.replace(LATENT, experts_held=4)
    (record,) = record_routing(cfg, [[1, 2, 0, 3, 4, 0, 5, 0]], 5)
    assert (record["chunk_rows"], record["chunks"]) == (
        moe.chunk_rows(15, 4, 8), 1)
    monkeypatch.setattr(moe, "CHUNK_ROWS", 4)
    (record,) = record_routing(cfg, [[1, 2, 0, 3, 4, 0, 5, 0]], 5)
    assert (record["chunk_rows"], record["chunks"]) == (4, 3)


def _loss_and_grads(cfg):
    """``(kernel calls, named values, loss, gradients)`` of the
    per-device loss on one device: the first two counted in the jaxpr
    of the gradient, the replayed blocks inside it."""
    from jax import shard_map
    from test_pallas_attention import _primitives

    from horovod_tpu.models.transformer import loss_fn, param_specs

    mesh = make_mesh(dp=1, pp=1, tp=1, sp=1, devices=jax.devices()[:1])
    params = shard_params(init_params(np.random.RandomState(0), cfg), cfg,
                          mesh)
    ids = np.random.RandomState(1).randint(0, cfg.vocab, (2, 33))
    sh = NamedSharding(mesh, P("dp", "sp"))
    tokens = jax.device_put(jnp.asarray(ids[:, :-1], jnp.int32), sh)
    targets = jax.device_put(jnp.asarray(ids[:, 1:], jnp.int32), sh)
    specs, data = param_specs(cfg), P("dp", "sp")
    fn = jax.jit(shard_map(
        lambda p, tok, tgt: jax.value_and_grad(loss_fn)(p, tok, tgt, cfg),
        mesh=mesh, check_vma=False, in_specs=(specs, data, data),
        out_specs=(P(), specs)))
    names = _primitives(jax.make_jaxpr(fn)(params, tokens, targets).jaxpr)
    return (names.count("pallas_call"), names.count("name"),
            *fn(params, tokens, targets))


# rel. l2 of remat=True's gradient against remat=False's, all leaves
# together and the worst leaf.  float32 is equal to rounding.  In
# bfloat16 the CPU compiler fuses the replayed block's converts
# otherwise; measured 0.0098 / 0.016 here, and 0.068 / 0.22 with the
# parent's policy-free checkpoint, whose replay ran the kernel again
# (CHANGES.md, PR 32).
_REMAT_APART = {"float32": (1e-6, 1e-6), "bfloat16": (2e-2, 5e-2)}


@pytest.mark.parametrize("kind,impl,dtype", [
    ("mha", "pallas", "float32"), ("latent", "pallas", "float32"),
    ("mha", "pallas", "bfloat16"), ("latent", "pallas", "bfloat16"),
    ("mha", "xla", "float32"), ("latent", "xla", "float32")])
def test_a_recomputed_block_keeps_what_its_attention_kernel_gave(
        kind, impl, dtype):
    """``remat=True`` against ``remat=False``, kernels interpreted: the
    same loss and gradients, and through the Pallas path the gradient's
    jaxpr holds three kernel calls a block (the GPT-2 block x 2; layer
    0, two expert layers and the MTP module's): one forward and the two
    backward, no second forward, because the replayed block takes
    ``out`` and ``lse`` from what the policy kept.  The XLA path names
    nothing, so the policy keeps nothing there and a recomputed block
    keeps only its input."""
    import dataclasses

    base, blocks = {"mha": (dataclasses.replace(CFG, n_layers=2), 2),
                    "latent": (LATENT, 4)}[kind]
    base = dataclasses.replace(base, attn_impl=impl, dtype=dtype)
    plain = _loss_and_grads(dataclasses.replace(base, remat=False))
    kept = _loss_and_grads(dataclasses.replace(base, remat=True))

    on_path = impl == "pallas"
    assert plain[:2] == (3 * blocks * on_path, 0)
    assert kept[:2] == (3 * blocks * on_path, 2 * blocks * on_path)
    assert float(kept[2]) == float(plain[2])
    pairs = [(np.asarray(a, np.float32), np.asarray(b, np.float32))
             for a, b in zip(jax.tree_util.tree_leaves(plain[3]),
                             jax.tree_util.tree_leaves(kept[3]))]
    whole, leaf = _REMAT_APART[dtype]
    assert (sum(np.sum((a - b) ** 2) for a, b in pairs)
            <= whole ** 2 * sum(np.sum(a ** 2) for a, _ in pairs))
    for a, b in pairs:
        assert np.linalg.norm(a - b) <= leaf * np.linalg.norm(a)
