"""DistributedOptimizer / DistributedGradientTape behavior.

Mirrors the reference's optimizer-wrapper tests (gradient averaging
across ranks, ``test/test_torch.py`` DistributedOptimizer cases and
``backward_passes_per_step`` accumulation, ``torch/__init__.py:127-162``).
"""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd

N = 8


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:N]), ("hvd",))


def test_intrace_grad_averaging(mesh):
    """Data-parallel step under shard_map: wrapped optimizer must apply
    the full-batch (cross-rank mean) gradient on every rank."""
    opt = hvd.DistributedOptimizer(optax.sgd(1.0), axis_name="hvd")
    w0 = jnp.ones((4,))
    # per-rank batch: rank r holds target r
    targets = jnp.arange(N, dtype=jnp.float32)

    def per_rank(t):
        w = w0
        state = opt.init(w)

        def loss(w):
            return jnp.sum((w - t[0]) ** 2)

        g = jax.grad(loss)(w)
        updates, _ = opt.update(g, state, w)
        return optax.apply_updates(w, updates)

    fn = jax.jit(shard_map(per_rank, mesh=mesh, check_vma=False,
                           in_specs=P("hvd"), out_specs=P("hvd")))
    out = np.asarray(fn(targets)).reshape(N, 4)
    # mean gradient = mean_r 2(w - r) = 2(1 - mean(r)); w' = w - lr*g
    expected = 1.0 - 2.0 * (1.0 - targets.mean())
    np.testing.assert_allclose(out, np.full((N, 4), expected), rtol=1e-6)
    # every rank took the same step (replicated update)
    assert np.ptp(out) < 1e-6


def test_eager_optimizer_single(hvd_single):
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    params = {"w": jnp.ones((3,)), "b": jnp.zeros(())}
    state = opt.init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + p["b"]

    grads = jax.grad(loss)(params)
    updates, state = opt.update(grads, state, params)
    new_params = optax.apply_updates(params, updates)
    np.testing.assert_allclose(np.asarray(new_params["w"]),
                               np.full(3, 1.0 - 0.1 * 2.0), rtol=1e-6)


def test_backward_passes_per_step(hvd_single):
    """Accumulate k=3 micro-batches, update once with the averaged grad
    (reference backward_passes_per_step)."""
    opt = hvd.DistributedOptimizer(optax.sgd(1.0), backward_passes_per_step=3)
    w = jnp.zeros((2,))
    state = opt.init(w)
    micro_grads = [jnp.full((2,), g) for g in (3.0, 6.0, 9.0)]
    for i, g in enumerate(micro_grads):
        updates, state = opt.update(g, state, w)
        w = optax.apply_updates(w, updates)
        if i < 2:
            np.testing.assert_allclose(np.asarray(w), 0.0)
    # mean grad = 6.0; single SGD step of lr 1.0
    np.testing.assert_allclose(np.asarray(w), -6.0)


def test_distributed_gradient_tape_eager(hvd_single):
    tape = hvd.DistributedGradientTape(lambda w: jnp.sum(w ** 2))
    g = tape.gradient(jnp.ones((4,)))
    np.testing.assert_allclose(np.asarray(g), np.full(4, 2.0))


def test_grad_wrapper_intrace(mesh):
    gfn = hvd.grad(lambda w, t: jnp.sum((w - t) ** 2), axis_name="hvd")

    def per_rank(t):
        return gfn(jnp.zeros(()), t[0]).reshape(1)

    fn = jax.jit(shard_map(per_rank, mesh=mesh, check_vma=False,
                           in_specs=P("hvd"), out_specs=P("hvd")))
    out = np.asarray(fn(jnp.arange(N, dtype=jnp.float32)))
    expected = -2.0 * np.arange(N).mean()
    np.testing.assert_allclose(out, np.full(N, expected), rtol=1e-6)


def test_eager_fused_pytree_mixed_dtypes(hvd_single):
    grads = {"a": jnp.ones((4,), jnp.float32),
             "b": jnp.ones((2, 2), jnp.bfloat16),
             "c": jnp.full((3,), 2.0, jnp.float32)}
    out = hvd.allreduce_gradients(grads, op=hvd.Sum)
    np.testing.assert_allclose(np.asarray(out["a"]), np.ones(4))
    assert out["b"].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out["c"]), np.full(3, 2.0))
    assert out["b"].shape == (2, 2)


def test_rejects_non_optax():
    with pytest.raises(TypeError):
        hvd.DistributedOptimizer(object())


def _compiled_op_names(mesh, monkeypatch, overlap: bool):
    import re

    monkeypatch.setenv("HOROVOD_OVERLAP", "1" if overlap else "0")
    opt = hvd.DistributedOptimizer(optax.sgd(0.1), axis_name="hvd")

    def per_rank(w, t):
        g = jax.grad(lambda w: jnp.sum((w - t[0]) ** 2))(w)
        updates, _ = opt.update(g, opt.init(w), w)
        return optax.apply_updates(w, updates)

    text = jax.jit(shard_map(
        per_rank, mesh=mesh, check_vma=False, in_specs=(P(), P("hvd")),
        out_specs=P())).lower(
            jnp.ones((4096,)), jnp.arange(N, dtype=jnp.float32)
    ).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def test_stage0_step_names_reduction_and_optimizer(mesh, monkeypatch):
    """docs/perf.md: a stage-0 ``DistributedOptimizer`` step shows the
    gradient collective under ``hvd_grad_reduce`` and the wrapped
    update under ``hvd_optimizer``; the family's own ``apply_updates``
    stays bare."""
    names = _compiled_op_names(mesh, monkeypatch, overlap=False)
    assert any("hvd_grad_reduce/psum" in n for n in names), names
    assert any("hvd_optimizer/" in n for n in names), names
    assert not any("hvd_grad_reduce" in n and "hvd_optimizer" in n
                   for n in names)


def test_overlap_buckets_keep_their_scope_inside_the_reduction(
        mesh, monkeypatch):
    """``hvd_grad_reduce`` encloses the bucketed schedule's own scopes;
    ``perf/attribution`` takes the innermost, so per-bucket seconds read
    as before."""
    from horovod_tpu.perf import attribution

    names = _compiled_op_names(mesh, monkeypatch, overlap=True)
    nested = [n for n in names
              if "hvd_grad_reduce/" in n and "hvd_overlap_" in n]
    assert nested, names
    found = {attribution._scope_of(n) for n in nested}
    assert found and all(s.startswith("hvd_overlap_") for s in found), found
    assert any(s.startswith("hvd_overlap_rs") for s in found)
    assert attribution._scope_of(
        "jit(f)/hvd_grad_reduce/psum") == "hvd_grad_reduce"
