"""Ring attention and Ulysses sequence parallelism vs dense reference.

New TPU capability (SURVEY §5.7 — absent in the reference); validated
numerically against single-device dense attention.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.parallel.ring_attention import (reference_attention,
                                                 ring_attention,
                                                 zigzag_shard,
                                                 zigzag_unshard)
from horovod_tpu.parallel.ulysses import ulysses_attention

SP = 8
B, L, H, D = 2, 64, 8, 16


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:SP]), ("sp",))


def _qkv(seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, L, H, D).astype(np.float32)) * 0.3
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(mesh, causal):
    q, k, v = _qkv()
    expected = reference_attention(q, k, v, causal=causal)

    fn = jax.jit(shard_map(
        lambda a, b_, c: ring_attention(a, b_, c, "sp", causal=causal),
        mesh=mesh, check_vma=False,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp")))
    out = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_zigzag_ring_attention_matches_dense(mesh, causal):
    """Zigzag layout (balanced causal work, fully-masked pairs skipped)
    must be numerically identical to dense attention after unshard."""
    q, k, v = _qkv()
    expected = reference_attention(q, k, v, causal=causal)

    qz = zigzag_shard(q, SP)
    kz = zigzag_shard(k, SP)
    vz = zigzag_shard(v, SP)
    fn = jax.jit(shard_map(
        lambda a, b_, c: ring_attention(a, b_, c, "sp", causal=causal,
                                        layout="zigzag"),
        mesh=mesh, check_vma=False,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp")))
    out = zigzag_unshard(fn(qz, kz, vz), SP)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_attention_matches_dense(causal):
    from horovod_tpu.parallel.ring_attention import blockwise_attention

    q, k, v = _qkv(7)
    expected = reference_attention(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


def test_zigzag_shard_roundtrip():
    x = jnp.arange(2 * 32 * 3).reshape(2, 32, 3)
    z = zigzag_shard(x, 4)
    assert not np.array_equal(np.asarray(z), np.asarray(x))
    back = zigzag_unshard(z, 4)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_zigzag_ring_attention_grad(mesh):
    q, k, v = _qkv(3)
    qz, kz, vz = (zigzag_shard(t, SP) for t in (q, k, v))

    def loss(a, b_, c):
        o = ring_attention(a, b_, c, "sp", causal=True, layout="zigzag")
        return (o * o).sum()

    fn = jax.jit(shard_map(
        lambda a, b_, c: jax.grad(loss, argnums=0)(a, b_, c),
        mesh=mesh, check_vma=False,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp")))
    g = fn(qz, kz, vz)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).max()) > 0


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_dense(mesh, causal):
    q, k, v = _qkv(1)
    expected = reference_attention(q, k, v, causal=causal)

    fn = jax.jit(shard_map(
        lambda a, b_, c: ulysses_attention(a, b_, c, "sp", causal=causal),
        mesh=mesh, check_vma=False,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp")))
    out = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_grad_flows(mesh):
    q, k, v = _qkv(2)

    def loss_spmd(a, b_, c):
        o = ring_attention(a, b_, c, "sp", causal=True)
        return jax.lax.psum(jnp.sum(o.astype(jnp.float32) ** 2), "sp").reshape(1)

    fn = jax.jit(shard_map(
        lambda a, b_, c: jax.grad(lambda x: loss_spmd(x, b_, c)[0])(a),
        mesh=mesh, check_vma=False,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp")))
    g_ring = np.asarray(fn(q, k, v))

    g_dense = np.asarray(jax.grad(
        lambda x: jnp.sum(reference_attention(x, k, v, True).astype(jnp.float32) ** 2))(q))
    # the psum in the SPMD loss transposes to a psum: grads carry an
    # axis-size factor relative to the single-device loss
    np.testing.assert_allclose(g_ring, SP * g_dense, rtol=5e-3, atol=5e-4)


def test_ring_attention_bf16(mesh):
    q, k, v = _qkv(3)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    expected = reference_attention(qb, kb, vb, causal=True)
    fn = jax.jit(shard_map(
        lambda a, b_, c: ring_attention(a, b_, c, "sp", causal=True),
        mesh=mesh, check_vma=False,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp")))
    out = fn(qb, kb, vb)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32)),
        np.asarray(expected.astype(jnp.float32)), rtol=0.1, atol=0.05)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sp", [1, 2, 4])
def test_pallas_ring_gives_what_the_carried_ring_gave(sp, causal):
    """The Pallas ring's first step takes no state, its last step
    finishes the softmax in the kernel, and K and V stay where the last
    step used them (at sp = 1 nothing rotates at all).  Values: those of
    the ring that carries its state through every step and normalizes
    in XLA (``impl="xla"``: the same recurrence in ``xla_block_step``),
    to an ulp.  Gradients of all three inputs: the dense reference's,
    dK and dV home after ``sp`` rotations."""
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    q, k, v = _qkv(11)
    spec = P(None, "sp")

    def run(impl):
        def per_device(a, b_, c):
            def loss(a_, b__, c_):
                out = ring_attention(a_, b__, c_, "sp", causal=causal,
                                     impl=impl)
                return jnp.sum(out ** 2), out
            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(a, b_, c)
            return (out,) + grads
        return jax.jit(shard_map(per_device, mesh=mesh, check_vma=False,
                                 in_specs=(spec,) * 3,
                                 out_specs=(spec,) * 4))(q, k, v)

    out, *grads = run("pallas")
    carried_out, *_ = run("xla")
    np.testing.assert_array_max_ulp(np.asarray(out), np.asarray(carried_out),
                                    maxulp=1)

    def dense_loss(a, b_, c):
        return jnp.sum(reference_attention(a, b_, c, causal=causal) ** 2)

    for got, want in zip(grads, jax.grad(dense_loss, argnums=(0, 1, 2))(
            q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-4)
