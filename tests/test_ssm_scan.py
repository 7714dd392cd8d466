"""The chunked state-space scan (``ops/ssm_scan.py``) against the
recurrence it stands for, computed one time step after the other:
values and every gradient, at sequences that are 2 and 5 whole chunks
and at one that is no whole number of them, in float32 and with bfloat16
operands."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import ssm_scan

B, H, P, G, N = 2, 4, 8, 2, 16


def _inputs(length: int, seed: int = 0, dtype=jnp.float32):
    """Operands as a Mamba-2 mixer hands them over: positive time
    steps, decay rates in [-16, -1], so a chunk's decay spans many
    orders of magnitude."""
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, length, H, P), dtype)
    dt = jax.nn.softplus(jnp.asarray(rng.randn(B, length, H) - 1.0,
                                     jnp.float32))
    a = -jnp.asarray(rng.uniform(1, 16, H), jnp.float32)
    b = jnp.asarray(rng.randn(B, length, G, N), dtype)
    c = jnp.asarray(rng.randn(B, length, G, N), dtype)
    d = jnp.asarray(rng.randn(H), jnp.float32)
    return x, dt, a, b, c, d


def _step_by_step(x, dt, a, b, c, d):
    """The recurrence itself in float32, written apart from the module's
    own golden model: a Python loop over the time steps."""
    x, b, c = (t.astype(jnp.float32) for t in (x, b, c))
    b, c = (jnp.repeat(t, H // G, axis=2) for t in (b, c))
    state = jnp.zeros((x.shape[0], H, P, N), jnp.float32)
    out = []
    for t in range(x.shape[1]):
        state = (jnp.exp(dt[:, t] * a)[..., None, None] * state
                 + jnp.einsum("bh,bhp,bhn->bhpn", dt[:, t], x[:, t], b[:, t]))
        out.append(jnp.einsum("bhpn,bhn->bhp", state, c[:, t])
                   + d[:, None] * x[:, t])
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("length,chunk", [(16, 8), (40, 8), (40, 16)],
                         ids=["2_chunks", "5_chunks", "2_and_a_half"])
def test_chunked_scan_is_the_recurrence_in_float32(length, chunk):
    """Values and the gradients of all six operands.  The two differ by
    the order of their float32 sums (read: 1e-6 relative)."""
    operands = _inputs(length)
    with jax.default_matmul_precision("highest"):
        y, least = ssm_scan.ssm_scan(*operands, chunk)
        want = _step_by_step(*operands)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(ssm_scan.scan_reference(*operands)), np.asarray(want),
            rtol=2e-5, atol=2e-5)
        weights = jnp.asarray(np.random.RandomState(1).randn(*want.shape),
                              jnp.float32)
        got = jax.grad(lambda *t: jnp.sum(
            weights * ssm_scan.ssm_scan(*t, chunk)[0]),
            argnums=range(6))(*operands)
        wanted = jax.grad(lambda *t: jnp.sum(weights * _step_by_step(*t)),
                          argnums=range(6))(*operands)
    for g, w in zip(got, wanted):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-4 * float(jnp.max(jnp.abs(w))))
    # the least logarithm of a whole chunk's decay, from the time steps
    padded = jnp.pad(operands[1], ((0, 0), (0, -length % chunk), (0, 0)))
    whole = (padded * operands[2]).reshape(B, -1, chunk, H).sum(axis=2)
    assert float(least) == pytest.approx(float(whole.min()), rel=1e-5)


@pytest.mark.parametrize("length,chunk", [(16, 8), (40, 8)],
                         ids=["2_chunks", "5_chunks"])
def test_chunked_scan_with_bfloat16_operands(length, chunk):
    """``x``, ``B`` and ``C`` in bfloat16 as the mixer hands them over;
    the time steps, the decays' logarithms and the carried state stay
    float32.  Against the float32 recurrence on the same (rounded)
    operands the scan differs by the rounding of its products' operands
    — ``L o C B^T``, ``dt o X`` and the state entering a chunk are
    rounded to 8 bits before they meet the MXU — 2^-8 a term, averaged
    over the terms of a sum: read 0.2-0.6 % of the largest value; the
    limit is 2 %."""
    operands = _inputs(length, seed=3, dtype=jnp.bfloat16)
    y, _ = ssm_scan.ssm_scan(*operands, chunk)
    assert y.dtype == jnp.float32
    want = _step_by_step(*operands)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(y - want))) < 2e-2 * scale
    got = jax.grad(lambda x, b: jnp.sum(
        ssm_scan.ssm_scan(x, operands[1], operands[2], b, *operands[4:],
                          chunk)[0] ** 2), argnums=(0, 1))(
        operands[0], operands[3])
    wanted = jax.grad(lambda x, b: jnp.sum(_step_by_step(
        x, operands[1], operands[2], b, *operands[4:]) ** 2),
        argnums=(0, 1))(operands[0], operands[3])
    for g, w in zip(got, wanted):
        assert g.dtype == jnp.bfloat16
        w = np.asarray(w.astype(jnp.float32))
        assert np.max(np.abs(np.asarray(g.astype(jnp.float32)) - w)) \
            < 4e-2 * np.max(np.abs(w))


def test_a_chunk_that_forgets_everything_stays_finite():
    """Decays whose logarithms sum to far below what float32 can hold
    (exp(-87) is its smallest normal number): the scan only ever takes
    ``exp`` of a difference of two sums inside a chunk, so nothing is
    divided by an underflowed decay, and values and gradients are
    finite and the recurrence's."""
    x, dt, a, b, c, d = _inputs(32, seed=5)
    dt = dt + 2.0                       # a chunk of 16 decays by exp(-500)
    with jax.default_matmul_precision("highest"):
        y, least = ssm_scan.ssm_scan(x, dt, a, b, c, d, 16)
        grads = jax.grad(lambda *t: jnp.sum(
            ssm_scan.ssm_scan(*t, 16)[0] ** 2), argnums=range(6))(
            x, dt, a, b, c, d)
        want = _step_by_step(x, dt, a, b, c, d)
    assert float(least) < -200
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)
