"""Pallas flash-attention kernel vs dense reference (interpret mode on
the CPU test mesh exercises the exact TPU kernel code path)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.ops.pallas_attention import flash_fwd_step
from horovod_tpu.parallel.ring_attention import (reference_attention,
                                                 ring_attention)

B, L, H, D = 2, 64, 4, 16


def _qkv(seed=0, l=L):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, l, H, D).astype(np.float32)) * 0.3
    return mk(), mk(), mk()


def _pack(x):
    b, l_, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, l_, d)


def _unpack(x, b, h):
    bh, l_, d = x.shape
    return x.reshape(b, h, l_, d).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("causal", [True, False])
def test_single_step_matches_dense(causal):
    q, k, v = _qkv()
    qp, kp, vp = _pack(q), _pack(k), _pack(v)
    m = jnp.full(qp.shape[:2], -jnp.inf, jnp.float32)
    l = jnp.zeros(qp.shape[:2], jnp.float32)
    o = jnp.zeros(qp.shape, jnp.float32)
    m, l, o = flash_fwd_step(qp, kp, vp, (m, l, o), 0, 0, causal=causal,
                             block_q=32, block_k=32, interpret=True)
    l = jnp.where(l == 0.0, 1.0, l)
    out = _unpack(o / l[..., None], B, H)
    expected = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_carried_state_composes_across_kv_chunks(causal):
    """Two sequential kernel calls over half-KV chunks must equal one
    dense attention — the ring-resume contract."""
    q, k, v = _qkv(1)
    qp, kp, vp = _pack(q), _pack(k), _pack(v)
    half = L // 2
    m = jnp.full(qp.shape[:2], -jnp.inf, jnp.float32)
    l = jnp.zeros(qp.shape[:2], jnp.float32)
    o = jnp.zeros(qp.shape, jnp.float32)
    # NB: q_offset=0 with k chunks at global offsets 0 and half
    m, l, o = flash_fwd_step(qp, kp[:, :half], vp[:, :half], (m, l, o),
                             0, 0, causal=causal, block_q=32, block_k=16,
                             interpret=True)
    m, l, o = flash_fwd_step(qp, kp[:, half:], vp[:, half:], (m, l, o),
                             0, half, causal=causal, block_q=32,
                             block_k=16, interpret=True)
    l = jnp.where(l == 0.0, 1.0, l)
    out = _unpack(o / l[..., None], B, H)
    expected = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


def test_block_shape_validation():
    q, k, v = _qkv()
    qp, kp, vp = _pack(q), _pack(k), _pack(v)
    m = jnp.zeros(qp.shape[:2], jnp.float32)
    o = jnp.zeros(qp.shape, jnp.float32)
    with pytest.raises(ValueError, match="divide"):
        flash_fwd_step(qp, kp, vp, (m, m, o), 0, 0, block_q=48,
                       interpret=True)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_pallas_matches_dense(causal):
    sp = 4
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    q, k, v = _qkv(2)
    expected = reference_attention(q, k, v, causal=causal)

    fn = jax.jit(shard_map(
        lambda a, b_, c: ring_attention(a, b_, c, "sp", causal=causal,
                                        impl="pallas"),
        mesh=mesh, check_vma=False,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp")))
    out = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_impls_agree_bfloat16():
    """bf16 inputs: both impls keep fp32 softmax state and agree."""
    sp = 2
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    q, k, v = [x.astype(jnp.bfloat16) for x in _qkv(3)]

    def run(impl):
        fn = jax.jit(shard_map(
            lambda a, b_, c: ring_attention(a, b_, c, "sp", causal=True,
                                            impl=impl),
            mesh=mesh, check_vma=False,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp")))
        return np.asarray(fn(q, k, v)).astype(np.float32)

    np.testing.assert_allclose(run("pallas"), run("xla"), rtol=2e-2,
                               atol=2e-2)


def test_impl_validation():
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="impl"):
        jax.jit(shard_map(
            lambda a, b_, c: ring_attention(a, b_, c, "sp", impl="palas"),
            mesh=mesh, check_vma=False,
            in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp")))(q, k, v)


def test_unaligned_chunk_raises_when_pallas_asked():
    """lc=12 has no tile the kernel can use: an explicit impl='pallas'
    raises instead of quietly running the XLA step under the kernel's
    name (the automatic pick logs once and uses XLA)."""
    sp = 4
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    q, k, v = _qkv(4, l=48)  # lc = 12
    fn = jax.jit(shard_map(
        lambda a, b_, c: ring_attention(a, b_, c, "sp", causal=True,
                                        impl="pallas"),
        mesh=mesh, check_vma=False,
        in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp")))
    with pytest.raises(ValueError, match="no tile size"):
        fn(q, k, v)


def test_grad_through_pallas_ring():
    """jax.grad must flow through the pallas impl (custom VJP = XLA
    step's backward) and agree with the xla impl's grad."""
    sp = 2
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    q, k, v = _qkv(5)

    def make_loss(impl):
        def loss(a, b_, c):
            o = ring_attention(a, b_, c, "sp", causal=True, impl=impl)
            return jnp.sum(o ** 2)
        return jax.jit(shard_map(
            lambda a, b_, c: jax.grad(loss, argnums=(0, 1, 2))(a, b_, c),
            mesh=mesh, check_vma=False,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=(P(None, "sp"),) * 3))

    gp = make_loss("pallas")(q, k, v)
    gx = make_loss("xla")(q, k, v)
    for a, b_ in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_bwd_kernels_match_dense_vjp(causal):
    """flash_bwd_dq/dkv (saved-LSE backward kernels) vs the dense
    reference attention's autodiff on one full block."""
    from horovod_tpu.ops.pallas_attention import (flash_bwd_dkv,
                                                  flash_bwd_dq)

    q, k, v = _qkv(7)
    qp, kp, vp = _pack(q), _pack(k), _pack(v)
    m = jnp.full(qp.shape[:2], -jnp.inf, jnp.float32)
    l = jnp.zeros(qp.shape[:2], jnp.float32)
    o = jnp.zeros(qp.shape, jnp.float32)
    m, l, o = flash_fwd_step(qp, kp, vp, (m, l, o), 0, 0, causal=causal,
                             block_q=32, block_k=16, interpret=True)
    lse = jnp.where(l > 0, m + jnp.log(jnp.where(l > 0, l, 1.0)), -jnp.inf)
    lsafe = jnp.where(l == 0.0, 1.0, l)
    out = o / lsafe[..., None]

    rng = np.random.RandomState(8)
    dout = jnp.asarray(rng.randn(*out.shape).astype(np.float32)) * 0.1
    delta = jnp.sum(dout * out, axis=-1)
    dq = flash_bwd_dq(qp, kp, vp, dout, lse, delta, 0, 0, causal=causal,
                      block_q=32, block_k=16, interpret=True)
    dk, dv = flash_bwd_dkv(qp, kp, vp, dout, lse, delta, 0, 0,
                           causal=causal, block_q=32, block_k=16,
                           interpret=True)

    def dense(qp_, kp_, vp_):
        d = qp_.shape[-1]
        s = jnp.einsum("bqd,bkd->bqk", qp_, kp_).astype(jnp.float32)
        s = s / (d ** 0.5)
        if causal:
            ll = qp_.shape[1]
            mask = jnp.tril(jnp.ones((ll, ll), bool))
            s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, vp_)

    _, vjp = jax.vjp(dense, qp, kp, vp)
    edq, edk, edv = vjp(dout)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(edq),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(edk),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(edv),
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_kernel_bwd_matches_dense_grads(causal):
    """sp=4 ring with the kernel backward vs dense reference autodiff:
    the full ring-level VJP contract (dq local, dk/dv after the full
    rotation cycle) on global tensors."""
    sp = 4
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    q, k, v = _qkv(9)

    def ring_loss(a, b_, c):
        o = ring_attention(a, b_, c, "sp", causal=causal, impl="pallas")
        return jnp.sum(o * o)

    gp = jax.jit(shard_map(
        lambda a, b_, c: jax.grad(ring_loss, argnums=(0, 1, 2))(a, b_, c),
        mesh=mesh, check_vma=False,
        in_specs=(P(None, "sp"),) * 3,
        out_specs=(P(None, "sp"),) * 3))(q, k, v)

    def dense_loss(a, b_, c):
        o = reference_attention(a, b_, c, causal=causal)
        return jnp.sum(o * o)

    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(gp, gd):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("batch, heads, seq, impl", [
    (16, 12, 1024, "xla"),       # gpt2-124m.s1024
    (8, 12, 2048, "xla"),        # R0's gpt2-124m.s2048: 3 GiB of scores
    (4, 12, 4096, "pallas"),     # 6 GiB
    (2, 12, 8192, "pallas"),     # gpt2-124m.s8192
    (2, 32, 8192, "pallas"),     # joyai-llm-flash.s8192.epshare
    (8, 16, 2048, "xla"),        # 8 * 2**29 bytes: the threshold itself
    (8, 16, 2056, "pallas"),     # the next sublane-aligned length
])
def test_auto_impl_picks_by_the_score_block(batch, heads, seq, impl):
    """8 bytes a score element (f32 scores and an f32 softmax
    transient) against ``XLA_SCORE_BYTES``: the three LM cells' shapes
    and both sides of the boundary."""
    from horovod_tpu.parallel import ring_attention as ra

    assert ra.XLA_SCORE_BYTES == 4 << 30
    assert ra.auto_impl(batch, heads, seq) == impl
    assert ra.auto_impl(batch, heads, seq, seq) == impl


def _lowered_ring(impl):
    """Forward and backward of a two-chip causal ring at a small shape,
    as lowered text (Pallas kernels in interpret mode)."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    spec = P(None, "sp")

    def loss(a, b_, c):
        return jnp.sum(ring_attention(a, b_, c, "sp", causal=True,
                                      impl=impl) ** 2)

    return jax.jit(shard_map(
        jax.grad(loss, argnums=(0, 1, 2)), mesh=mesh, check_vma=False,
        in_specs=(spec,) * 3, out_specs=(spec,) * 3)).lower(
            *_qkv(6)).as_text()


_REMOVED_KNOBS = {
    "HOROVOD_ATTN_BLOCK_Q": ("attn_block_q", "8"),
    "HOROVOD_ATTN_BLOCK_K": ("attn_block_k", "8"),
    "HOROVOD_ATTN_PALLAS_BWD": ("attn_pallas_bwd", "remat"),
    "HOROVOD_ATTN_XLA_SCORE_BYTES": ("attn_xla_score_bytes", "0"),
}


@pytest.fixture(scope="module")
def clean_ring_texts():
    with pytest.MonkeyPatch.context() as patch:
        for env in _REMOVED_KNOBS:
            patch.delenv(env, raising=False)
        return {impl: _lowered_ring(impl) for impl in ("pallas", "xla")}


@pytest.mark.parametrize("env", sorted(_REMOVED_KNOBS))
def test_a_removed_attention_knob_is_not_read(monkeypatch, clean_ring_texts,
                                              env):
    """The four switches the pre-harness A/B apparatus swept are gone:
    set to a value that used to change the program (8-wide tiles, the
    XLA-remat backward, a threshold of nothing), each leaves the lowered
    ring and ``auto_impl``'s pick as they are, and the registry does not
    know the name."""
    from horovod_tpu.common import config
    from horovod_tpu.parallel.ring_attention import auto_impl

    key, value = _REMOVED_KNOBS[env]
    monkeypatch.setenv(env, value)
    for impl, text in clean_ring_texts.items():
        assert _lowered_ring(impl) == text, impl
    assert auto_impl(16, 12, 1024) == "xla"
    with pytest.raises(KeyError):
        config.get(key)
    assert env not in {k.env for k in config.knobs().values()}


@pytest.mark.parametrize("chunk, tile", [(8192, 1024), (1024, 1024),
                                         (512, 512), (384, 128),
                                         (136, 8), (12, None)])
def test_auto_tile_is_the_largest_on_the_ladder(chunk, tile):
    """Tiles come from the shape, up to 1024: a grid step costs the
    same whatever its tile."""
    from horovod_tpu.parallel.ring_attention import (_block_sizes,
                                                     _pick_block)

    assert _pick_block(chunk) == tile
    assert _block_sizes(chunk, chunk, 64, 2) == (tile, tile)


def test_auto_tile_steps_down_to_fit_vmem():
    """A head so wide that 1024x1024 would ask for more than half of
    VMEM gets a smaller K tile, then a smaller Q tile; what the kernels
    ask Mosaic for follows the tile."""
    from horovod_tpu.ops.pallas_attention import (VMEM_BUDGET,
                                                  tile_vmem_bytes)
    from horovod_tpu.parallel.ring_attention import _block_sizes

    assert tile_vmem_bytes(1024, 1024, 64, 2) < VMEM_BUDGET // 3
    assert _block_sizes(8192, 8192, 256, 4) == (1024, 1024)
    for d, tiles in ((768, (1024, 512)), (1024, (512, 512))):
        assert _block_sizes(8192, 8192, d, 4) == tiles
        assert tile_vmem_bytes(*tiles, d, 4) <= VMEM_BUDGET


def test_causal_tile_counts_match_a_count_over_positions():
    """grid / live / diagonal tile pairs as the kernels' predicates
    decide them, against the mask itself: a pair is live iff any
    (query, key) position in it is visible and diagonal iff, being
    live, any is hidden."""
    from horovod_tpu.ops.pallas_attention import causal_tile_counts

    def brute(lq, lk, bq, bk, qo, ko):
        visible = ((qo + np.arange(lq))[:, None]
                   >= (ko + np.arange(lk))[None, :])
        tiles = visible.reshape(lq // bq, bq, lk // bk, bk)
        some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
        return some.size, int(some.sum()), int((some & ~every).sum())

    for case in [(128, 128, 32, 16, 0, 0), (128, 128, 16, 32, 0, 0),
                 (128, 128, 32, 16, 128, 0), (128, 128, 16, 32, 0, 128),
                 (64, 128, 16, 64, 40, 8), (96, 64, 8, 32, 0, 24)]:
        assert causal_tile_counts(*case) == brute(*case), case
    # the benchmark's long cell, per head: today's tiles and the parent's
    assert causal_tile_counts(8192, 8192, 1024, 1024) == (64, 36, 8)
    assert causal_tile_counts(8192, 8192, 128, 128) == (4096, 2080, 64)


@pytest.mark.parametrize("q_offset, k_offset",
                         [(0, 0), (128, 0), (0, 128)],
                         ids=["diagonal", "past", "future"])
def test_kernels_match_xla_step_over_block_positions(q_offset, k_offset):
    """The three kernels against ``xla_block_step`` and its ``jax.vjp``
    with rectangular tiles, on a KV block that holds the diagonal
    (masked, unmasked and skipped tile pairs), one wholly in the past
    (no pair builds a mask) and one wholly in the future (every pair
    skipped: the carried state comes back unchanged and the gradients
    are exactly zero)."""
    from horovod_tpu.ops.pallas_attention import (flash_bwd_dkv,
                                                  flash_bwd_dq)
    from horovod_tpu.parallel.ring_attention import xla_block_step

    q, k, v = (_pack(x) for x in _qkv(11, l=128))
    rng = np.random.RandomState(12)
    bh, lq, d = q.shape
    # a carried state from an earlier block, as in a ring's later steps
    m0 = jnp.asarray(rng.randn(bh, lq), jnp.float32)
    l0 = jnp.asarray(rng.rand(bh, lq) + 0.5, jnp.float32)
    o0 = jnp.asarray(rng.randn(bh, lq, d), jnp.float32)
    dout = jnp.asarray(rng.randn(bh, lq, d), jnp.float32) * 0.1

    def normalized(step):
        def fn(q_, k_, v_):
            m, l, o = step(q_, k_, v_)
            return o / l[..., None]
        return fn

    xla = lambda q_, k_, v_: xla_block_step(
        q_, k_, v_, m0, l0, o0, q_offset, k_offset, causal=True)
    em, el, eo = xla(q, k, v)
    eout, vjp = jax.vjp(normalized(xla), q, k, v)
    edq, edk, edv = vjp(dout)
    # the carried o0 is a constant of the reference: take it out of
    # delta, as the ring's backward sees only this block's products
    lse = em + jnp.log(el)
    delta = jnp.sum(dout * eout, axis=-1)
    future = k_offset > q_offset + lq - 1

    for bq, bk in ((32, 16), (16, 32)):
        tiles = dict(causal=True, block_q=bq, block_k=bk, interpret=True)
        m, l, o = flash_fwd_step(q, k, v, (m0, l0, o0), q_offset,
                                 k_offset, **tiles)
        dq = flash_bwd_dq(q, k, v, dout, lse, delta, q_offset, k_offset,
                          **tiles)
        dk, dv = flash_bwd_dkv(q, k, v, dout, lse, delta, q_offset,
                               k_offset, **tiles)
        if future:
            for got, want in ((m, m0), (l, l0), (o, o0)):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want))
            for g in (dq, dk, dv):
                assert not np.asarray(g).any()
        for got, want in ((m, em), (l, el), (o, eo)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-5)
        for got, want in ((dq, edq), (dk, edk), (dv, edv)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-3, atol=2e-4)


def test_kernel_compiles_through_mosaic_on_tpu():
    """Guards the non-interpret lowering path: BlockSpec/scratch layout
    changes that only break Mosaic (not interpret mode) must fail CI on
    a TPU runner, not at first user compile."""
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip("needs a real TPU backend for Mosaic lowering")
    bh, l, d = 2, 256, 128
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(bh, l, d), jnp.float32)
    k = jnp.asarray(r.randn(bh, l, d), jnp.float32)
    v = jnp.asarray(r.randn(bh, l, d), jnp.float32)
    m = jnp.full((bh, l), -np.inf, jnp.float32)
    den = jnp.zeros((bh, l), jnp.float32)
    o = jnp.zeros((bh, l, d), jnp.float32)
    m2, l2, o2 = flash_fwd_step(q, k, v, (m, den, o), 0, 0,
                                interpret=False)
    out = np.asarray(o2 / np.asarray(l2)[..., None])
    s = np.einsum("bqd,bkd->bqk", np.asarray(q),
                  np.asarray(k)) / np.sqrt(d)
    s = np.where(np.tril(np.ones((l, l), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    ref = np.einsum("bqk,bkd->bqd", p / p.sum(-1, keepdims=True),
                    np.asarray(v))
    np.testing.assert_allclose(out, ref, atol=2e-2)


@pytest.mark.parametrize("kernel", ["hvd_flash_fwd", "hvd_flash_bwd_dq",
                                    "hvd_flash_bwd_dkv"])
def test_each_kernel_is_lowered_under_its_own_name(kernel):
    """Lowered for a TPU (no chip, no TPU library: the Mosaic call is
    made while lowering), each of the three ``pallas_call``s is a
    ``tpu_custom_call`` named as docs/perf.md names it, so that a trace
    tells the kernels apart by name and not by result shape."""
    import re

    from horovod_tpu.ops import pallas_attention as pa

    bh, l, d = 2, 256, 64
    x = jax.ShapeDtypeStruct((bh, l, d), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((bh, l), jnp.float32)
    acc = jax.ShapeDtypeStruct((bh, l, d), jnp.float32)
    calls = {
        "hvd_flash_fwd": (pa.flash_fwd_step, (x, x, x, (row, row, acc))),
        "hvd_flash_bwd_dq": (pa.flash_bwd_dq, (x, x, x, x, row, row)),
        "hvd_flash_bwd_dkv": (pa.flash_bwd_dkv, (x, x, x, x, row, row)),
    }
    fn, args = calls[kernel]
    text = jax.jit(lambda *a: fn(*a, 0, 0, interpret=False)).trace(
        *args).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "tpu_custom_call" in text
    assert set(re.findall(r'kernel_name = "(\w+)"', text)) == {kernel}
    assert f"{kernel}/pallas_call" in text      # and in the op_name


# ---------------------------------------------------------------------------
# A v head size other than the q/k head size (latent attention: 192 / 128)
# ---------------------------------------------------------------------------


def _latent_qkv(seed, bh=3, l=64, d=24, dv=16):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(bh, l, d), jnp.float32),
            jnp.asarray(rng.randn(bh, l, d), jnp.float32),
            jnp.asarray(rng.randn(bh, l, dv), jnp.float32))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kernel", ["fwd", "bwd_dq", "bwd_dkv"])
def test_kernels_take_a_v_head_size_of_their_own(kernel, causal):
    """Each of the three kernels at ``d_v != d_qk`` against
    ``xla_block_step`` and its ``jax.vjp``, in the interpreter, with a
    carried state and rectangular tiles.  Tolerances as at equal head
    sizes: f32 on both sides, the kernels' tile order of the sums
    apart."""
    from horovod_tpu.ops.pallas_attention import (flash_bwd_dkv,
                                                  flash_bwd_dq)
    from horovod_tpu.parallel.ring_attention import xla_block_step

    q, k, v = _latent_qkv(21)
    bh, lq, d = q.shape
    dv = v.shape[-1]
    rng = np.random.RandomState(22)
    m0 = jnp.asarray(rng.randn(bh, lq), jnp.float32)
    l0 = jnp.asarray(rng.rand(bh, lq) + 0.5, jnp.float32)
    o0 = jnp.asarray(rng.randn(bh, lq, dv), jnp.float32)
    dout = jnp.asarray(rng.randn(bh, lq, dv), jnp.float32) * 0.1

    def xla(q_, k_, v_):
        return xla_block_step(q_, k_, v_, m0, l0, o0, 0, 0, causal=causal)

    def normalized(q_, k_, v_):
        m, l, o = xla(q_, k_, v_)
        return o / l[..., None]

    em, el, eo = xla(q, k, v)
    assert eo.shape == (bh, lq, dv)
    eout, vjp = jax.vjp(normalized, q, k, v)
    want = dict(zip(("dq", "dk", "dv"), vjp(dout)))
    lse, delta = em + jnp.log(el), jnp.sum(dout * eout, axis=-1)
    tiles = dict(causal=causal, block_q=16, block_k=32, interpret=True)
    if kernel == "fwd":
        got = flash_fwd_step(q, k, v, (m0, l0, o0), 0, 0, **tiles)
        for g, w in zip(got, (em, el, eo)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-4, atol=2e-5)
        return
    if kernel == "bwd_dq":
        got = {"dq": flash_bwd_dq(q, k, v, dout, lse, delta, 0, 0, **tiles)}
        assert got["dq"].shape == (bh, lq, d)
    else:
        dk, dv_ = flash_bwd_dkv(q, k, v, dout, lse, delta, 0, 0, **tiles)
        assert dk.shape == k.shape and dv_.shape == v.shape
        got = {"dk": dk, "dv": dv_}
    for name, g in got.items():
        np.testing.assert_allclose(np.asarray(g), np.asarray(want[name]),
                                   rtol=2e-3, atol=2e-4)


def test_ring_attention_at_two_head_sizes_both_impls():
    """``ring_attention`` over sp = 4 with q/k heads of 24 and v heads
    of 16: the Pallas ring and its saved-LSE backward against the XLA
    ring, values and the gradients of all three inputs."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    rng = np.random.RandomState(23)
    b, l, h, d, dv = 2, 64, 2, 24, 16
    q, k = (jnp.asarray(rng.randn(b, l, h, d), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(b, l, h, dv), jnp.float32)

    def grads(impl):
        def per_device(q_, k_, v_):
            def loss(q__, k__, v__):
                out = ring_attention(q__, k__, v__, "sp", causal=True,
                                     impl=impl)
                assert out.shape == q__.shape[:3] + (dv,)
                return jnp.sum(out ** 2), out
            (_, out), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                             has_aux=True)(q_, k_, v_)
            return (out,) + g

        spec = P(None, "sp")
        return jax.jit(shard_map(per_device, mesh=mesh, check_vma=False,
                                 in_specs=(spec,) * 3,
                                 out_specs=(spec,) * 4))(q, k, v)

    for got, want in zip(grads("pallas"), grads("xla")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-4)


def test_vmem_estimate_at_equal_head_sizes_is_the_old_one():
    """``dv`` left out, or equal to ``d``, asks Mosaic for exactly what
    the kernels asked before they took a v head size (the GPT-2 cells'
    compiled steps stay the parent's); 192 / 128 at 1024x1024 bf16
    stays under the budget."""
    from horovod_tpu.ops.pallas_attention import (VMEM_BUDGET,
                                                  tile_vmem_bytes)
    from horovod_tpu.parallel.ring_attention import _block_sizes

    for d, itemsize in ((64, 2), (128, 2), (256, 4)):
        n = 1024
        old = ((n * n * (8 + itemsize)
                + 2 * (4 * n * d * itemsize + n * 128 * 4 + 2 * n * d * 4)
                + 2 * n * 128 * 4 + 2 * n * d * 4) * 5 // 4)
        assert tile_vmem_bytes(n, n, d, itemsize) == old
        assert tile_vmem_bytes(n, n, d, itemsize, d) == old
    assert tile_vmem_bytes(1024, 1024, 192, 2, 128) < VMEM_BUDGET
    assert _block_sizes(8192, 8192, 192, 2, 128) == (1024, 1024)


# ---------------------------------------------------------------------------
# The ends of the ring: the forward kernel starts and finishes the softmax
# itself, the backward kernels write the operands' type (a one-step ring
# has only ends)
# ---------------------------------------------------------------------------


def _ring_of_one(seed, d, dv, dtype, bh=2, l=64):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(bh, l, d), dtype),
            jnp.asarray(rng.randn(bh, l, d), dtype),
            jnp.asarray(rng.randn(bh, l, dv), dtype),
            jnp.asarray(rng.randn(bh, l, dv) * 0.1, dtype))


def _carried_then_epilogue(q, k, v, q_offset, k_offset, **tiles):
    """What a one-step ring ran before its kernel could start and
    finish: (-inf, 0, 0) handed in through HBM, the carried kernel, and
    the normalization and lse in XLA."""
    bh, lq, _ = q.shape
    m, l, o = flash_fwd_step(
        q, k, v, (jnp.full((bh, lq), -jnp.inf, jnp.float32),
                  jnp.zeros((bh, lq), jnp.float32),
                  jnp.zeros((bh, lq, v.shape[-1]), jnp.float32)),
        q_offset, k_offset, **tiles)
    lse = jnp.where(l > 0.0, m + jnp.log(jnp.where(l > 0.0, l, 1.0)),
                    -jnp.inf)
    return o / jnp.where(l == 0.0, 1.0, l)[..., None], lse


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("d, dv", [(64, 64), (192, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_first_and_last_step_at_once_is_carried_step_and_epilogue(
        causal, d, dv, dtype):
    """No state in, ``out`` and ``lse`` out: the same numbers as the
    carried kernel followed by the epilogue, to one ulp of fp32 (the
    division and the log are the kernel's now), and the result in the
    operands' type is that fp32 ``out`` rounded once."""
    q, k, v, _ = _ring_of_one(31, d, dv, dtype)
    tiles = dict(causal=causal, block_q=32, block_k=16, interpret=True)
    want_out, want_lse = _carried_then_epilogue(q, k, v, 0, 0, **tiles)
    out, lse, out_q = flash_fwd_step(q, k, v, None, 0, 0, last=True,
                                     **tiles)
    assert out.dtype == lse.dtype == jnp.float32 and out_q.dtype == dtype
    np.testing.assert_array_max_ulp(np.asarray(out), np.asarray(want_out),
                                    maxulp=1)
    np.testing.assert_array_max_ulp(np.asarray(lse), np.asarray(want_lse),
                                    maxulp=1)
    np.testing.assert_array_equal(
        np.asarray(out_q.astype(jnp.float32)),
        np.asarray(out.astype(dtype).astype(jnp.float32)))


def test_first_step_then_last_step_compose():
    """A ring of two, by hand: the first step takes no state and hands
    one on, the last takes it and finishes — dense attention over both
    KV halves."""
    q, k, v = (_pack(x) for x in _qkv(32))
    half = L // 2
    tiles = dict(causal=True, block_q=32, block_k=16, interpret=True)
    state = flash_fwd_step(q, k[:, :half], v[:, :half], None, 0, 0, **tiles)
    out, lse, _ = flash_fwd_step(q, k[:, half:], v[:, half:], state, 0,
                                 half, last=True, **tiles)
    want_out, want_lse = _carried_then_epilogue(q, k, v, 0, 0, **tiles)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=2e-6, atol=2e-7)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-6)


def test_a_row_that_sees_no_key():
    """A KV block that starts 16 positions after the Q chunk: the first
    16 rows see no key.  The finishing kernel gives them lse = -inf and
    out = 0 (not 0 / 0), and they add exactly nothing to any
    gradient."""
    from horovod_tpu.ops.pallas_attention import (flash_bwd_dkv,
                                                  flash_bwd_dq,
                                                  flash_fwd_step)

    q, k, v, dout = _ring_of_one(33, 24, 16, jnp.float32)
    blind = 16
    tiles = dict(causal=True, block_q=32, block_k=16, interpret=True)
    out, lse, _ = flash_fwd_step(q, k, v, None, 0, blind, last=True,
                                 **tiles)
    want_out, want_lse = _carried_then_epilogue(q, k, v, 0, blind, **tiles)
    assert np.all(np.asarray(lse[:, :blind]) == -np.inf)
    assert not np.asarray(out[:, :blind]).any()
    assert np.isfinite(np.asarray(lse[:, blind:])).all()
    np.testing.assert_array_max_ulp(np.asarray(out), np.asarray(want_out),
                                    maxulp=1)
    np.testing.assert_array_equal(np.asarray(lse[:, :blind]),
                                  np.asarray(want_lse[:, :blind]))

    def grads(dout):
        delta = jnp.sum(dout * out, axis=-1)
        return (flash_bwd_dq(q, k, v, dout, lse, delta, 0, blind, **tiles),
                *flash_bwd_dkv(q, k, v, dout, lse, delta, 0, blind,
                               **tiles))

    dq, dk, dv = grads(dout)
    assert not np.asarray(dq[:, :blind]).any()
    assert np.asarray(dq[:, blind:]).any()
    # dK and dV with the blind rows' dO taken away: bit for bit the same
    for got, want in zip((dk, dv), grads(dout.at[:, :blind].set(0.0))[1:]):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("d, dv", [(64, 64), (192, 128)])
def test_bwd_kernels_write_the_operands_type(d, dv):
    """``out_dtype=bfloat16``: the fp32 accumulators rounded once as
    they are written — the numbers the fp32 result gives when cast."""
    from horovod_tpu.ops.pallas_attention import (flash_bwd_dkv,
                                                  flash_bwd_dq,
                                                  flash_fwd_step)

    q, k, v, dout = _ring_of_one(34, d, dv, jnp.bfloat16)
    tiles = dict(causal=True, block_q=16, block_k=32, interpret=True)
    out, lse, _ = flash_fwd_step(q, k, v, None, 0, 0, last=True, **tiles)
    delta = jnp.sum(dout.astype(jnp.float32) * out, axis=-1)
    args = (q, k, v, dout, lse, delta, 0, 0)
    wide = (flash_bwd_dq(*args, **tiles), *flash_bwd_dkv(*args, **tiles))
    narrow = (flash_bwd_dq(*args, out_dtype=jnp.bfloat16, **tiles),
              *flash_bwd_dkv(*args, out_dtype=jnp.bfloat16, **tiles))
    for w, n, like in zip(wide, narrow, (q, k, v)):
        assert w.dtype == jnp.float32 and n.dtype == jnp.bfloat16
        assert n.shape == like.shape and np.asarray(w).any()
        np.testing.assert_array_equal(
            np.asarray(n.astype(jnp.float32)),
            np.asarray(w.astype(jnp.bfloat16).astype(jnp.float32)))


def _primitives(jaxpr):
    """Every primitive's name in a jaxpr and in the jaxprs its
    equations hold (shard_map, custom_vjp, pallas_call, cond...)."""
    from jax.extend import core as jcore

    def inner(value):
        if isinstance(value, jcore.ClosedJaxpr):
            yield value.jaxpr
        elif isinstance(value, jcore.Jaxpr):
            yield value
        elif isinstance(value, (tuple, list)):
            for item in value:
                yield from inner(item)

    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in inner(value):
                names.extend(_primitives(sub))
    return names


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_a_ring_of_one_rotates_nothing_and_loops_over_nothing(dtype):
    """``jax.grad`` through ``ring_attention(impl="pallas")`` on a
    one-device ``sp`` axis: three kernel calls, no ``ppermute`` (not of
    K and V, not of dK and dV) and no loop; values and gradients are
    the dense reference's."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    q, k, v = (x.astype(dtype) for x in _qkv(35))

    def loss(a, b_, c, attention):
        return jnp.sum(attention(a, b_, c).astype(jnp.float32) ** 2)

    ring = lambda a, b_, c: ring_attention(a, b_, c, "sp", causal=True,
                                           impl="pallas")
    fn = shard_map(
        lambda a, b_, c: jax.value_and_grad(loss, argnums=(0, 1, 2))(
            a, b_, c, ring),
        mesh=mesh, check_vma=False, in_specs=(P(None, "sp"),) * 3,
        out_specs=(P(), (P(None, "sp"),) * 3))
    names = _primitives(jax.make_jaxpr(fn)(q, k, v).jaxpr)
    assert names.count("pallas_call") == 3, names
    assert not {"ppermute", "while", "scan"} & set(names), names

    value, grads = jax.jit(fn)(q, k, v)
    want_value, want_grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(
        q, k, v, lambda a, b_, c: reference_attention(a, b_, c, True))
    tol = (dict(rtol=2e-3, atol=2e-4) if dtype == jnp.float32
           else dict(rtol=5e-2, atol=5e-2))
    np.testing.assert_allclose(float(value), float(want_value),
                               rtol=tol["rtol"])
    for got, want in zip(grads, want_grads):
        assert got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol)
