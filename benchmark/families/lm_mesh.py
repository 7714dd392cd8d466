"""Family ``lm_mesh``: a decoder-only language model through the
flagship path — ``TransformerConfig`` + ``init_params`` +
``shard_params`` + ``make_train_step`` on a ``make_mesh`` mesh.

No ``attn_impl`` is forced and no ``HOROVOD_*`` variable is set: the
attention path is whatever ``ring_attention.auto_impl`` picks for the
cell's shape.

The plain reference reads the system's parameter tree and computes the
same loss in float32 with ``jax.numpy`` and ``lax.scan`` only: no
kernel, no ``shard_map``, attention as a masked softmax over query
blocks under ``jax.checkpoint`` so that its backward pass fits at long
sequences.
"""

from __future__ import annotations

import math

from benchmark import agreement

# Step-0 loss and gradient norm of the system (bf16 matrix products and
# a bf16 residual stream, f32 parameters) against the float32 reference,
# relative.  On the chip the loss came within 5e-6 to 1.3e-5 and the norm
# within 3e-5 to 1.0e-3 (PR 22, both gpt2-124m cells, two seeds each);
# the bounds leave ten times that.  Without the causal mask the loss at
# random weights barely moves but the gradient norm does; a dropped layer
# or a missing term of a backward kernel moves the norm by tens of
# percent.
LOSS_RTOL = 1e-3
GRAD_NORM_RTOL = 1e-2


def _sizes(config: dict) -> dict:
    d = config["n_embd"]
    return dict(vocab=config["vocab_size"], d_model=d,
                n_heads=config["n_head"], head_dim=d // config["n_head"],
                n_layers=config["n_layer"],
                d_ff=config["n_inner"] or 4 * d)


# ---------------------------------------------------------------------------
# Operations the architecture and its kernels require, from shapes
# ---------------------------------------------------------------------------


def model_flops_per_sample(config: dict, job: dict) -> float:
    """Forward plus backward of one sequence: 3 x forward, 2 FLOPs a
    multiply-accumulate.  Forward: the four matrices of every block and
    the output head for every token, and causal attention at half the
    square (QK^T and PV over the keys a query may see).  Nothing that is
    recomputed is counted; norms, GELU and softmax are left out."""
    s, seq = _sizes(config), job["seq"]
    attn_width = s["n_heads"] * s["head_dim"]
    per_token = s["n_layers"] * (4 * s["d_model"] * attn_width
                                 + 2 * s["d_model"] * s["d_ff"])
    per_token += s["d_model"] * s["vocab"]                # tied head
    attention = s["n_layers"] * attn_width * seq * (seq + 1)   # 2 products
    return 3.0 * 2.0 * (seq * per_token + attention)


def kernel_costs(config: dict, job: dict) -> dict:
    """What one train step requires of the flash-attention kernels, over
    all layers, per chip: ``{"flash_attn": {"flops", "bytes"}}``.

    FLOPs: seven causal (rows x keys x head_dim) matrix products a
    layer.  Forward QK^T and PV; backward QK^T once more (flash
    attention keeps no scores), dO V^T, P^T dO, dS K and dS^T Q.  The
    program's two backward kernels each recompute QK^T and dO V^T, nine
    products in all; the two repeats are not required and not counted.
    Bytes: every tensor read or written once, bf16: q, k, v in and o out
    forward; q, k, v, o, dO in and dq, dk, dv out backward; plus the f32
    row statistics."""
    s, seq = _sizes(config), job["seq"]
    mesh = job["mesh"]
    rows = job["batch_per_chip"] * s["n_heads"] // mesh["tp"]
    layers = s["n_layers"] // mesh["pp"]
    # 2 FLOPs a multiply-accumulate over seq * (seq + 1) / 2 score pairs
    product = float(rows * s["head_dim"] * seq * (seq + 1))
    tensor = rows * seq * s["head_dim"]
    return {"flash_attn": {
        "flops": layers * 7 * product,
        "bytes": layers * (12 * 2 * tensor + 2 * 4 * rows * seq)}}


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

_QUERY_BLOCK = 1024


def _rmsnorm(x, gain):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * gain


def _gelu_tanh(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _causal_attention(q, k, v):
    """q, k, v: (batch, seq, heads, head_dim) float32.  Query blocks of
    ``_QUERY_BLOCK`` rows, each a plain masked softmax over all keys,
    recomputed in the backward pass."""
    import jax
    import jax.numpy as jnp

    seq, head_dim = q.shape[1], q.shape[-1]
    block = min(_QUERY_BLOCK, seq)
    keys = jnp.arange(seq)

    @jax.checkpoint
    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(head_dim)
        seen = (start + jnp.arange(block))[:, None] >= keys[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    blocks = jax.lax.map(one, jnp.arange(0, seq, block))
    return jnp.moveaxis(blocks, 0, 1).reshape(q.shape)


def reference_loss(config: dict, params: dict, tokens, targets):
    """Mean next-token cross-entropy over every position, float32."""
    import jax
    import jax.numpy as jnp

    s = _sizes(config)
    batch, seq = tokens.shape

    @jax.checkpoint
    def block(x, lp):
        h = _rmsnorm(x, lp["ln1"])
        qkv = (h @ lp["wqkv"]).reshape(batch, seq, 3, s["n_heads"],
                                       s["head_dim"])
        attn = _causal_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
        x = x + attn.reshape(batch, seq, -1) @ lp["wo"]
        h = _rmsnorm(x, lp["ln2"])
        return x + _gelu_tanh(h @ lp["w1"]) @ lp["w2"], None

    @jax.checkpoint
    def nll_of_rows(x_rows, target_rows):
        logp = jax.nn.log_softmax(x_rows @ params["embed"].T, axis=-1)
        return -jnp.sum(jnp.take_along_axis(
            logp, target_rows[..., None], axis=-1))

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens] + params["pos"][jnp.arange(seq)]
        x, _ = jax.lax.scan(block, x, params["layers"])
        x = _rmsnorm(x, params["ln_f"])
        rows = min(_QUERY_BLOCK, seq)
        chunks = (batch, seq // rows, rows)
        total = jnp.sum(jax.lax.map(
            lambda xt: nll_of_rows(*xt),
            (jnp.moveaxis(x.reshape(*chunks, -1), 1, 0),
             jnp.moveaxis(targets.reshape(chunks), 1, 0))))
    return total / (batch * seq)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


class _DeviceRandn:
    """Stands in for the ``numpy.random.RandomState`` that
    ``init_params`` draws from, and draws on the device: under ``jit``
    the program's own initialiser then makes every weight in one call
    (124 M normals take the host 12 s)."""

    def __init__(self, key):
        self._key, self._draws = key, 0

    def randn(self, *shape):
        import jax
        import jax.numpy as jnp

        self._draws += 1
        return jax.random.normal(
            jax.random.fold_in(self._key, self._draws), shape, jnp.float32)


class Trainer:
    """Builds the flagship trainer; ``hvd.init()`` has returned."""

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
        self.cfg = cfg = transformer.TransformerConfig(
            max_seq=max(config["n_positions"], seq),
            dtype=config["compute_dtype"], **_sizes(config))
        self.mesh = mesh = make_mesh(**axes, devices=jax.devices()[:chips])
        opt = optax.adamw(config["optimizer"]["learning_rate"])
        params = transformer.shard_params(
            jax.jit(lambda key: transformer.init_params(
                _DeviceRandn(key), cfg))(jax.random.PRNGKey(seed)),
            cfg, mesh)
        self.state = (params, opt.init(params))
        self._step = transformer.make_train_step(cfg, mesh, opt)

        pool = job["batch_pool"]
        rows = job["batch_per_chip"] * axes["dp"]
        data = NamedSharding(mesh, P("dp", "sp"))

        def make_pool(key):
            ids = jax.random.randint(key, (pool, 2, rows, seq), 0,
                                     cfg.vocab, jnp.int32)
            return tuple((ids[i, 0], ids[i, 1]) for i in range(pool))

        self.batches = jax.jit(make_pool, out_shardings=data)(
            jax.random.PRNGKey(seed + 1))
        self.samples_per_step = rows
        self.units_per_sample = seq
        self.compiled = None

    def compile(self) -> None:
        self.compiled = self._step.lower(
            *self.state, *self.batches[0]).compile()

    def compiled_text(self) -> str:
        return self.compiled.as_text()

    def run_step(self, i: int):
        """Dispatch step ``i``; returns its loss, still on the device."""
        *state, loss = self.compiled(
            *self.state, *self.batches[i % len(self.batches)])
        self.state = tuple(state)
        return loss

    def params(self):
        return self.state[0]

    def check_reference(self) -> dict:
        """Step-0 loss and global gradient norm on the first
        ``reference_samples`` sequences: the system's ``loss_fn`` and its
        backward pass over the cell's mesh, reduced as
        ``make_train_step`` reduces them, against the float32
        reference."""
        import functools

        import jax
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.models import transformer
        from horovod_tpu.parallel.sharding import (grad_reduce_axes,
                                                   tree_map_with_specs)

        cfg, params = self.cfg, self.state[0]
        n = self.job["reference_samples"]
        tokens, targets = (a[:n] for a in self.batches[0])
        specs = transformer.param_specs(cfg)

        def per_device(p, tok, tgt):
            loss, grads = jax.value_and_grad(transformer.loss_fn)(
                p, tok, tgt, cfg)
            grads = tree_map_with_specs(
                lambda g, spec: (lax.psum(g, grad_reduce_axes(spec))
                                 if grad_reduce_axes(spec) else g),
                grads, specs)
            return lax.psum(loss, ("dp", "sp")), grads

        loss, grads = jax.jit(shard_map(
            per_device, mesh=self.mesh, check_vma=False,
            in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
            out_specs=(P(), specs)))(params, tokens, targets)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(functools.partial(
            reference_loss, self.config)))(params, tokens, targets)
        return agreement.against_reference(
            loss, grads, ref_loss, ref_grads, LOSS_RTOL, GRAD_NORM_RTOL)
