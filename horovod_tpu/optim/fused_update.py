"""Pallas-fused optimizer tail (``HOROVOD_FUSED_UPDATE=1``).

The post-reduction weight-update chain — unscale by world size, dtype
cast, momentum / Adam moment update, bias correction, step scaling —
lowers as a string of small elementwise XLA ops, each one a full HBM
round trip over every flat gradient buffer.  arXiv:2004.13336 showed
the fused weight-update path is the lever that dominates at scale;
this module collapses that chain into **one Pallas kernel per flat
per-dtype buffer** (the :mod:`horovod_tpu.ops.quantization` idiom:
fused TPU kernel, bit-identical jnp fallback off-TPU, interpret-mode
test hook via ``HOROVOD_QUANT_PALLAS=1``).

**Bit-exactness contract.** The fused math mirrors optax's update
expressions verbatim (``optax.sgd`` / ``optax.trace`` /
``optax.scale_by_adam`` + ``scale_by_learning_rate``), so
``HOROVOD_FUSED_UPDATE=1`` is bit-exact against the unfused chain —
the parity matrix in ``tests/test_fused_update.py`` proves it per
dtype-group x optimizer x ZeRO stage x int8-EF cell (interpret mode,
CPU).  Compiled by Mosaic on a v5e, SGD, momentum and Adam's moments
(multiplies and adds) stay bit-exact; Adam's update divides and takes
a square root, which Mosaic and XLA round differently — 4.7e-7
relative at most over a ResNet-50-sized buffer (chip_smoke.py, PR 21).
That contract is
only possible when the hyperparameters are knowable, so fusion applies
to optimizers built by :func:`sgd` / :func:`adam` below (plain optax
``GradientTransformation``s are closures — their hyperparameters are
not introspectable).  They ARE the optax optimizers (same init, same
update, same state pytree) plus a :class:`FusedSpec` tag; with the
knob off, or wrapped by ``optax.chain``, they behave identically to
``optax.sgd``/``optax.adam``.  ``HOROVOD_FUSED_UPDATE=1`` with an
untagged optimizer warns once and runs unfused — the knob can never
change results, only fuse them.

The fused tail is the third piece of the update path's kernel story:
the wire side (residual-add into the fused buffer, quant pack/unpack)
is already fused by the PR 1/PR 10 Pallas codecs; this closes the
optimizer side.  Selection is local to each rank (the update runs
after the wire), so no round-0 handshake entry is needed.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from horovod_tpu.common import config as _config
from horovod_tpu.common import logging as _log
from horovod_tpu.common.platform import pallas_interpret
from horovod_tpu.runtime import metrics as _metrics

# Row tile: (16, 128) covers the native f32 (8, 128) and bf16 (16, 128)
# tilings; flat buffers are padded up to one tile and sliced back.
_ROW_TILE = 16
_LANES = 128

_M_FUSED = _metrics.gauge(
    "hvd_fused_update",
    "1 when the Pallas-fused optimizer tail is active for the "
    "last-constructed DistributedOptimizer, 0 when requested but "
    "unavailable (untagged optimizer / unrecognized state).")

_warned: set = set()


class FusedSpec(NamedTuple):
    """Hyperparameters of a fusable update, attached to the optimizer
    at construction (kind: ``sgd`` | ``momentum`` | ``adam``)."""
    kind: str
    lr: float
    momentum: float = 0.0
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    eps_root: float = 0.0


class FusableTransformation(NamedTuple):
    """An optax ``GradientTransformation`` (same ``init``/``update``
    fields, duck-type compatible everywhere) carrying the
    :class:`FusedSpec` the fused tail needs.  A separate NamedTuple
    because optax's has ``__slots__`` — attributes cannot be attached
    to it after the fact."""
    init: Callable
    update: Callable
    fused_spec: FusedSpec


def sgd(learning_rate: float, momentum: float | None = None
        ) -> FusableTransformation:
    """``optax.sgd`` tagged for the fused tail (momentum ``None``/0
    means plain SGD; schedules are not fusable — pass a float)."""
    import optax

    _require_float("learning_rate", learning_rate)
    if momentum is not None:
        _require_float("momentum", momentum)
    inner = optax.sgd(learning_rate, momentum=momentum)
    # optax adds the trace transform for ANY non-None momentum —
    # including 0.0 — so the spec kind must follow the same rule or the
    # state layout never matches and fusion silently disables.
    spec = FusedSpec("sgd" if momentum is None else "momentum",
                     float(learning_rate), float(momentum or 0.0))
    return FusableTransformation(inner.init, inner.update, spec)


def adam(learning_rate: float, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8, eps_root: float = 0.0
         ) -> FusableTransformation:
    """``optax.adam`` tagged for the fused tail (float hyperparameters
    only — schedules are not fusable)."""
    import optax

    for name, v in (("learning_rate", learning_rate), ("b1", b1),
                    ("b2", b2), ("eps", eps), ("eps_root", eps_root)):
        _require_float(name, v)
    inner = optax.adam(learning_rate, b1=b1, b2=b2, eps=eps,
                       eps_root=eps_root)
    spec = FusedSpec("adam", float(learning_rate), 0.0, float(b1),
                     float(b2), float(eps), float(eps_root))
    return FusableTransformation(inner.init, inner.update, spec)


def _require_float(name: str, v) -> None:
    if callable(v):
        raise TypeError(
            f"fused_update.{name} must be a float (schedules change "
            "per step and cannot be baked into the fused kernel); use "
            "plain optax for scheduled runs.")


def spec_of(optimizer) -> FusedSpec | None:
    return getattr(optimizer, "fused_spec", None)


def enabled() -> bool:
    return bool(_config.get("fused_update"))


def active() -> bool:
    """Whether the fused tail actually ran for the last-constructed
    optimizer (the ``hvd_fused_update`` gauge): ``enabled()`` records
    the request, this records the outcome — trace-time fallbacks
    (untagged optimizer, unrecognized state layout, non-float group)
    clear it."""
    return bool(_M_FUSED.value())


def _warn_once(category: str, msg: str) -> None:
    if category not in _warned:
        _warned.add(category)
        _log.warning(f"fused-update: {msg}")


def resolve_spec(optimizer) -> FusedSpec | None:
    """The spec the DistributedOptimizer should fuse with, or ``None``
    (knob off, or optimizer untagged — warned once, never fatal: the
    knob can only fuse results, not change them)."""
    if not enabled():
        _M_FUSED.set(0)
        return None
    spec = spec_of(optimizer)
    if spec is None:
        _M_FUSED.set(0)
        _warn_once(
            "untagged",
            "HOROVOD_FUSED_UPDATE=1 but the wrapped optimizer carries "
            "no FusedSpec (its hyperparameters are closure-internal, "
            "so a bit-exact fused kernel cannot be built); construct "
            "it with hvd.fused_update.sgd/adam to fuse. Running the "
            "unfused optax chain.")
        return None
    _M_FUSED.set(1)
    return spec


# ---------------------------------------------------------------------------
# Kernel / fallback selection — the quantization-module contract:
# HOROVOD_QUANT_PALLAS = auto (Pallas on TPU, jnp elsewhere) | 1 (force
# Pallas; interpret mode off-TPU — the bit-identity test hook) | 0.
# ---------------------------------------------------------------------------


def _use_pallas() -> bool:
    mode = str(_config.get("quant_pallas")).strip().lower()
    if mode in ("0", "off", "jnp", "false"):
        return False
    if mode in ("1", "on", "force", "true"):
        return True
    return jax.default_backend() == "tpu"


def _pad2d(flat):
    n = flat.shape[0]
    pad = (-n) % (_ROW_TILE * _LANES)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, _LANES), n


def _unpad(x2d, n: int):
    return x2d.reshape(-1)[:n]


# --- the update math, written once -----------------------------------------
# These expressions mirror optax bit-for-bit (optax.scale ->
# ``(-lr) * g``; optax.trace -> ``g + decay * t``; optax.scale_by_adam
# -> the moment/bias-correction/step lines below).  The Pallas kernels
# and the jnp fallback both call them, so the two paths cannot drift.


def _prep_grad(g, navg: int, dtype):
    # the unfused chain's ``shard = shard / n`` (Average only, wire
    # dtype) followed by ``shard.astype(group_dtype)``
    if navg > 1:
        g = g / navg
    return g.astype(dtype)


def _sgd_math(g, neg_lr: float):
    return neg_lr * g


def _momentum_math(g, t, decay: float, neg_lr: float):
    t2 = g + decay * t
    return neg_lr * t2, t2


def _adam_math(g, mu, nu, bc1, bc2, spec: FusedSpec):
    mu2 = (1 - spec.b1) * g + spec.b1 * mu
    nu2 = (1 - spec.b2) * (g * g) + spec.b2 * nu
    mu_hat = mu2 / bc1.astype(mu2.dtype)
    nu_hat = nu2 / bc2.astype(nu2.dtype)
    u = (-spec.lr) * (mu_hat / (jnp.sqrt(nu_hat + spec.eps_root)
                                + spec.eps))
    return u, mu2, nu2


def _safe_int32_increment(count):
    maxi = jnp.iinfo(jnp.int32).max
    return jnp.where(count < maxi, count + jnp.array(1, jnp.int32),
                     maxi)


@functools.partial(jax.jit, static_argnums=(0, 1), inline=True)
def _bias_correction_pair(b1: float, b2: float, count_inc):
    return 1 - b1 ** count_inc, 1 - b2 ** count_inc


def bias_corrections(spec: FusedSpec, count_inc):
    """(1 - b**t) pair, computed exactly like optax's
    ``tree_bias_correction`` (f32 scalar, cast to the moment dtype at
    the division site inside the kernel).  Jitted like optax's helper
    on purpose: on the eager path XLA's compiled scalar ``pow`` and
    the op-by-op dispatch path can differ in the last ulp, and the
    bit-exactness contract needs both sides to take the compiled
    one."""
    return _bias_correction_pair(spec.b1, spec.b2, count_inc)


# --- Pallas kernels ---------------------------------------------------------


def _sgd_kernel(g_ref, o_ref, *, navg: int, neg_lr: float):
    g = _prep_grad(g_ref[...], navg, o_ref.dtype)
    o_ref[...] = _sgd_math(g, neg_lr)


def _momentum_kernel(g_ref, t_ref, o_ref, t_out_ref, *, navg: int,
                     decay: float, neg_lr: float):
    g = _prep_grad(g_ref[...], navg, t_ref.dtype)
    u, t2 = _momentum_math(g, t_ref[...], decay, neg_lr)
    o_ref[...] = u
    t_out_ref[...] = t2


def _adam_kernel(g_ref, mu_ref, nu_ref, aux_ref, o_ref, mu_out, nu_out,
                 *, navg: int, spec: FusedSpec):
    g = _prep_grad(g_ref[...], navg, mu_ref.dtype)
    bc1 = aux_ref[0, 0]
    bc2 = aux_ref[1, 0]
    u, mu2, nu2 = _adam_math(g, mu_ref[...], nu_ref[...], bc1, bc2,
                             spec)
    o_ref[...] = u
    mu_out[...] = mu2
    nu_out[...] = nu2


def _row_spec(rows):
    from jax.experimental import pallas as pl

    return pl.BlockSpec((_ROW_TILE, _LANES), lambda i: (i, 0))


def _aux_spec():
    from jax.experimental import pallas as pl

    # every grid step reads the same (bc1, bc2) scalar block
    return pl.BlockSpec((_ROW_TILE, _LANES), lambda i: (0, 0))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _sgd_pallas(g2d, dtype, navg: int, neg_lr: float, interpret: bool):
    from jax.experimental import pallas as pl

    rows = g2d.shape[0]
    return pl.pallas_call(
        functools.partial(_sgd_kernel, navg=navg, neg_lr=neg_lr),
        grid=(rows // _ROW_TILE,),
        in_specs=[_row_spec(rows)],
        out_specs=_row_spec(rows),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), dtype),
        interpret=interpret,
    )(g2d)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _momentum_pallas(g2d, t2d, navg: int, decay: float, neg_lr: float,
                     interpret: bool = False):
    from jax.experimental import pallas as pl

    rows = g2d.shape[0]
    return pl.pallas_call(
        functools.partial(_momentum_kernel, navg=navg, decay=decay,
                          neg_lr=neg_lr),
        grid=(rows // _ROW_TILE,),
        in_specs=[_row_spec(rows)] * 2,
        out_specs=[_row_spec(rows)] * 2,
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), t2d.dtype)] * 2,
        interpret=interpret,
    )(g2d, t2d)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _adam_pallas(g2d, mu2d, nu2d, aux, navg: int, spec: FusedSpec,
                 interpret: bool = False):
    from jax.experimental import pallas as pl

    rows = g2d.shape[0]
    return pl.pallas_call(
        functools.partial(_adam_kernel, navg=navg, spec=spec),
        grid=(rows // _ROW_TILE,),
        in_specs=[_row_spec(rows)] * 3 + [_aux_spec()],
        out_specs=[_row_spec(rows)] * 3,
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), mu2d.dtype)] * 3,
        interpret=interpret,
    )(g2d, mu2d, nu2d, aux)


def _aux_block(bc1, bc2):
    aux = jnp.zeros((_ROW_TILE, _LANES), jnp.float32)
    return aux.at[0, :].set(bc1.astype(jnp.float32)).at[1, :].set(
        bc2.astype(jnp.float32))


# --- per-buffer dispatch ----------------------------------------------------


def _apply_buffer(spec: FusedSpec, g, mu, nu, bc1, bc2, navg: int,
                  dtype):
    """One flat buffer through the fused tail: ``(update, new_mu,
    new_nu)`` (``None`` moments for kinds without them)."""
    dtype = jnp.dtype(dtype)
    if g.size == 0:
        z = jnp.zeros((0,), dtype)
        return z, (z if mu is not None else None), \
            (z if nu is not None else None)
    if _use_pallas():
        interpret = pallas_interpret()
        g2d, n = _pad2d(g.reshape(-1))
        if spec.kind == "sgd":
            o = _sgd_pallas(g2d, dtype, navg, -spec.lr, interpret)
            return _unpad(o, n), None, None
        if spec.kind == "momentum":
            t2d, _ = _pad2d(mu.reshape(-1))
            o, t2 = _momentum_pallas(g2d, t2d, navg, spec.momentum,
                                     -spec.lr, interpret)
            return _unpad(o, n), _unpad(t2, n), None
        mu2d, _ = _pad2d(mu.reshape(-1))
        nu2d, _ = _pad2d(nu.reshape(-1))
        o, m2, v2 = _adam_pallas(g2d, mu2d, nu2d, _aux_block(bc1, bc2),
                                 navg, spec, interpret)
        return _unpad(o, n), _unpad(m2, n), _unpad(v2, n)
    # jnp fallback: the same math, op for op
    g = _prep_grad(g, navg, dtype)
    if spec.kind == "sgd":
        return _sgd_math(g, -spec.lr), None, None
    if spec.kind == "momentum":
        u, t2 = _momentum_math(g, mu, spec.momentum, -spec.lr)
        return u, t2, None
    u, m2, v2 = _adam_math(g, mu, nu, bc1, bc2, spec)
    return u, m2, v2


# --- state structure recognition -------------------------------------------


def _split_state(spec: FusedSpec, inner_state, grads):
    """Match the wrapped optax state against ``grads`` (a list of flat
    buffers or gradient leaves): ``(count, mus, nus, treedef)`` or
    ``None`` when the structure is not the expected optax layout
    (chain(trace?, scale) / chain(scale_by_adam, scale)) — the caller
    then runs the unfused update (fail-open, like the AOT cache's
    fail-closed compile)."""
    leaves, treedef = jax.tree_util.tree_flatten(inner_state)
    k = len(grads)

    # ``grads`` entries only need .shape/.dtype (arrays, tracers, or
    # jax.ShapeDtypeStruct views — the groups path passes structs so no
    # casted copy is ever materialized just for matching)
    def match(sub):
        return len(sub) == k and all(
            tuple(jnp.shape(a)) == tuple(g.shape)
            and jnp.asarray(a).dtype == jnp.dtype(g.dtype)
            for a, g in zip(sub, grads))

    if spec.kind == "sgd":
        if not leaves:
            return None, None, None, treedef
    elif spec.kind == "momentum":
        if match(leaves):
            return None, list(leaves), None, treedef
    elif spec.kind == "adam":
        if len(leaves) == 1 + 2 * k and jnp.shape(leaves[0]) == () \
                and match(leaves[1:1 + k]) and match(leaves[1 + k:]):
            return leaves[0], list(leaves[1:1 + k]), \
                list(leaves[1 + k:]), treedef
    return None


def _rebuild_state(spec: FusedSpec, treedef, count_inc, mus, nus):
    if spec.kind == "sgd":
        leaves = []
    elif spec.kind == "momentum":
        leaves = mus
    else:
        leaves = [count_inc] + mus + nus
    return jax.tree_util.tree_unflatten(treedef, leaves)


# --- the two entry points the DistributedOptimizer calls -------------------


def fused_update_groups(spec: FusedSpec, shards, inner_state,
                        navg: int, dtypes):
    """Fused replacement for ``update_fn(gshards, inner_state)`` on the
    ZeRO (stage >= 1) paths: ``shards`` are the raw post-scatter flat
    buffers (wire dtype, pre-unscale), ``dtypes`` the per-group target
    dtypes, ``navg`` the Average divisor (1 for Sum / already-averaged
    eager shards).  Returns ``(update_shards, new_inner_state)`` or
    ``None`` when a group is non-float or the state layout is
    unrecognized."""
    if not shards or not all(
            jnp.issubdtype(jnp.dtype(d), jnp.floating) for d in dtypes):
        # same guard as the tree path: float update math into an
        # integer dtype group would crash the kernel (or silently
        # drift the unfused chain's integer state dtype to float)
        _M_FUSED.set(0)
        _warn_once(
            "int-group",
            "a non-float dtype group is present; running the unfused "
            "chain")
        return None
    # moments live in the GROUP dtype (the unfused chain casts before
    # update_fn), so match against shape/dtype VIEWS in that dtype —
    # no casted copy is materialized for the comparison
    views = [jax.ShapeDtypeStruct(tuple(jnp.shape(s)), jnp.dtype(d))
             for s, d in zip(shards, dtypes)]
    parts = _split_state(spec, inner_state, views)
    if parts is None:
        _M_FUSED.set(0)
        _warn_once(
            "state",
            f"wrapped {spec.kind} state does not match the expected "
            "optax layout; running the unfused chain")
        return None
    count, mus, nus, treedef = parts
    count_inc = bc1 = bc2 = None
    if spec.kind == "adam":
        count_inc = _safe_int32_increment(count)
        bc1, bc2 = bias_corrections(spec, count_inc)
    outs, new_mus, new_nus = [], [], []
    for i, s in enumerate(shards):
        u, m2, v2 = _apply_buffer(
            spec, jnp.asarray(s),
            mus[i] if mus is not None else None,
            nus[i] if nus is not None else None,
            bc1, bc2, navg, dtypes[i])
        outs.append(u)
        if m2 is not None:
            new_mus.append(m2)
        if v2 is not None:
            new_nus.append(v2)
    return outs, _rebuild_state(spec, treedef, count_inc, new_mus,
                                new_nus)


def fused_update_tree(spec: FusedSpec, grads, inner_state):
    """Fused replacement for the replicated (stage 0) update: one
    kernel per gradient leaf (the leaves ARE the flat buffers there —
    reduction already averaged, so no unscale).  Returns ``(updates,
    new_inner_state)`` or ``None`` when a leaf is non-float or the
    state layout is unrecognized."""
    leaves, gdef = jax.tree_util.tree_flatten(grads)
    leaves = [jnp.asarray(g) for g in leaves]
    if not leaves or not all(
            jnp.issubdtype(g.dtype, jnp.floating) for g in leaves):
        _M_FUSED.set(0)
        _warn_once(
            "int-group",
            "a non-float gradient leaf is present; running the "
            "unfused chain")
        return None
    parts = _split_state(spec, inner_state, leaves)
    if parts is None:
        _M_FUSED.set(0)
        _warn_once(
            "state",
            f"wrapped {spec.kind} state does not match the expected "
            "optax layout; running the unfused chain")
        return None
    count, mus, nus, treedef = parts
    count_inc = bc1 = bc2 = None
    if spec.kind == "adam":
        count_inc = _safe_int32_increment(count)
        bc1, bc2 = bias_corrections(spec, count_inc)
    outs, new_mus, new_nus = [], [], []
    for i, g in enumerate(leaves):
        u, m2, v2 = _apply_buffer(
            spec, g.reshape(-1),
            mus[i].reshape(-1) if mus is not None else None,
            nus[i].reshape(-1) if nus is not None else None,
            bc1, bc2, 1, g.dtype)
        outs.append(u.reshape(g.shape))
        if m2 is not None:
            new_mus.append(m2.reshape(g.shape))
        if v2 is not None:
            new_nus.append(v2.reshape(g.shape))
    return (jax.tree_util.tree_unflatten(gdef, outs),
            _rebuild_state(spec, treedef, count_inc, new_mus, new_nus))
