"""The selective state-space recurrence of a Mamba-2 mixer, computed as a
chunked scan (the "state-space dual" form).

Absent from the reference (SURVEY §2.7); TPU extension.  Per head, with
a state ``S`` of ``P x N`` (head size x state size), a time step
``dt_t > 0`` and a decay rate ``A < 0``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S_0 = 0
    y_t = S_t C_t + D x_t

``B`` and ``C`` come in groups: head ``h`` reads group ``h // (H / G)``.

The sequence is cut into chunks of ``chunk`` steps.  Inside a chunk the
outputs are one masked product, ``Y = (L o C B^T)(dt o X)`` with
``L_ij = exp(sum_{j<k<=i} dt_k A)`` for ``i >= j``; a chunk's end state
is the ``B``-weighted sum of its decayed inputs; the states are passed
from chunk to chunk by a loop of ``L / chunk`` steps, and what the
incoming state gives, ``C S`` decayed to the row's own step, is added.
No loop over time steps and no matrix over the whole sequence.

Precision: the logarithms of the decays are summed in float32 and only
differences of those sums meet ``exp``, so nothing is divided by a decay
that has underflowed; the state is carried in float32; the products take
operands in ``x``'s type and accumulate in float32.  The backward pass
is JAX's own derivative of this form.

Imported only where a layer pattern asks for a state-space layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def ssm_scan(x, dt, a, b, c, d, chunk: int):
    """``(y, least)``.  ``x``: (B, L, H, P) in the compute dtype;
    ``dt``: (B, L, H) float32, positive; ``a``: (H,) float32, negative;
    ``b``, ``c``: (B, L, G, N) in the compute dtype; ``d``: (H,)
    float32.  ``y``: (B, L, H, P) float32.  ``least``: the least
    logarithm of a whole chunk's decay, a float32 scalar that takes no
    gradient (how near ``exp`` comes to underflow).  A sequence that is
    no whole number of chunks is padded with steps that neither decay
    nor feed the state."""
    with jax.named_scope("hvd_ssm_scan"):
        return _chunked(x, dt, a, b, c, d, chunk)


def _chunked(x, dt, a, b, c, d, chunk):
    batch, length, heads, size = x.shape
    groups, state = b.shape[2:]
    per = heads // groups
    cd = x.dtype
    pad = -length % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    n = (length + pad) // chunk

    # (batch, chunks, steps, groups, heads of a group, ...)
    x = x.reshape(batch, n, chunk, groups, per, size)
    dt = dt.reshape(batch, n, chunk, groups, per)
    b = b.reshape(batch, n, chunk, groups, state)
    c = c.reshape(batch, n, chunk, groups, state)
    # log-decays, summed within the chunk up to and with each step
    cum = jnp.cumsum(dt * a.reshape(groups, per), axis=2)
    whole = cum[:, :, -1]                       # (batch, n, groups, per)
    fed = x.astype(jnp.float32) * dt[..., None]           # dt o X

    # inside a chunk: Y = (L o C B^T)(dt o X)
    rows = jnp.arange(chunk)
    seen = rows[:, None] >= rows[None, :]
    span = jnp.moveaxis(cum, 2, -1)             # (batch, n, groups, per, i)
    decay = jnp.exp(jnp.where(seen, span[..., :, None] - span[..., None, :],
                              -jnp.inf))
    scores = jnp.einsum("zkign,zkjgn->zkgij", c, b,
                        preferred_element_type=jnp.float32)
    y = jnp.einsum("zkgrij,zkjgrp->zkigrp",
                   (scores[:, :, :, None] * decay).astype(cd),
                   fed.astype(cd), preferred_element_type=jnp.float32)

    # a chunk's own end state, and the states passed along the chunks
    to_end = jnp.exp(whole[:, :, None] - cum)
    own = jnp.einsum("zkjgn,zkjgrp->zkgrpn", b,
                     (fed * to_end[..., None]).astype(cd),
                     preferred_element_type=jnp.float32)

    def pass_on(carried, chunk_):
        kept, added = chunk_
        return kept[..., None, None] * carried + added, carried

    _, entering = lax.scan(
        pass_on, jnp.zeros(own.shape[:1] + own.shape[2:], jnp.float32),
        (jnp.moveaxis(jnp.exp(whole), 1, 0), jnp.moveaxis(own, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)     # (batch, n, g, r, P, N)
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "zkign,zkgrpn->zkigrp", c, entering.astype(cd),
        preferred_element_type=jnp.float32)

    y = y + d.reshape(groups, per)[:, :, None] * x.astype(jnp.float32)
    y = y.reshape(batch, n * chunk, heads, size)[:, :length]
    return y, lax.stop_gradient(jnp.min(whole))


def scan_reference(x, dt, a, b, c, d):
    """Plain golden model for tests: the recurrence itself, one time
    step after the other, in the operands' type (float32 in the tests).
    Same arguments as :func:`ssm_scan` but for the chunk; returns
    ``y``."""
    batch, _, heads, size = x.shape
    groups, state = b.shape[2:]
    per = heads // groups

    def step(s, at):
        x_t, dt_t, b_t, c_t = at                # (batch, heads, ..)
        b_t, c_t = (jnp.repeat(t, per, axis=1) for t in (b_t, c_t))
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return s, jnp.sum(s * c_t[:, :, None, :], axis=-1) + d[:, None] * x_t

    _, y = lax.scan(step, jnp.zeros((batch, heads, size, state), x.dtype),
                    tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)
