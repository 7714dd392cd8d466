"""How a family holds the system to its plain reference: the step-0 loss
and the global gradient norm, each within a relative bound the family
states with its reason."""

from __future__ import annotations

import math


def global_norm(tree) -> float:
    """One program for the whole tree, not one for every leaf."""
    import jax
    import optax

    return float(jax.jit(optax.global_norm)(tree))


def against_reference(loss, grads, ref_loss, ref_grads, loss_rtol: float,
                      grad_norm_rtol: float) -> dict:
    """The record of one comparison; ``ok`` decides ``correct``."""
    loss, ref_loss = float(loss), float(ref_loss)
    norm, ref_norm = global_norm(grads), global_norm(ref_grads)
    loss_err = abs(loss - ref_loss) / abs(ref_loss)
    norm_err = abs(norm - ref_norm) / ref_norm
    return {"ok": bool(math.isfinite(loss) and loss_err < loss_rtol
                       and norm_err < grad_norm_rtol),
            "loss": loss, "reference_loss": ref_loss,
            "loss_rel_err": loss_err, "loss_rtol": loss_rtol,
            "grad_norm": norm, "reference_grad_norm": ref_norm,
            "grad_norm_rel_err": norm_err, "grad_norm_rtol": grad_norm_rtol}
