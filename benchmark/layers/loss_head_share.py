"""``loss_head_share``: device time under the scope ``hvd_loss_head`` —
the f32 ``(batch, seq, vocab)`` logits product, ``log_softmax`` and the
target gather, forward and backward — as a share of the busy time of
the traced leaves (``benchmark/scopes.py``).  Cuts across ``fwd_share``
and ``bwd_share``.  Layer: the compiled train step."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.scope_share(trace, cell, "hvd_loss_head")
