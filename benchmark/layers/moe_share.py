"""``moe_share``: device time of the expert layer — what runs under the
scope ``hvd_moe``: routing (``hvd_moe_route``), the held experts' sort,
gather and scatter-add (``hvd_moe_experts``) and the shared expert
(``hvd_moe_shared``), all passes — and of the compiler's
grouped-product kernels, which carry no scope and are told by their
name (``benchmark/experts.py``), as a share of the busy time of the
traced leaves.  The MTP module's expert layer counts too.  Layer:
expert layer."""

from benchmark import experts


def read(trace, counters, cell):
    return experts.scope_share(trace, cell, "hvd_moe")
