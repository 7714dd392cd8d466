"""``attn_kernel_share``: device time in Mosaic (Pallas) kernels — today
the flash forward, ``flash_bwd_dq`` and ``flash_bwd_dkv`` calls, which
the trace cannot tell apart — as a share of the device's busy time
(device trace).  0 where ``auto_impl`` picks the XLA attention.  Layer:
attention kernels."""

from benchmark import reduce


def read(trace, counters, cell):
    return trace.mean(lambda ops: reduce.kernel_ns(ops)
                      / reduce.total(reduce.busy(ops)))
