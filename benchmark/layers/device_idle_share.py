"""``device_idle_share``: 1 - (union of the device's operation
intervals / traced window), the window running from the first traced
operation to the last (device trace).  Layer: device."""

from benchmark import reduce


def read(trace, counters, cell):
    return trace.mean(lambda ops: 1.0 - reduce.total(reduce.busy(ops))
                      / reduce.total([reduce.window(ops)]))
