"""device_other_ms: device busy time of the traced fit outside the
support kernel and the collectives (compaction, materialize_prefix,
escalation and retry re-materialization, wire packing), mean over the
cell's chips, in ms.  Layer: device."""
from bench import cells, trace

KERNEL = cells.metric_reader("kernel_ms").KERNEL
COLLECTIVE = cells.metric_reader("collective_ms").COLLECTIVE


def _other(ops):
    return (trace.busy_ns(ops) - trace.busy_ns(trace.matching(ops, KERNEL))
            - trace.busy_ns(trace.matching(ops, COLLECTIVE)))


def read(x):
    ns = trace.chip_mean(x.trace, x.devices, _other)
    return None if ns is None else ns / 1e6
