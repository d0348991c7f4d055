"""kernel_ms: device time of the fused support kernel in the traced fit,
mean over the cell's chips, in ms.  Layer: support kernel.

The kernel is the ``pallas_call`` that ``kernels/fused_level.py`` wraps.
In the trace's ``XLA Ops`` line (read by hand on a TPU v5 lite) it is
the custom call named after its jitted wrapper, with a numeric suffix:
``%fused_level_packed_pallas.1 = (s32[8,1024,8,128]..., ...)
custom-call(...)`` (packed, below 2^16 graphs) or
``fused_level_pallas.<n>`` (dense).  Every fit runs the kernel, so a
traced fit in which no op has that name reads nothing, and a benchmark
run, which must read every metric listed for its cell, fails.
"""
import re

from bench import trace

KERNEL = re.compile(r"fused_level(_packed)?_pallas(\.\d+)?$")


def read(x):
    ns = trace.chip_mean(
        x.trace, x.devices,
        lambda ops: trace.busy_ns(trace.matching(ops, KERNEL)))
    return None if not ns else ns / 1e6
