"""collective_ms: device time of the collectives in the traced fit (the
shuffle's reduce-scatter and the verdict and cost all-gathers, the
overflow psums), mean over the cell's chips, in ms.  Layer: shuffle.

Matched by HLO op name or opcode in the trace's ``XLA Ops`` line:
``reduce-scatter``, ``all-gather``, ``all-reduce``, with or without an
async ``-start``/``-done`` half."""
import re

from bench import trace

COLLECTIVE = re.compile(
    r"(all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all)")


def read(x):
    ns = trace.chip_mean(
        x.trace, x.devices,
        lambda ops: trace.busy_ns(trace.matching(ops, COLLECTIVE)))
    return None if not ns else ns / 1e6
