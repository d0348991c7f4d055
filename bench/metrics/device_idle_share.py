"""device_idle_share: the share of the traced fit's wall time in which no
op ran on a chip, mean over the cell's chips, in %.  Layer: device."""
from bench import trace


def read(x):
    busy = trace.chip_mean(x.trace, x.devices, trace.busy_ns)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / 1e9 / x.fit_walls[-1])
