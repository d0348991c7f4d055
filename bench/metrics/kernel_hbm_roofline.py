"""kernel_hbm_roofline: the support kernel's share of its HBM roofline
in the traced fit, in %: the least bytes the fit's levels must move
(``bench/roofline.py``, from the levels' shapes), per chip, over the
chip's published HBM bandwidth, over ``kernel_ms``.  The v5e publishes
no integer VPU peak, so no compute bound is taken.  Layer: support
kernel."""
from bench import cells, roofline


def read(x):
    kernel_ms = cells.metric_reader("kernel_ms").read(x)
    if not kernel_ms or not x.shapes or not x.peaks:
        return None
    nbytes = sum(roofline.support_bytes(s) for s in x.shapes)
    least_s = nbytes / len(x.devices) / x.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_ms / 1e3)
