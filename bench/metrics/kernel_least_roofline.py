"""kernel_least_roofline: the support kernel's share of its HBM roofline
in the traced fit, in %, counted from the embeddings that exist: the
levels' ``roofline.least_support_bytes`` (the reference's real parent
embeddings and edge occurrences, whatever store holds them), per chip,
over the chip's published HBM bandwidth, over ``kernel_ms``.  Beside
``kernel_hbm_roofline``, which counts the dense store's padded slots,
it reads the same work for any store layout.  Layer: support kernel."""
from bench import cells, roofline


def read(x):
    kernel_ms = cells.metric_reader("kernel_ms").read(x)
    if not kernel_ms or not x.shapes or not x.peaks:
        return None
    nbytes = sum(roofline.least_support_bytes(s) for s in x.shapes)
    least_s = nbytes / len(x.devices) / x.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_ms / 1e3)
