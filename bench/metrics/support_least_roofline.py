"""support_least_roofline: the whole support step's share of its HBM
roofline in the traced fit, in %, counted from the embeddings that
exist: the levels' ``roofline.least_support_bytes`` (the reference's
real parent embeddings and edge occurrences, whatever store holds
them), per chip, over the chip's published HBM bandwidth, over the
device time under the program's ``mirage/support_kernel`` and
``mirage/spill_support`` scopes (``bench/phases.py``): the dense
kernel and the join over the spill table together.  Nothing where no op
carries either scope.  Layer: support kernel."""
from bench import phases, roofline


def read(x):
    if x.phases is None or not x.shapes or not x.peaks:
        return None
    parts = [phases.scope_ns(x.phases, x.devices, s)
             for s in ("support_kernel", "spill_support")]
    if None in parts or not sum(parts):
        return None
    nbytes = sum(roofline.least_support_bytes(s) for s in x.shapes)
    least_s = nbytes / len(x.devices) / x.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(parts) / 1e9)
