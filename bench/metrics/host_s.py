"""host_s: time of the traced fit outside the level programs and their
retries: the fit's wall time less its levels' ``map_seconds`` (dispatch
to decoded wire) and less ``retry_s`` (partition, edge-OL build, upload,
loop-head candgen, candidate metadata, auditor).  Layer: mining loop."""
from bench import cells


def read(x):
    retry = cells.metric_reader("retry_s").read(x)
    if not x.stats or retry is None:
        return None
    return (x.fit_walls[-1] - sum(s["map_seconds"] for s in x.stats[-1])
            - retry)
