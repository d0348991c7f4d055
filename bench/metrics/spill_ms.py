"""spill_ms: device busy time of the traced fit's ops under the
program's ``mirage/spill_support`` (the join over the spill table) and
``mirage/spill_materialize`` (its count and write passes) scopes, mean
over the cell's chips, in ms (``bench/phases.py``).  Nothing where no op
carries either scope.  Layer: embedding store."""
from bench import phases


def read(x):
    if x.phases is None:
        return None
    parts = [phases.scope_ns(x.phases, x.devices, s)
             for s in ("spill_support", "spill_materialize")]
    if None in parts or not sum(parts):
        return None
    return sum(parts) / 1e6
