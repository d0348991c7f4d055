"""prep_s: seconds of the traced fit's preparation before level 2, the
program's ``mirage:`` spans ``partition``, ``edge_ol_build``, ``level1``
and ``upload`` (``bench/phases.py``).  Nothing where the program puts no
spans in the trace.  Layer: mining loop."""
from bench import phases


def read(x):
    return None if x.phases is None else phases.prep_s(x.phases)
