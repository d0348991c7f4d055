"""gc_s: seconds of Python's generation-2 garbage collections in the
traced fit, the ``gc_s`` counter the program's ``mirage:fit`` span
reports (``bench/phases.py``).  Nothing where the program puts no spans
in the trace.  Layer: host runtime."""
from bench import phases


def read(x):
    return None if x.phases is None else phases.gc_s(x.phases)
