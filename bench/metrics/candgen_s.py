"""candgen_s: seconds of the traced fit's host candidate generation
before each level's dispatch, the program's ``mirage:`` spans
``candgen``, ``candidate_meta`` and ``schedule`` (``bench/phases.py``);
speculative candgen overlapped with a level program is not in it.
Nothing where the program puts no spans in the trace.  Layer: host
candgen."""
from bench import phases


def read(x):
    return None if x.phases is None else phases.candgen_s(x.phases)
