"""wire_wait_s: seconds the traced fit's host blocked on a level
program's wire, the program's ``mirage:wire_wait`` spans
(``bench/phases.py``).  Nothing where the program puts no spans in the
trace.  Layer: level program."""
from bench import phases


def read(x):
    return None if x.phases is None else phases.wire_wait_s(x.phases)
