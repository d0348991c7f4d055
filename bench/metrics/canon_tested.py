"""canon_tested: raw candidates the traced fit put through host
candgen's canonicality walk, the ``canon_tested`` counter the program's
``mirage:fit`` span reports (``bench/phases.py``).  The same on every
seed of a cell, since every seed mines the same classes.  Nothing where
the program does not count them.  Layer: host candgen."""
from bench import phases


def read(x):
    return None if x.phases is None else phases.canon_tested(x.phases)
