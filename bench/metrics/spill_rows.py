"""spill_rows: rows the spill store's table held after each level of the
traced fit, summed over the levels: the ``spill_rows`` arg of the
program's ``mirage:fit`` span (``bench/phases.py``).  The same on every
seed of a cell, since every seed mines the same classes with the same
embedding counts.  Nothing where the program has no spill store.
Layer: embedding store."""


def read(x):
    if x.phases is None:
        return None
    fit = next((s for s in x.phases.spans if s[0] == "fit"), None)
    return None if fit is None else fit[3].get("spill_rows")
