"""retry_s: wall time of the traced fit's materialize-only retries, the
``bench:retry_materialize`` spans around ``Mirage._materialize_exact``:
a level whose survivors outgrew the cap, or whose embeddings
overflowed M, is materialized again (at doubled M on an overflow).
Nothing where that span could not be put in place.  Layer: mining loop."""


def read(x):
    if x.trace is None or "retry_materialize" not in x.spanned:
        return None
    return sum(d for name, _s, d in x.trace.spans
               if name == "retry_materialize") / 1e9
