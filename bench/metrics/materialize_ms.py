"""materialize_ms: device busy time of the traced fit's ops under the
program's ``mirage/materialize`` scope (the level pass's and the
retries' materialization of the survivors' embeddings), mean over the
cell's chips, in ms (``bench/phases.py``: an op's scope comes from its
program's HloProto in the trace).  Nothing where no op carries a
scope.  Layer: materialization."""
from bench import phases


def read(x):
    if x.phases is None:
        return None
    return phases.materialize_ms(x.phases, x.devices)
