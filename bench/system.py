"""The system under test, seen from the benchmark: MIRAGE's ``Mirage.fit``.

Everything the benchmark takes from the program passes through here:
the miner and its mesh, the conversion of a generated DB into the
program's ``Graph``s, the answer and the ``LevelStats`` a fit returns,
and (in a traced run) host spans around the program's phases.
"""
from __future__ import annotations

import contextlib
import functools
import importlib

import jax

#: host phases wrapped in ``bench:<name>`` spans in a traced run, so a
#: device idle gap can be named by what the host was doing: (module,
#: class or None, attribute, span name).  A phase the program no longer
#: has is skipped; a metric that reads its span then reads nothing, and
#: the traced run fails.
HOST_SPANS = [
    ("repro.core.mining", None, "make_partitions", "partition"),
    ("repro.core.mining", None, "build_edge_ol", "edge_ol_build"),
    ("repro.core.mining", None, "level1_ol", "level1_ol"),
    ("repro.core.mining", "Mirage", "_device_put", "device_put"),
    ("repro.core.mining", None, "generate_candidates", "candgen"),
    ("repro.core.mining", None, "candidate_meta", "candidate_meta"),
    ("repro.core.mining", None, "dispatch_level", "dispatch"),
    ("repro.core.level_step", "PendingLevel", "finish", "fetch_decode"),
    ("repro.core.mining", "Mirage", "_materialize_exact",
     "retry_materialize"),
    ("repro.core.auditor", "Auditor", "check_level", "auditor"),
]


def to_graphs(db):
    from repro.core.graphdb import Graph

    return [Graph(vl, edges, el) for vl, edges, el in db]


def build_miner(config: dict, traffic: dict, devices):
    """``Mirage`` for the cell: the traffic's minsup, the configuration's
    partitions and miner settings, on a mesh over ``devices``."""
    from repro.core.mapreduce import MiningMesh
    from repro.core.mining import Mirage, MirageConfig
    from repro.runtime import jax_compat

    mesh = MiningMesh(jax_compat.make_mesh((len(devices),), ("w",),
                                           devices=list(devices)))
    cfg = MirageConfig(minsup=float(traffic["minsup"]),
                       max_size=traffic.get("max_size"),
                       n_partitions=int(config["n_partitions"]),
                       **config.get("miner", {}))
    return Mirage(cfg, mesh)


def answer(result) -> tuple[list[set], dict]:
    """(frequent codes per level, support per code) of a fit."""
    return [set(lv) for lv in result.levels], dict(result.supports)


def level_stats(result) -> list[dict]:
    return [{"level": s.level, "candidates": s.n_candidates,
             "frequent": s.n_frequent, "seconds": s.seconds,
             "map_seconds": s.map_seconds, "escalations": s.escalations,
             "retried": bool(s.retried), "survivor_cap": s.survivor_cap}
            for s in result.stats]


def _spanned(fn, name):
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.profiler.TraceAnnotation(f"bench:{name}"):
            return fn(*args, **kwargs)
    return wrapper


@contextlib.contextmanager
def host_spans(log):
    """Wrap the program's host phases in profiler spans, and restore them.
    Yields the names of the spans put in place."""
    undo = []
    placed: set[str] = set()
    try:
        for mod_name, cls, attr, name in HOST_SPANS:
            owner = importlib.import_module(mod_name)
            if cls is not None:
                owner = getattr(owner, cls, None)
            fn = getattr(owner, attr, None) if owner is not None else None
            if fn is None:
                log(f"host span {name}: {mod_name}.{cls or ''}.{attr} "
                    f"not found, not spanned")
                continue
            setattr(owner, attr, _spanned(fn, name))
            undo.append((owner, attr, fn))
            placed.add(name)
        yield placed
    finally:
        for owner, attr, fn in reversed(undo):
            setattr(owner, attr, fn)
