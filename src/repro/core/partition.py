"""Data-partition phase (paper §IV-C.1).

Splits the transaction database into many partitions — deliberately far
more partitions than workers (paper Fig. 20: mapper cost is exponential
in partition size, shuffle cost only linear) — and strips globally
infrequent edges while doing so (paper Fig. 11).

Three schemes:
  scheme 1 — balance the number of graphs per partition (paper);
  scheme 2 — balance the total number of *edges* per partition (greedy
             LPT bin packing), the load-balancing win of Table IV (paper);
  "density" — balance edge DENSITY, à la Aridhi et al. (arXiv
             1212.0017): graphs sorted by density 2E/(V(V-1)) and
             snake-dealt across partitions, so the densest graphs — the
             ones whose embedding joins dominate map cost superlinearly
             in E — spread evenly instead of pooling in one LPT bin and
             serializing a shard.  Edge count is the tie-break within
             equal density, graph count the final tie-break.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Sequence

import numpy as np

from .graphdb import FlatGraphs, Graph, row_ids, validate_db
from .candgen import EdgeAlphabet

__all__ = ["PartitionResult", "filter_infrequent_edges", "graph_density",
           "make_partitions"]


@dataclasses.dataclass
class PartitionResult:
    partitions: list[list[Graph]]      # filtered graphs per partition
    graph_ids: list[list[int]]         # original indices (for support audit)
    alphabet: EdgeAlphabet             # global F_1 label triples
    minsup: int                        # absolute threshold
    n_graphs: int                      # original database size


def filter_infrequent_edges(
    graphs: Sequence[Graph], minsup: int
) -> tuple[list[Graph], EdgeAlphabet]:
    """Drop every edge whose label triple is globally infrequent.

    A triple's support is the number of graphs holding an edge of it,
    counted over the distinct (canonical triple, graph) pairs of the
    whole DB's edges at once."""
    flat = FlatGraphs.of(graphs)
    lu, lv = flat.endpoint_labels()
    a, e, b = np.minimum(lu, lv), flat.elabels, np.maximum(lu, lv)
    triple, n_triples = row_ids(a, e, b)
    pairs = np.unique(triple * max(len(graphs), 1) + flat.graph)
    support = np.bincount(pairs // max(len(graphs), 1),
                          minlength=n_triples)
    frequent = support >= minsup
    # one edge of each triple, to read the triple's labels from
    first = np.zeros(n_triples, np.int64)
    first[triple] = np.arange(triple.shape[0])
    first = first[frequent]
    alphabet = EdgeAlphabet(zip(a[first].tolist(), e[first].tolist(),
                                b[first].tolist()))
    return flat.keep_edges(frequent[triple]).graphs(), alphabet


def graph_density(g: Graph) -> float:
    """Undirected edge density 2E/(V(V-1)); a single-vertex (or empty)
    graph has density 0 by convention."""
    v = g.n_vertices
    return 0.0 if v < 2 else 2.0 * g.n_edges / (v * (v - 1))


def make_partitions(
    graphs: Sequence[Graph],
    minsup: int | float,
    n_partitions: int,
    *,
    scheme: int | str = 2,
) -> PartitionResult:
    """Filter + split.  ``minsup`` may be absolute (int) or a fraction.

    Raises when the split would leave partitions empty: an empty
    partition pads silently into the dense device encoding and wastes a
    worker slot — the caller (``Mirage.fit``) auto-clamps instead.  An
    EMPTY database is exempt (its partitions are necessarily empty;
    mining short-circuits to an empty result).
    """
    n = len(graphs)
    if n:
        # the load boundary: user input is validated HERE, before any
        # filtering (keep_edges legitimately empties graphs later).
        # An empty database stays exempt per the contract above.
        validate_db(graphs)
    if n_partitions < 1:
        raise ValueError(f"n_partitions={n_partitions} must be >= 1")
    if n and n_partitions > n:
        raise ValueError(
            f"n_partitions={n_partitions} exceeds the database size {n}: "
            f"every partition must hold at least one graph (clamp "
            f"n_partitions or pass more graphs)")
    abs_minsup = (int(np.ceil(minsup * n)) if isinstance(minsup, float)
                  else int(minsup))
    filtered, alphabet = filter_infrequent_edges(graphs, abs_minsup)

    n_e = np.array([g.n_edges for g in filtered], np.int64)
    if scheme == 1:
        parts = [list(range(p, n, n_partitions))
                 for p in range(n_partitions)]
    elif scheme == 2:
        parts = [[] for _ in range(n_partitions)]
        # LPT: heaviest graphs first onto the lightest partition;
        # ties (e.g. fully-filtered zero-edge graphs) break on graph
        # count, then on the lower partition, so no partition is
        # starved empty
        lightest = [(0, 0, p) for p in range(n_partitions)]
        for i in np.argsort(-n_e, kind="stable").tolist():
            load, count, p = lightest[0]
            parts[p].append(i)
            heapq.heapreplace(lightest, (load + int(n_e[i]), count + 1, p))
    elif scheme == "density":
        # densest graphs first, snake-dealt (0..NP-1, NP-1..0, ...): each
        # pass hands every partition exactly one graph of comparable
        # density, and the direction flip cancels the within-pass bias —
        # graph counts stay balanced (|Δ| <= 1) by construction, so no
        # partition starves even when the DB is density-uniform; edge
        # count breaks ties
        density = np.array([graph_density(g) for g in filtered])
        order = np.lexsort((-n_e, -density))
        sweep, pos = np.divmod(np.arange(n), n_partitions)
        deal = np.where(sweep % 2 == 0, pos, n_partitions - 1 - pos)
        parts = [order[deal == p].tolist() for p in range(n_partitions)]
    else:
        raise ValueError(f"unknown scheme {scheme!r} (1 | 2 | 'density')")

    return PartitionResult(
        partitions=[[filtered[i] for i in p] for p in parts],
        graph_ids=parts,
        alphabet=alphabet,
        minsup=abs_minsup,
        n_graphs=n,
    )
