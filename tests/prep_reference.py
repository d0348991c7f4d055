"""Loop implementations of host preparation, frozen as references.

``build_edge_ol``, ``level1_ol``, ``filter_infrequent_edges`` and
``make_partitions`` as they were written before they moved onto flat
arrays: one Python step per edge, per cell or per graph.  The program's
versions must return exactly what these return.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from repro.core.candgen import EdgeAlphabet
from repro.core.graphdb import Graph, validate_db
from repro.core.host_miner import frequent_edges
from repro.core.partition import PartitionResult, graph_density

PAD = -1


def build_edge_ol(graphs: Sequence[Graph], triples, *, pad_graphs=None,
                  max_occ=None):
    """(src, dst, mask, triple_index) of the edge OLs."""
    tindex = {tuple(t): i for i, t in enumerate(triples)}
    G = pad_graphs or len(graphs)
    occs = [[[] for _ in range(G)] for _ in range(len(triples))]
    for gi, g in enumerate(graphs):
        for (u, v), el in zip(g.edges, g.elabels):
            lu, lv = int(g.vlabels[u]), int(g.vlabels[v])
            for (a, la, b, lb) in ((int(u), lu, int(v), lv),
                                   (int(v), lv, int(u), lu)):
                ti = tindex.get((la, int(el), lb))
                if ti is not None:
                    occs[ti][gi].append((a, b))
    F = max_occ or max((len(o) for row in occs for o in row), default=1)
    F = max(F, 1)
    T = len(triples)
    src = np.full((T, G, F), PAD, np.int32)
    dst = np.full((T, G, F), PAD, np.int32)
    mask = np.zeros((T, G, F), bool)
    for ti in range(T):
        for gi in range(G):
            o = occs[ti][gi][:F]
            if o:
                src[ti, gi, : len(o)] = [p[0] for p in o]
                dst[ti, gi, : len(o)] = [p[1] for p in o]
                mask[ti, gi, : len(o)] = True
    return src, dst, mask, tindex


def level1_ol(codes, src, dst, emask, triple_index, *, max_embeddings):
    """(ol, mask) of the level-1 store."""
    P, M = len(codes), max_embeddings
    _, G, F = src.shape
    ol = np.full((P, G, M, 2), PAD, np.int32)
    mask = np.zeros((P, G, M), bool)
    for pi, code in enumerate(codes):
        (i, j, a, e, b) = code[0]
        ti = triple_index[(a, e, b)]
        take = min(M, F)
        ol[pi, :, :take, 0] = src[ti, :, :take]
        ol[pi, :, :take, 1] = dst[ti, :, :take]
        mask[pi, :, :take] = emask[ti, :, :take]
    return ol, mask


def filter_infrequent_edges(graphs: Sequence[Graph], minsup: int
                            ) -> tuple[list[Graph], EdgeAlphabet]:
    alphabet, _ = frequent_edges(graphs, minsup)
    out = []
    for g in graphs:
        keep = np.zeros(g.n_edges, bool)
        for k, ((u, v), el) in enumerate(zip(g.edges, g.elabels)):
            t = (int(g.vlabels[u]), int(el), int(g.vlabels[v]))
            keep[k] = t in alphabet
        out.append(g.keep_edges(keep))
    return out, alphabet


def make_partitions(graphs: Sequence[Graph], minsup, n_partitions, *,
                    scheme=2) -> PartitionResult:
    n = len(graphs)
    if n:
        validate_db(graphs)
    if n_partitions < 1:
        raise ValueError(f"n_partitions={n_partitions} must be >= 1")
    if n and n_partitions > n:
        raise ValueError(f"n_partitions={n_partitions} exceeds the "
                         f"database size {n}")
    abs_minsup = (int(np.ceil(minsup * n)) if isinstance(minsup, float)
                  else int(minsup))
    filtered, alphabet = filter_infrequent_edges(graphs, abs_minsup)
    ids = list(range(n))
    parts: list[list[int]] = [[] for _ in range(n_partitions)]
    if scheme == 1:
        for i in ids:
            parts[i % n_partitions].append(i)
    elif scheme == 2:
        load = np.zeros(n_partitions, np.int64)
        order = sorted(ids, key=lambda i: -filtered[i].n_edges)
        for i in order:
            p = min(range(n_partitions),
                    key=lambda b: (load[b], len(parts[b])))
            parts[p].append(i)
            load[p] += filtered[i].n_edges
    elif scheme == "density":
        order = sorted(ids, key=lambda i: (-graph_density(filtered[i]),
                                           -filtered[i].n_edges))
        for rank, i in enumerate(order):
            sweep, pos = divmod(rank, n_partitions)
            parts[pos if sweep % 2 == 0 else
                  n_partitions - 1 - pos].append(i)
    else:
        raise ValueError(f"unknown scheme {scheme!r} (1 | 2 | 'density')")
    return PartitionResult(
        partitions=[[filtered[i] for i in p] for p in parts],
        graph_ids=parts, alphabet=alphabet, minsup=abs_minsup, n_graphs=n)
