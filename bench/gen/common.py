"""Shared pieces of the DB generators.

A library is drawn once from the configuration's ``library_seed``; a
run's ``--seed`` then only reorders it (:func:`reorder`): the order of
the graphs, each graph's vertex numbering and the order of its edges.
Every seed so mines the same isomorphism classes with the same
supports and the same embedding counts, in another order, and the work
a fit does is the same from seed to seed.
"""
from __future__ import annotations

import numpy as np

GraphT = tuple  # (vlabels (n_v,), edges (n_e, 2) with u < v, elabels (n_e,))


def random_connected_graph(rng: np.random.Generator, n_v: int,
                           extra_edge_prob: float, n_vlabels: int,
                           n_elabels: int) -> GraphT:
    """Random spanning tree by random attachment, plus
    ``int(extra_edge_prob * n_v)`` tries at an extra edge."""
    vlabels = rng.integers(0, n_vlabels, size=n_v)
    edge_set: set[tuple[int, int]] = set()
    order = rng.permutation(n_v)
    for idx in range(1, n_v):
        u = int(order[idx])
        v = int(order[rng.integers(0, idx)])
        edge_set.add((min(u, v), max(u, v)))
    if n_v >= 3 and extra_edge_prob > 0:
        for _ in range(int(extra_edge_prob * n_v)):
            u, v = rng.integers(0, n_v, size=2)
            if u != v:
                edge_set.add((min(int(u), int(v)), max(int(u), int(v))))
    edges = np.array(sorted(edge_set), dtype=np.int32).reshape(-1, 2)
    elabels = rng.integers(0, n_elabels, size=edges.shape[0])
    return (vlabels.astype(np.int32), edges, elabels.astype(np.int32))


def reorder(graphs: list[GraphT], seed: int) -> list[GraphT]:
    """The run's DB: ``graphs`` in a seed-drawn order, each with its
    vertices renumbered and its edges listed in a seed-drawn order."""
    rng = np.random.default_rng(seed % (1 << 64))
    out = []
    for gi in rng.permutation(len(graphs)):
        vl, edges, el = graphs[gi]
        perm = rng.permutation(len(vl))          # old vertex id -> new
        new_vl = np.empty_like(vl)
        new_vl[perm] = vl
        e = perm[edges]
        e = np.stack([e.min(axis=1), e.max(axis=1)], axis=1)
        eo = rng.permutation(len(el))
        out.append((new_vl, e[eo].astype(np.int32), el[eo]))
    return out
