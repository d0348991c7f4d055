"""Molecule-library generator: the paper's Table I profile.

A copy of the program's ``pubchem_like_db`` with its constants as
parameters: ~``avg_edges`` bonds per molecule (normal, sd 4), a sparse
near-tree topology with a few ring-closing bonds, ``n_vlabels`` atom
labels of which ``carbon_share`` are forced to label 0 (carbon), and
``n_elabels`` bond labels.
"""
from __future__ import annotations

import numpy as np

from .common import GraphT, random_connected_graph


def generate(n_graphs: int, seed: int, *, avg_edges: float = 28.0,
             n_vlabels: int = 8, n_elabels: int = 3,
             extra_edge_prob: float = 0.12,
             carbon_share: float = 0.6) -> list[GraphT]:
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_graphs):
        n_e_target = max(3, int(rng.normal(avg_edges, 4.0)))
        n_v = max(3, int(n_e_target * 0.92))
        vl, edges, el = random_connected_graph(rng, n_v, extra_edge_prob,
                                               n_vlabels, n_elabels)
        vl[rng.random(len(vl)) < carbon_share] = 0
        out.append((vl, edges, el))
    return out
