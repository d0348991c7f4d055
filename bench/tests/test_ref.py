"""The frozen reference miner against an oracle that shares no code with
it (``tests/oracle.py``: every connected edge subset of every graph,
grouped by networkx isomorphism), and the frozen generators against the
program's own."""
import math

import numpy as np
import pytest

from bench.gen import common, molecule
from bench.ref import miner


def _small_db(seed, n=7):
    rng = np.random.default_rng(seed)
    return [common.random_connected_graph(rng, int(rng.integers(4, 7)), 0.4,
                                          2, 2) for _ in range(n)]


def _canon(P):
    """A pattern graph (networkx) as a min-DFS code of the reference."""
    nodes = sorted(P.nodes)
    idx = {v: i for i, v in enumerate(nodes)}
    vl = [P.nodes[v]["label"] for v in nodes]
    edges = [(min(idx[u], idx[v]), max(idx[u], idx[v])) for u, v in P.edges]
    el = [P.edges[u, v]["label"] for u, v in P.edges]
    return miner.min_dfs_code((vl, edges, el))


@pytest.mark.parametrize("seed,minsup", [(0, 2), (1, 3), (2, 2), (3, 4)])
def test_reference_matches_brute_force(seed, minsup):
    oracle = pytest.importorskip("oracle")
    from repro.core.graphdb import Graph

    db = _small_db(seed)
    ref = miner.mine(db, minsup)
    max_edges = max(len(e) for _, e, _ in db)
    brute = oracle.brute_force_frequent(
        [Graph(vl, e, el) for vl, e, el in db], minsup, max_edges)
    want = {_canon(P): len(ids) for P, ids, _ in brute}
    assert ref.supports == want


def test_reference_is_invariant_to_the_seed_order():
    lib = molecule.generate(40, 3)
    a = miner.mine(common.reorder(lib, 5), 8)
    b = miner.mine(common.reorder(lib, 2 ** 31 + 7), 8)
    assert a.supports == b.supports and len(a.supports) > 20


def test_generator_matches_the_programs():
    """At the program's constants the frozen generator draws the
    program's ``pubchem_like_db`` graph for graph."""
    from repro.core import graphdb

    ours = molecule.generate(50, 0)
    theirs = graphdb.pubchem_like_db(50, seed=0)
    for (vl, e, el), g in zip(ours, theirs):
        assert np.array_equal(vl, g.vlabels)
        assert np.array_equal(e, g.edges)
        assert np.array_equal(el, g.elabels)


def test_reference_matches_the_programs_host_miner():
    from repro.core.graphdb import Graph
    from repro.core.host_miner import mine_host

    db = common.reorder(molecule.generate(120, 1), 9)
    minsup = math.ceil(0.1 * len(db))
    ref = miner.mine(db, minsup)
    host = mine_host([Graph(*g) for g in db], minsup)
    assert ref.supports == {c: p.support for c, p in host.frequent.items()}
