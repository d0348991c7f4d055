"""min-dfs-code exactness + canonicality properties (hypothesis), and the
canonicality walk (`canonical_prefix`) against its definition,
``min_dfs_code(code_to_graph(c)) == c``."""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import candgen
from repro.core.candgen import Extension, generate_candidates
from repro.core.dfscode import (array_to_code, canonical_prefix, code_lt,
                                code_to_array, code_to_graph, edge_lt,
                                is_canonical, min_dfs_code, rightmost_path)
from repro.core.graphdb import Graph, random_db
from repro.core.host_miner import mine_host


def permute(g: Graph, perm: np.ndarray) -> Graph:
    """Relabel vertex ids by permutation (labels travel with vertices)."""
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    vl = g.vlabels[inv]
    edges = perm[g.edges]
    return Graph(vl, edges, g.elabels)


@st.composite
def small_graphs(draw):
    n_v = draw(st.integers(2, 7))
    n_vlab = draw(st.integers(1, 3))
    n_elab = draw(st.integers(1, 2))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    vl = rng.integers(0, n_vlab, n_v)
    # random spanning tree + a couple extras
    edges = set()
    for i in range(1, n_v):
        j = int(rng.integers(0, i))
        edges.add((j, i))
    for _ in range(draw(st.integers(0, 3))):
        a, b = rng.integers(0, n_v, 2)
        if a != b:
            edges.add((min(int(a), int(b)), max(int(a), int(b))))
    edges = np.array(sorted(edges), np.int32)
    el = rng.integers(0, n_elab, len(edges))
    return Graph(vl, edges, el)


@settings(max_examples=150, deadline=None)
@given(small_graphs(), st.integers(0, 2**31 - 1))
def test_min_code_invariant_under_relabeling(g, seed):
    """The canonical key must not depend on vertex ids — the property that
    makes the MapReduce shuffle key well-defined across partitions."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(g.n_vertices)
    assert min_dfs_code(g) == min_dfs_code(permute(g, perm))


@settings(max_examples=100, deadline=None)
@given(small_graphs())
def test_min_code_is_canonical_and_minimal(g):
    c = min_dfs_code(g)
    assert is_canonical(c)
    # code reconstructs an isomorphic graph: same size, same canonical code
    g2 = code_to_graph(c)
    assert g2.n_edges == g.n_edges
    assert min_dfs_code(g2) == c


@settings(max_examples=100, deadline=None)
@given(small_graphs())
def test_bound_early_exit_consistent(g):
    c = min_dfs_code(g)
    assert min_dfs_code(g, bound=c) == c


def test_single_edge_code():
    g = Graph([1, 0], [(0, 1)], [7])
    assert min_dfs_code(g) == ((0, 1, 0, 7, 1),)


def test_triangle_same_labels():
    g = Graph([0, 0, 0], [(0, 1), (1, 2), (0, 2)], [0, 0, 0])
    c = min_dfs_code(g)
    assert c == ((0, 1, 0, 0, 0), (1, 2, 0, 0, 0), (2, 0, 0, 0, 0))
    assert rightmost_path(c) == (0, 1, 2)


def test_paper_fig5_example():
    """Paper Fig. 5: B-{A,C,D} star.  min code extends A-B with C then D.
    Labels: A=0,B=1,C=2,D=3.  Expected (per paper §IV-A.2):
    (1,2,A,B)(2,3,B,C)(2,4,B,D) -> 0-based (0,1,0,_,1)(1,2,1,_,2)(1,3,1,_,3)."""
    g = Graph([0, 1, 2, 3], [(0, 1), (1, 2), (1, 3)], [0, 0, 0])
    c = min_dfs_code(g)
    assert c == ((0, 1, 0, 0, 1), (1, 2, 1, 0, 2), (1, 3, 1, 0, 3))


def test_noncanonical_generation_path_rejected():
    """Paper Fig. 5(b): building the star via A-B-D first is invalid."""
    bad = ((0, 1, 0, 0, 1), (1, 2, 1, 0, 3), (1, 3, 1, 0, 2))
    assert not is_canonical(bad)


def test_code_array_roundtrip():
    c = ((0, 1, 0, 0, 1), (1, 2, 1, 0, 2), (2, 0, 2, 1, 0))
    a = code_to_array(c, 6)
    assert a.shape == (6, 5)
    assert array_to_code(a) == c


def test_code_lt_total_order_on_sample():
    g = random_db(5, n_vertices=6, seed=3)
    codes = [min_dfs_code(x) for x in g]
    for a in codes:
        assert not code_lt(a, a)
        for b in codes:
            if a != b:
                assert code_lt(a, b) != code_lt(b, a)


# ---------------------------------------------------------------------------
# the canonicality walk against the min-dfs-code definition
# ---------------------------------------------------------------------------

def check_walk(code):
    """``canonical_prefix`` against the min-dfs-code of ``code``'s graph:
    the verdict is the definition's, and on a valid DFS code the walk
    stops exactly where the minimum first goes below ``code``."""
    m = min_dfs_code(code_to_graph(code))
    p = canonical_prefix(code)
    assert is_canonical(code) == (m == code), code
    assert m[:p] == code[:p], code
    assert p == len(code) or edge_lt(m[p], code[p]), code


def molecules(n=150, seed=7):
    """A molecule-like library: near-trees of 8-23 atoms with a few
    ring-closing bonds, 38 atom labels of which 60% are carbon (label 0),
    3 bond labels."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        n_v = int(rng.integers(8, 24))
        edges = {(int(rng.integers(0, v)), v) for v in range(1, n_v)}
        for _ in range(n_v // 6):
            a, b = (int(x) for x in rng.integers(0, n_v, 2))
            if a != b:
                edges.add((min(a, b), max(a, b)))
        vl = rng.integers(0, 38, n_v)
        vl[rng.random(n_v) < 0.6] = 0
        edges = sorted(edges)
        out.append(Graph(vl, edges, rng.integers(0, 3, len(edges))))
    return out


@pytest.fixture(scope="module")
def library():
    """The library mined to level 5 (minsup 10 of 150): its frequent
    levels, its alphabet, and every raw child ``generate_candidates``
    put through the walk, by level."""
    tested = {}
    walk = candgen.canonical_prefix

    def recording(code):
        tested.setdefault(len(code), []).append(code)
        return walk(code)

    candgen.canonical_prefix = recording
    try:
        res = mine_host(molecules(), 10, max_size=5)
    finally:
        candgen.canonical_prefix = walk
    return res.levels, res.alphabet, tested


def oracle_candidates(parents, alphabet):
    """(raw children, candidates) of ``parents``: every rightmost
    extension by an alphabet edge (back edges first, then forward ones
    from the root down), kept iff it is its own min-dfs-code."""
    closure = sorted({t for a, e, b in alphabet.canonical()
                      for t in ((a, e, b), (b, e, a))})
    raw, out = [], []
    for p, code in enumerate(parents):
        vl = {}
        for i, j, li, _, lj in code:
            vl[i], vl[j] = li, lj
        rmp = rightmost_path(code)
        rmv = rmp[-1]
        has = {frozenset(e[:2]) for e in code}
        exts = [(False, rmv, w) for w in rmp[:-1]
                if frozenset((rmv, w)) not in has]
        exts += [(True, w, len(vl)) for w in rmp]
        for fwd, stub, to in exts:
            for a, e, b in closure:
                if a != vl[stub] or (not fwd and b != vl[to]):
                    continue
                child = code + ((stub, to, a, e, b),)
                raw.append(child)
                if min_dfs_code(code_to_graph(child)) == child:
                    out.append((child, p, Extension(fwd, stub, to, (a, e, b))))
    return raw, out


@pytest.mark.parametrize("level", [2, 3, 4, 5])
def test_walk_agrees_on_every_raw_child(library, level):
    _, _, tested = library
    assert len(tested[level]) > 100
    for code in tested[level]:
        check_walk(code)


@pytest.mark.parametrize("level", [2, 3, 4, 5])
def test_generate_candidates_equals_the_definitions_filter(library, level):
    """Same raw children tested, same candidates kept, in the same order."""
    levels, alphabet, tested = library
    parents = levels[level - 2]
    raw, want = oracle_candidates(parents, alphabet)
    got = generate_candidates(parents, alphabet)
    assert tested[level] == raw
    assert [(c.code, c.parent, c.ext) for c in got] == want
    assert want


def random_dfs_code(g, rng):
    """The DFS code of a random DFS traversal of ``g`` from a random
    vertex: a valid code, rarely the minimum."""
    adj = {v: [] for v in range(g.n_vertices)}
    for k, ((u, v), el) in enumerate(zip(g.edges.tolist(),
                                         g.elabels.tolist())):
        adj[u].append((v, el, k))
        adj[v].append((u, el, k))
    vl = g.vlabels.tolist()
    dfs, used, code = {}, set(), []

    def visit(u):
        for i in rng.permutation(len(adj[u])):
            v, el, k = adj[u][i]
            if v in dfs:
                continue
            dfs[v] = len(dfs)
            used.add(k)
            code.append((dfs[u], dfs[v], vl[u], el, vl[v]))
            # the new rightmost vertex's back edges, to its ancestors
            for jd, w, el2, k2 in sorted((dfs[w], w, el2, k2)
                                         for w, el2, k2 in adj[v]
                                         if w in dfs and k2 not in used):
                used.add(k2)
                code.append((dfs[v], jd, vl[v], el2, vl[w]))
            visit(v)

    start = int(rng.integers(g.n_vertices))
    dfs[start] = 0
    visit(start)
    assert len(code) == g.n_edges
    return tuple(code)


@pytest.mark.parametrize("seed", range(6))
def test_walk_agrees_on_random_traversals(seed):
    rng = np.random.default_rng(seed)
    graphs = random_db(8, n_vertices=6, extra_edge_prob=0.4, n_vlabels=2,
                       n_elabels=2, seed=seed)
    verdicts = []
    for g in graphs:
        g = permute(g, rng.permutation(g.n_vertices))
        for _ in range(12):
            code = random_dfs_code(g, rng)
            check_walk(code)
            verdicts.append(is_canonical(code))
        check_walk(min_dfs_code(g))
    assert not all(verdicts)


C = (0, 0, 0)  # two carbons over a single bond: (l_i, l_e, l_j)
EDGE_CASES = {
    # name: (code, canonical, where the walk stops)
    "single_edge": (((0, 1, 0, 0, 1),), True, 1),
    "single_edge_reversed": (((0, 1, 1, 0, 0),), False, 0),
    "first_edge_not_minimal": (((0, 1, 1, 0, 1), (1, 2, 1, 0, 0)), False, 0),
    "triangle_back_to_root": (((0, 1) + C, (1, 2) + C, (2, 0) + C), True, 3),
    "square_back_to_root": (((0, 1) + C, (1, 2) + C, (2, 3) + C,
                             (3, 0) + C), True, 4),
    "back_edge_too_late": (((0, 1) + C, (1, 2) + C, (2, 3) + C,
                            (3, 1) + C), False, 2),
    "ring_with_tail": (((0, 1) + C, (1, 2) + C, (2, 3) + C, (3, 1) + C,
                        (3, 4, 0, 0, 1)), False, 2),
    "carbon_chain": (tuple((i, i + 1) + C for i in range(5)), True, 5),
    "carbon_chain_from_inside": (((0, 1) + C, (1, 2) + C, (2, 3) + C,
                                  (0, 4) + C), False, 3),
    "carbon_star": (((0, 1) + C, (1, 2) + C, (1, 3) + C, (1, 4) + C),
                    True, 4),
    "carbon_star_from_center": (((0, 1) + C, (0, 2) + C, (0, 3) + C,
                                 (0, 4) + C), False, 1),
    "carbon_ring": (tuple((i, i + 1) + C for i in range(5))
                    + ((5, 0) + C,), True, 6),
    "kekule_ring": (tuple((i, i + 1, 0, i % 2, 0) for i in range(5))
                    + ((5, 0, 0, 1, 0),), True, 6),
    "kekule_ring_double_first": (tuple((i, i + 1, 0, 1 - i % 2, 0)
                                       for i in range(5))
                                 + ((5, 0, 0, 0, 0),), False, 0),
}


@pytest.mark.parametrize("name", sorted(EDGE_CASES))
def test_walk_edge_cases(name):
    code, canonical, stop = EDGE_CASES[name]
    assert is_canonical(code) is canonical
    assert canonical_prefix(code) == stop
    check_walk(code)
