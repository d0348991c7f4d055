"""Plain reference miner: the paper's Fig. 3 algorithm, host-side and exact.

A frozen, self-contained copy of the sequential baseline (breadth-first
candidate generation and test with occurrence lists), the rightmost-path
candidate generator and the min-DFS-code canonicality test it needs.  It
imports nothing of the program under test, so no change to the miner can
move the yardstick the benchmark compares it with.

A graph is a ``(vlabels, edges, elabels)`` triple: ``vlabels`` (n_v,)
int, ``edges`` (n_e, 2) int with u < v, ``elabels`` (n_e,) int.  A
pattern is keyed by its min-DFS code, a tuple of 5-tuples
``(i, j, l_i, l_e, l_j)``; an occurrence list maps a graph index to the
embeddings of the pattern there (vertex-id tuples ordered by DFS id).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

Code = tuple
GraphT = tuple  # (vlabels, edges, elabels)


# ---------------------------------------------------------------------------
# DFS codes (gSpan order) and the min-DFS-code canonicality test
# ---------------------------------------------------------------------------

def edge_lt(a, b) -> bool:
    """gSpan DFS-lexicographic edge order ``a < b`` (strict)."""
    ia, ja = a[0], a[1]
    ib, jb = b[0], b[1]
    fa, fb = ia < ja, ib < jb
    if fa and fb:
        if (ja, -ia) != (jb, -ib):
            return (ja, -ia) < (jb, -ib)
    elif (not fa) and (not fb):
        if (ia, ja) != (ib, jb):
            return (ia, ja) < (ib, jb)
    elif (not fa) and fb:
        return ia < jb
    else:
        return ja <= ib
    return a[2:] < b[2:]


def code_graph(code: Code) -> GraphT:
    """The pattern graph of a DFS code (dense 0-based ids)."""
    n_v = max(max(e[0], e[1]) for e in code) + 1
    vl = [-1] * n_v
    edges, el = [], []
    for (i, j, li, le, lj) in code:
        vl[i], vl[j] = li, lj
        edges.append((min(i, j), max(i, j)))
        el.append(le)
    return vl, edges, el


def min_dfs_code(graph: GraphT, bound: Optional[Code] = None
                 ) -> Optional[Code]:
    """Exact min-DFS code of ``graph``; with ``bound``, None as soon as
    the minimum is provably smaller than ``bound``."""
    vl, edges, els = graph
    adj: dict[int, list[tuple[int, int, int]]] = {}
    for k, ((u, v), el) in enumerate(zip(edges, els)):
        adj.setdefault(u, []).append((v, el, k))
        adj.setdefault(v, []).append((u, el, k))
    inits = []
    best0 = None
    for k, ((u, v), el) in enumerate(zip(edges, els)):
        for a, b in ((u, v), (v, u)):
            t = (0, 1, vl[a], el, vl[b])
            inits.append((t, a, b, k))
            if best0 is None or t[2:] < best0[2:]:
                best0 = t
    code = [best0]
    if bound is not None and best0 != bound[0]:
        return None
    # a state: (graph vid -> dfs id, dfs id -> graph vid, used edges,
    # rightmost path as dfs ids)
    states = [({a: 0, b: 1}, [a, b], frozenset([k]), (0, 1))
              for (t, a, b, k) in inits if t == best0]
    while len(code) < len(edges):
        best = None
        nexts = []
        for g2d, d2g, used, rmp in states:
            rm = rmp[-1]
            rm_g = d2g[rm]
            for (nbr, el, k) in adj[rm_g]:
                if k in used or nbr not in g2d or g2d[nbr] not in rmp[:-1]:
                    continue
                t = (rm, g2d[nbr], vl[rm_g], el, vl[nbr])
                nexts.append((t, (g2d, d2g, used | {k}, rmp)))
                if best is None or edge_lt(t, best):
                    best = t
            for pos in range(len(rmp) - 1, -1, -1):
                wd = rmp[pos]
                wg = d2g[wd]
                for (nbr, el, k) in adj[wg]:
                    if k in used or nbr in g2d:
                        continue
                    nd = len(d2g)
                    t = (wd, nd, vl[wg], el, vl[nbr])
                    ng2d = dict(g2d)
                    ng2d[nbr] = nd
                    nexts.append((t, (ng2d, d2g + [nbr], used | {k},
                                      rmp[:pos + 1] + (nd,))))
                    if best is None or edge_lt(t, best):
                        best = t
        code.append(best)
        if bound is not None and best != bound[len(code) - 1]:
            return None
        states = [st for (t, st) in nexts if t == best]
    return tuple(code)


def is_canonical(code: Code) -> bool:
    return min_dfs_code(code_graph(code), bound=code) == code


def rightmost_path(code: Code) -> tuple[int, ...]:
    parent: dict[int, int] = {}
    top = 0
    for (i, j, *_l) in code:
        if i < j:
            parent[j] = i
            top = max(top, j)
    path = [top]
    while path[-1] != 0:
        path.append(parent[path[-1]])
    return tuple(reversed(path))


# ---------------------------------------------------------------------------
# Rightmost-path candidate generation
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Candidate:
    code: Code
    parent: int                      # index into the sorted parent level
    forward: bool
    stub: int                        # dfs id of the attachment vertex
    to: int                          # dfs id of the other endpoint
    triple: tuple[int, int, int]     # (l_stub, l_edge, l_other)


def partners(alphabet: frozenset, label: int) -> list[tuple[int, int]]:
    return sorted({(e, b) for (a, e, b) in alphabet if a == label})


def candidates(parents: Sequence[Code], alphabet: frozenset
               ) -> list[Candidate]:
    """Every canonical one-edge extension of ``parents`` by a frequent
    edge: back edges from the rightmost vertex to its strict ancestors on
    the rightmost path, forward edges from any rightmost-path vertex."""
    out = []
    for pidx, code in enumerate(parents):
        vl, edges, _ = code_graph(code)
        rmp = rightmost_path(code)
        rmv = rmp[-1]
        existing = set(edges)
        for w in rmp[:-1]:
            if (min(rmv, w), max(rmv, w)) in existing:
                continue
            for (e, other) in partners(alphabet, vl[rmv]):
                if other != vl[w]:
                    continue
                child = code + ((rmv, w, vl[rmv], e, vl[w]),)
                if is_canonical(child):
                    out.append(Candidate(child, pidx, False, rmv, w,
                                         (vl[rmv], e, vl[w])))
        n_v = len(vl)
        for w in rmp:
            for (e, other) in partners(alphabet, vl[w]):
                child = code + ((w, n_v, vl[w], e, other),)
                if is_canonical(child):
                    out.append(Candidate(child, pidx, True, w, n_v,
                                         (vl[w], e, other)))
    return out


# ---------------------------------------------------------------------------
# The miner
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Level:
    """One mined level: its candidates (None for the single edges) and
    the frequent patterns with their occurrence lists."""

    candidates: Optional[list[Candidate]]
    frequent: dict                   # code -> {graph: [embedding, ...]}


@dataclasses.dataclass
class Reference:
    minsup: int
    supports: dict                   # code -> support, every level
    levels: list[Level]
    edge_occ: dict                   # oriented frequent triple -> OL

    def frequent_by_level(self) -> list[set]:
        return [set(lv.frequent) for lv in self.levels if lv.frequent]


def edge_occurrences(graphs: Sequence[GraphT]) -> dict:
    """Oriented occurrence lists per label triple: (a, e, b) maps graph
    index to (u, v) pairs with label(u)=a, elabel=e, label(v)=b."""
    out: dict = {}
    for gi, (vl, edges, els) in enumerate(graphs):
        for (u, v), el in zip(edges, els):
            for (a, b) in ((u, v), (v, u)):
                out.setdefault((vl[a], el, vl[b]), {}).setdefault(
                    gi, []).append((a, b))
    return out


def extend(parent_ol: dict, cand: Candidate, eocc: dict) -> dict:
    """Child occurrence list: parent OL joined with the edge OL."""
    edge_ol = eocc.get(cand.triple, {})
    child = {}
    for gi, embs in parent_ol.items():
        occs = edge_ol.get(gi)
        if not occs:
            continue
        acc = []
        for emb in embs:
            su = emb[cand.stub]
            if cand.forward:
                acc.extend(emb + (v,) for (u, v) in occs
                           if u == su and v not in emb)
            elif any(u == su and v == emb[cand.to] for (u, v) in occs):
                acc.append(emb)
        if acc:
            child[gi] = acc
    return child


def _as_lists(g) -> GraphT:
    vl, edges, els = g
    return ([int(x) for x in vl],
            [(int(u), int(v)) for u, v in np.asarray(edges).reshape(-1, 2)],
            [int(x) for x in els])


def mine(graphs: Sequence[GraphT], minsup: int) -> Reference:
    """Every connected pattern contained in at least ``minsup`` graphs,
    with its exact support, mined level by level to fixpoint."""
    graphs = [_as_lists(g) for g in graphs]
    eocc = edge_occurrences(graphs)
    # both orientations of a triple occur in the same graphs, so the
    # frequent alphabet is closed under reversal
    alphabet = frozenset(t for t, ol in eocc.items() if len(ol) >= minsup)
    eocc = {t: ol for t, ol in eocc.items() if t in alphabet}
    level1 = {((0, 1) + t,): {gi: list(occ) for gi, occ in ol.items()}
              for t, ol in eocc.items() if t[0] <= t[2]
              and len(ol) >= minsup}
    levels = [Level(None, level1)]
    supports = {c: len(ol) for c, ol in level1.items()}
    current = level1
    while current:
        parents = sorted(current)
        cands = candidates(parents, alphabet)
        nxt = {}
        for cand in cands:
            ol = extend(current[parents[cand.parent]], cand, eocc)
            if len(ol) >= minsup:
                nxt[cand.code] = ol
        levels.append(Level(cands, nxt))
        supports.update((c, len(ol)) for c, ol in nxt.items())
        current = nxt
    return Reference(minsup, supports, levels, eocc)
