"""DFS codes and min-dfs-code canonical labeling (paper §IV-A.2).

MIRAGE adopts gSpan's canonical coding scheme: a pattern's edges are
serialized as 5-tuples ``(i, j, l_i, l_e, l_j)`` where ``i, j`` are DFS
discovery ids, and the lexicographically smallest valid DFS serialization
(the *min-dfs-code*) is the pattern's canonical key.  A candidate
generation path is valid iff the insertion order of its edges equals the
min-dfs-code edge order — this is the isomorphism_checking() of the
paper's mapper (Fig. 7, line 3) and what makes the algorithm complete
*without duplicates* (the concrete failure of Hill et al. [32]).

Pattern graphs are tiny (≤ ~15 edges in practice), so this module is exact
host-side Python/numpy.  The data-scale work (support counting over the
partitioned database) lives on-device in ``embedding.py`` / ``kernels/``.

Edge order (gSpan, Yan & Han 2002, DFS lexicographic order) for
``e1 = (i1, j1)``, ``e2 = (i2, j2)``:

  * both forward (i < j):  e1 < e2  iff  j1 < j2, or (j1 == j2 and i1 > i2)
  * both backward (i > j): e1 < e2  iff  i1 < i2, or (i1 == i2 and j1 < j2)
  * e1 backward, e2 forward: e1 < e2  iff  i1 < j2
  * e1 forward, e2 backward: e1 < e2  iff  j1 <= i2

with ties broken by the label triple ``(l_i, l_e, l_j)``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .graphdb import Graph

# A code edge is a 5-tuple of ints: (i, j, l_i, l_e, l_j)
Edge5 = tuple[int, int, int, int, int]
Code = tuple[Edge5, ...]

__all__ = [
    "Edge5",
    "Code",
    "edge_lt",
    "code_lt",
    "code_to_graph",
    "min_dfs_code",
    "is_canonical",
    "canonical_prefix",
    "rightmost_path",
    "code_to_array",
    "array_to_code",
    "edge_struct_key",
    "code_array_vertex_labels",
    "code_array_rightmost_path",
    "min_dfs_canonical_array",
]


def edge_lt(a: Edge5, b: Edge5) -> bool:
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
    elif (not fa) and fb:      # backward vs forward
        return ia < jb
    else:                      # forward vs backward
        return ja <= ib
    # identical (i, j) structure -> label order
    return a[2:] < b[2:]


def code_lt(a: Code, b: Code) -> bool:
    """Strict DFS-lexicographic order on whole codes (prefix-aware)."""
    for ea, eb in zip(a, b):
        if ea == eb:
            continue
        return edge_lt(ea, eb)
    return len(a) < len(b)


def code_to_graph(code: Code) -> Graph:
    """Materialize the pattern graph of a DFS code (dense 0-based ids)."""
    n_v = max(max(e[0], e[1]) for e in code) + 1
    vlabels = -np.ones(n_v, dtype=np.int32)
    edges, elabels = [], []
    for (i, j, li, le, lj) in code:
        vlabels[i] = li
        vlabels[j] = lj
        edges.append((min(i, j), max(i, j)))
        elabels.append(le)
    assert (vlabels >= 0).all(), f"disconnected code {code}"
    return Graph(vlabels, np.array(edges, np.int32), np.array(elabels, np.int32))


@dataclasses.dataclass
class _State:
    """One partial DFS traversal of the pattern graph."""

    g2d: dict[int, int]          # graph vid -> dfs id
    d2g: list[int]               # dfs id -> graph vid
    used: frozenset[int]         # used (undirected) edge indices
    rmp: tuple[int, ...]         # rightmost path, as dfs ids root..rightmost


def min_dfs_code(
    g: Graph,
    bound: Optional[Code] = None,
) -> Optional[Code]:
    """Exact min-dfs-code of ``g`` by breadth-parallel minimal extension.

    Maintains *all* partial DFS traversals that realize the current minimal
    code prefix; at each step enumerates every legal gSpan extension
    (backward from the rightmost vertex, then forward from rightmost-path
    vertices), keeps the minimal edge tuple, and prunes states.

    If ``bound`` is given, returns ``None`` as soon as the minimal code is
    provably *smaller* than ``bound`` at some position (early exit for
    canonicality checking: a non-None result equal to bound ⇒ canonical).
    """
    if g.n_edges == 0:
        raise ValueError("empty pattern")
    adj: dict[int, list[tuple[int, int, int]]] = {}  # u -> [(v, elabel, eidx)]
    for k, ((u, v), el) in enumerate(zip(map(tuple, g.edges), g.elabels)):
        adj.setdefault(int(u), []).append((int(v), int(el), k))
        adj.setdefault(int(v), []).append((int(u), int(el), k))

    vl = g.vlabels

    # --- initial edge: minimal (l_u, l_e, l_v) over all orientations
    best0: Optional[Edge5] = None
    inits: list[tuple[Edge5, int, int, int]] = []
    for k, ((u, v), el) in enumerate(zip(map(tuple, g.edges), g.elabels)):
        for a, b in ((int(u), int(v)), (int(v), int(u))):
            t: Edge5 = (0, 1, int(vl[a]), int(el), int(vl[b]))
            inits.append((t, a, b, k))
            if best0 is None or t[2:] < best0[2:]:
                best0 = t
    assert best0 is not None
    code: list[Edge5] = [best0]
    if bound is not None and code[0] != bound[0]:
        # min first edge differs from bound's: it can only be smaller.
        return None
    states = [
        _State({a: 0, b: 1}, [a, b], frozenset([k]), (0, 1))
        for (t, a, b, k) in inits
        if t == best0
    ]

    n_edges = g.n_edges
    while len(code) < n_edges:
        best: Optional[Edge5] = None
        nexts: list[tuple[Edge5, _State]] = []
        for st in states:
            rm_dfs = st.rmp[-1]
            rm_g = st.d2g[rm_dfs]
            # backward extensions: rightmost vertex -> rightmost-path vertex
            # (never the immediate parent; edge must exist and be unused)
            for (nbr, el, k) in adj[rm_g]:
                if k in st.used or nbr not in st.g2d:
                    continue
                jd = st.g2d[nbr]
                # target must be a strict ancestor (on RMP, not rightmost
                # itself); the parent edge is already in `used` and the
                # graph is simple, so the no-multigraph rule holds.
                if jd not in st.rmp[:-1]:
                    continue
                t = (rm_dfs, jd, int(vl[rm_g]), el, int(vl[nbr]))
                nexts.append((t, _ext_backward(st, k)))
                if best is None or edge_lt(t, best):
                    best = t
            # forward extensions: from rightmost-path vertices to new vertices
            for pos in range(len(st.rmp) - 1, -1, -1):
                wd = st.rmp[pos]
                wg = st.d2g[wd]
                for (nbr, el, k) in adj[wg]:
                    if k in st.used or nbr in st.g2d:
                        continue
                    nd = len(st.d2g)
                    t = (wd, nd, int(vl[wg]), el, int(vl[nbr]))
                    nexts.append((t, _ext_forward(st, k, nbr, wd)))
                    if best is None or edge_lt(t, best):
                        best = t
        assert best is not None, "graph must be connected"
        pos = len(code)
        code.append(best)
        if bound is not None:
            if best != bound[pos]:
                # best < bound[pos] (bound is realizable, so min <= bound)
                return None
        states = [st for (t, st) in nexts if t == best]
    return tuple(code)


def _ext_backward(st: _State, eidx: int) -> _State:
    return _State(st.g2d, st.d2g, st.used | {eidx}, st.rmp)


def _ext_forward(st: _State, eidx: int, nbr_g: int, from_dfs: int) -> _State:
    nd = len(st.d2g)
    g2d = dict(st.g2d)
    g2d[nbr_g] = nd
    d2g = st.d2g + [nbr_g]
    # new rightmost path: truncate at the extension stub, append new vertex
    cut = st.rmp.index(from_dfs) + 1
    rmp = st.rmp[:cut] + (nd,)
    return _State(g2d, d2g, frozenset(st.used | {eidx}), rmp)


def is_canonical(code: Code) -> bool:
    """True iff ``code`` equals the min-dfs-code of its own pattern graph,
    i.e. ``min_dfs_code(code_to_graph(code)) == code``.

    This is exactly the mapper's isomorphism_checking() (paper Fig. 7
    line 3): of all generation paths of a pattern, only the one matching
    the min-dfs-code survives.
    """
    return canonical_prefix(code) == len(code)


def canonical_prefix(code: Code) -> int:
    """How many leading edges of ``code`` the min-dfs-code agrees with:
    ``len(code)`` iff ``code`` is canonical, else the position at which a
    smaller code (or none at all) is found.

    The walk of `min_dfs_code`, checked against ``code`` as it goes: the
    pattern graph is read straight off the code's tuples (vertex ids are
    the code's own dfs ids), and at each position every extension of
    every state is compared with ``code[pos]`` as it is found.  A smaller
    one ends the walk (any partial DFS traversal can be completed, so a
    smaller prefix proves a smaller code); an equal one carries its
    state forward; a larger one is dropped without building a state.
    All states realize the same prefix, so the rightmost path, and how
    each extension's ``(i, j)`` ranks against ``code[pos]``, are shared.
    """
    vl: dict[int, int] = {}
    adj: dict[int, list[tuple[int, int, int]]] = {}  # v -> [(nbr, el, k)]
    for k, (i, j, li, le, lj) in enumerate(code):
        vl[i] = li
        vl[j] = lj
        adj.setdefault(i, []).append((j, le, k))
        adj.setdefault(j, []).append((i, le, k))

    # a state: (d2g, used) — dfs id -> pattern vertex, used-edge bitmask
    head = code[0]
    if head[:2] != (0, 1):
        return 0
    want = head[2:]
    states: list[tuple[tuple[int, ...], int]] = []
    for k, (i, j, _, le, _) in enumerate(code):
        for a, b in ((i, j), (j, i)):
            t = (vl[a], le, vl[b])
            if t < want:
                return 0
            if t == want:
                states.append(((a, b), 1 << k))
    if not states:
        return 0

    rmp = [0, 1]                 # rightmost path, dfs ids root..rightmost
    for pos in range(1, len(code)):
        target = code[pos]
        want = target[2:]
        n_d = len(states[0][0])  # dfs ids so far; the next new one
        rm = rmp[-1]
        # where each possible (i, j) falls against code[pos] in
        # `edge_lt`'s order, whatever its labels: the sort key is the
        # tuple form of `edge_struct_key`.  Keys past it are left out;
        # the flag is True for a key before it, False for its own.
        ti, tj = target[0], target[1]
        tkey = (2 * tj, -ti) if ti < tj else (2 * ti + 1, tj)
        bk, fk = 2 * rm + 1, 2 * n_d
        back = {jd: (bk, jd) < tkey for jd in rmp[:-1] if (bk, jd) <= tkey}
        fwd = [(wd, (fk, -wd) < tkey) for wd in reversed(rmp)
               if (fk, -wd) <= tkey]
        nexts: list[tuple[tuple[int, ...], int]] = []
        for d2g, used in states:
            if back:
                rm_g = d2g[rm]
                for nbr, el, k in adj[rm_g]:
                    if used >> k & 1 or nbr not in d2g:
                        continue
                    before = back.get(d2g.index(nbr))
                    if before is None:
                        continue
                    t = (vl[rm_g], el, vl[nbr])
                    if before or t < want:
                        return pos
                    if t == want:
                        nexts.append((d2g, used | 1 << k))
            for wd, before in fwd:
                wg = d2g[wd]
                for nbr, el, k in adj[wg]:
                    if nbr in d2g:
                        continue
                    t = (vl[wg], el, vl[nbr])
                    if before or t < want:
                        return pos
                    if t == want:
                        nexts.append((d2g + (nbr,), used | 1 << k))
        if not nexts:
            return pos
        states = nexts
        if target[0] < target[1]:           # forward: the path is cut
            rmp = rmp[:rmp.index(target[0]) + 1] + [target[1]]
    return len(code)


def rightmost_path(code: Code) -> tuple[int, ...]:
    """Rightmost path of a (valid) DFS code, as dfs ids root..rightmost."""
    parent: dict[int, int] = {}
    max_id = 0
    for (i, j, *_l) in code:
        if i < j:  # forward edge
            parent[j] = i
            max_id = max(max_id, j)
    path = [max_id]
    while path[-1] != 0:
        path.append(parent[path[-1]])
    return tuple(reversed(path))


# ---------------------------------------------------------------------------
# Fixed-shape array interop (device representation of pattern metadata)
# ---------------------------------------------------------------------------

def code_to_array(code: Code, max_edges: int) -> np.ndarray:
    """Pack a code into a (max_edges, 5) int32 array, -1 padded."""
    a = -np.ones((max_edges, 5), dtype=np.int32)
    if len(code) > max_edges:
        raise ValueError(f"code of size {len(code)} exceeds max_edges={max_edges}")
    for r, e in enumerate(code):
        a[r] = e
    return a


def array_to_code(a: np.ndarray) -> Code:
    out = []
    for row in np.asarray(a):
        if row[0] < 0 and row[1] < 0:
            break
        out.append(tuple(int(x) for x in row))
    return tuple(out)  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# Device-side DFS-code ops (pipeline="device_loop", DESIGN.md §13)
#
# The host `edge_lt` / `min_dfs_code` machinery above re-expressed as
# fixed-shape jnp programs so candidate generation can run inside the
# whole-run `lax.while_loop`.  Codes travel as (L, 5) int32 arrays,
# -1 padded (``code_to_array`` layout).
# ---------------------------------------------------------------------------

_BIG = np.int32(1 << 29)  # lexicographic sentinel (labels/keys are << this)


def edge_struct_key(i, j, nv: int):
    """Linearize `edge_lt`'s structural (i, j) comparison into one int key.

    forward  (i < j): key = (2j)   * (nv+1) + (nv - i)   — orders by (j, -i)
    backward (i > j): key = (2i+1) * (nv+1) + j          — orders by (i, j)

    The parity of the leading coefficient resolves the mixed cases exactly:
    backward(i1,·) < forward(·,j2) iff 2·i1+1 < 2·j2 iff i1 < j2, and
    forward(·,j1) < backward(i2,·) iff 2·j1 < 2·i2+1 iff j1 <= i2 — the
    four `edge_lt` structural rules.  Label triples break the remaining
    ties separately (see `min_dfs_canonical_array`'s masked lex-min).
    """
    fwd = i < j
    return jnp.where(fwd, (2 * j) * (nv + 1) + (nv - i),
                     (2 * i + 1) * (nv + 1) + j).astype(jnp.int32)


def _lex_min(mask, comps):
    """Masked lexicographic min over broadcastable int components.

    Returns ([min components], achiever-mask); mask must have the full
    broadcast shape."""
    best = []
    for c in comps:
        m = jnp.min(jnp.where(mask, c, _BIG))
        mask = mask & (c == m)
        best.append(m)
    return best, mask


def code_array_vertex_labels(code, n_vertex_slots: int):
    """(L,5) code array -> (NV,) vertex labels, -1 on unused slots."""
    NV = n_vertex_slots
    valid = code[:, 0] >= 0
    vl = jnp.full((NV,), -1, jnp.int32)
    vl = vl.at[jnp.where(valid, code[:, 0], NV)].set(code[:, 2], mode="drop")
    vl = vl.at[jnp.where(valid, code[:, 1], NV)].set(code[:, 4], mode="drop")
    return vl


def _dfs_parents(code, n_vertex_slots: int, row_mask):
    """parent[j] = i over forward rows selected by ``row_mask``."""
    NV = n_vertex_slots
    fwd = row_mask & (code[:, 0] < code[:, 1]) & (code[:, 0] >= 0)
    par = jnp.full((NV,), -1, jnp.int32)
    return par.at[jnp.where(fwd, code[:, 1], NV)].set(code[:, 0], mode="drop")


def code_array_rightmost_path(code, n_vertex_slots: int):
    """(L,5) code array -> (rmp (NV,) root-first -1-padded, rmp_len, n_v).

    Array twin of `rightmost_path`: walk the forward-edge parent chain
    from the rightmost (max dfs id) vertex to the root.
    """
    NV = n_vertex_slots
    L = code.shape[0]
    valid = code[:, 0] >= 0
    n_v = jnp.max(jnp.where(valid, jnp.maximum(code[:, 0], code[:, 1]), -1)) + 1
    par = _dfs_parents(code, NV, jnp.ones((L,), bool))
    rm = n_v - 1

    def up(s, carry):
        cur, rev = carry
        rev = rev.at[s].set(cur)
        nxt = jnp.where(cur > 0, par[jnp.clip(cur, 0, NV - 1)], -1)
        return nxt, rev

    _, rev = jax.lax.fori_loop(0, NV, up, (rm, jnp.full((NV,), -1, jnp.int32)))
    rmp_len = (rev >= 0).sum()
    idx = rmp_len - 1 - jnp.arange(NV)
    rmp = jnp.where(idx >= 0, rev[jnp.clip(idx, 0, NV - 1)], -1)
    return rmp, rmp_len, n_v


def _onpath_mask(par, rm, n_vertex_slots: int):
    """(NV,) bool: dfs ids on the rightmost path (root..rm inclusive)."""
    NV = n_vertex_slots
    cols = jnp.arange(NV)

    def wstep(s, carry):
        cur, onp = carry
        onp = onp | ((cols == cur) & (cur >= 0))
        return jnp.where(cur > 0, par[jnp.clip(cur, 0, NV - 1)], -1), onp

    _, onpath = jax.lax.fori_loop(0, NV, wstep, (rm, jnp.zeros((NV,), bool)))
    return onpath


def min_dfs_canonical_array(code, *, n_vertex_slots: int, max_states: int):
    """Array twin of `is_canonical`: (canonical, overflow) bool scalars.

    Runs the breadth-parallel minimal-extension machine of `min_dfs_code`
    under a fixed state budget: all partial traversals realizing the
    minimal prefix live in ``max_states`` slots of (graph->dfs, dfs->graph,
    used-edge-bitmask) arrays.  The dfs-side quantities (vertex count,
    rightmost path) are shared across states — they are functions of the
    code prefix alone — so only the graph-side mappings are per-state.

    If the live state set ever exceeds ``max_states`` the result is
    unreliable and ``overflow`` is set — callers must fall back to the
    host `is_canonical` (the driver bails the whole device loop).
    Vmappable over a batch of codes; requires L < 32 (int32 edge bitmask).
    """
    L = code.shape[0]
    NV = n_vertex_slots
    MS = max_states
    if L >= 32:
        raise ValueError(f"max_edges={L} exceeds the int32 edge-bitmask width")
    ar_l = jnp.arange(L)
    cols = jnp.arange(NV)

    i_, j_ = code[:, 0], code[:, 1]
    li_, le_, lj_ = code[:, 2], code[:, 3], code[:, 4]
    valid_e = i_ >= 0
    ne = valid_e.sum()
    vl = code_array_vertex_labels(code, NV)

    # directed orientation table (2L,): first L rows umin->umax, then flipped
    umin, umax = jnp.minimum(i_, j_), jnp.maximum(i_, j_)
    du = jnp.concatenate([umin, umax])
    dv = jnp.concatenate([umax, umin])
    de = jnp.concatenate([le_, le_])
    dk = jnp.concatenate([ar_l, ar_l]).astype(jnp.int32)
    dvalid = jnp.concatenate([valid_e, valid_e])
    dlu = vl[jnp.clip(du, 0, NV - 1)]
    dlv = vl[jnp.clip(dv, 0, NV - 1)]

    # --- initial edge: minimal (l_u, l_e, l_v) over valid orientations
    (b0l, b0e, b0r), m0 = _lex_min(dvalid, (dlu, de, dlv))
    ok0 = (b0l == li_[0]) & (b0e == le_[0]) & (b0r == lj_[0])

    pos0 = jnp.cumsum(m0) - 1
    dest0 = jnp.where(m0, pos0, MS)
    src_o = jnp.zeros((MS,), jnp.int32).at[dest0].set(
        jnp.arange(2 * L, dtype=jnp.int32), mode="drop")
    alive = jnp.arange(MS) < m0.sum()
    su, sv, sk = du[src_o], dv[src_o], dk[src_o]
    g2d = jnp.where(cols[None, :] == su[:, None], 0,
                    jnp.where(cols[None, :] == sv[:, None], 1, -1))
    d2g = jnp.where(cols[None, :] == 0, su[:, None],
                    jnp.where(cols[None, :] == 1, sv[:, None], -1))
    used = jnp.where(alive, jnp.int32(1) << sk, 0)

    fwd_rows = valid_e & (i_ < j_)

    def step(t, carry):
        g2d, d2g, used, alive, result, done, ovf = carry
        act = (~done) & (t < ne)
        # shared dfs-space prefix quantities (rows [0, t) are consumed)
        pre = ar_l < t
        nmap = 1 + jnp.sum(fwd_rows & pre)
        rm = nmap - 1
        par = _dfs_parents(code, NV, pre)
        onpath = _onpath_mask(par, rm, NV)

        # extension slots: (state, orientation) -> candidate edge
        fu = g2d[:, jnp.clip(du, 0, NV - 1)]      # (MS, 2L) dfs id of u
        fv = g2d[:, jnp.clip(dv, 0, NV - 1)]
        unused = ((used[:, None] >> dk[None, :]) & 1) == 0
        base = alive[:, None] & dvalid[None, :] & unused
        is_b = (fu == rm) & (fv >= 0)
        okb = base & is_b & (fv != rm) & onpath[jnp.clip(fv, 0, NV - 1)]
        is_f = (fv < 0) & (fu >= 0)
        okf = base & is_f & onpath[jnp.clip(fu, 0, NV - 1)]
        okx = okb | okf
        ei = jnp.where(is_b, rm, fu)
        ej = jnp.where(is_b, fv, nmap)
        skey = edge_struct_key(ei, ej, NV)

        shape2 = (MS, 2 * L)
        (bk_, bl1, bl2, bl3), mbest = _lex_min(
            okx, (skey,
                  jnp.broadcast_to(dlu, shape2),
                  jnp.broadcast_to(de, shape2),
                  jnp.broadcast_to(dlv, shape2)))
        bkey_t = edge_struct_key(i_[t], j_[t], NV)
        match = ((bk_ == bkey_t) & (bl1 == li_[t]) & (bl2 == le_[t])
                 & (bl3 == lj_[t]) & mbest.any())

        # compact achiever (state, orientation) pairs into the state slots
        flat = mbest.reshape(-1)
        posn = jnp.cumsum(flat) - 1
        nn = flat.sum()
        dest = jnp.where(flat, posn, MS)
        sidx = jnp.zeros((MS,), jnp.int32).at[dest].set(
            jnp.arange(MS * 2 * L, dtype=jnp.int32), mode="drop")
        s_sel = sidx // (2 * L)
        o_sel = sidx % (2 * L)
        isf_sel = okf.reshape(-1)[sidx]
        gv = dv[jnp.clip(o_sel, 0, 2 * L - 1)]
        ng2d = jnp.where((cols[None, :] == gv[:, None]) & isf_sel[:, None],
                         nmap, g2d[s_sel])
        nd2g = jnp.where((cols[None, :] == nmap) & isf_sel[:, None],
                         gv[:, None], d2g[s_sel])
        nused = used[s_sel] | (jnp.int32(1) << dk[jnp.clip(o_sel, 0, 2 * L - 1)])
        nalive = jnp.arange(MS) < jnp.minimum(nn, MS)

        g2d = jnp.where(act, ng2d, g2d)
        d2g = jnp.where(act, nd2g, d2g)
        used = jnp.where(act, nused, used)
        alive = jnp.where(act, nalive, alive)
        result = result & jnp.where(act, match, True)
        done = done | (act & ~match)
        ovf = ovf | (act & (nn > MS))
        return g2d, d2g, used, alive, result, done, ovf

    ovf0 = m0.sum() > MS
    init = (g2d, d2g, used, alive, ok0, ~ok0, ovf0)
    if L > 1:
        _, _, _, _, result, _, ovf = jax.lax.fori_loop(1, L, step, init)
    else:
        result, ovf = ok0, ovf0
    return result, ovf
