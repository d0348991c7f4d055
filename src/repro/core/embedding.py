"""Dense occurrence-list (OL) algebra — the device-side data plane.

MIRAGE's support counting is OL intersection (paper §IV-A.3, Fig. 6): the
child pattern's embeddings are the parent's embeddings joined with the
adjoined edge's occurrences.  Hadoop-MIRAGE does this in Java per mapper;
here it becomes fixed-shape masked tensor ops so a partition's whole
level-k state lives on a TPU core and the join runs on the VPU
(`kernels/embedding_join.py` is the tiled version; this module is the
pure-jnp reference/oracle and the shape contract).

Dense shapes for one partition (G graphs padded):

  edge-OL   : src/dst (T, G, F) int32 + mask (T, G, F) bool
              T = directed frequent label triples, F = max occ/graph
  level-k OL: ol (P, G, M, K) int32 + mask (P, G, M) bool
              P = |F_k| patterns, M = max embeddings/graph,
              K = k+1 (vertex-count pad; unused slots are -1)
  candidates: meta (C, 5) int32 rows [parent, stub, to, fwd, triple_idx]

Two-pass level execution (a beyond-paper optimization — Hadoop MIRAGE
materializes and *ships* OLs for every locally-non-zero candidate; we
materialize survivors only, locally):

  pass 1  local_supports()   -> (C,) per-graph-any popcount   [hot path]
  pass 2  materialize_prefix() -> compacted child OLs for frequent c only
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .candgen import Candidate
from .dfscode import Code
from .graphdb import FlatGraphs, Graph, row_ids

__all__ = [
    "EdgeOL", "LevelOL", "CandidateMeta",
    "build_edge_ol", "level1_ol", "candidate_meta",
    "join_valid", "local_supports_ref", "support_bits_ref",
    "materialize_rows", "materialize_prefix",
    "NOKEY", "build_adjacency", "empty_spill", "spill_support_rows",
    "spill_child_counts", "spill_write",
]

PAD = -1


@dataclasses.dataclass
class EdgeOL:
    """Partition-static directed edge occurrence lists (paper Fig. 12b)."""

    triples: np.ndarray    # (T, 3) int32 — the directed label-triple table
    src: np.ndarray        # (T, G, F) int32
    dst: np.ndarray        # (T, G, F) int32
    mask: np.ndarray       # (T, G, F) bool
    triple_index: dict[tuple[int, int, int], int]
    occurrences: int       # directed occurrences placed in the lists
    truncated: int         # those past ``max_occ`` in their (T, G) cell

    @property
    def shape(self):
        return self.src.shape


@dataclasses.dataclass
class LevelOL:
    """Stacked OLs for all frequent patterns of one level."""

    ol: np.ndarray | jax.Array     # (P, G, M, K) int32, PAD-filled
    mask: np.ndarray | jax.Array   # (P, G, M) bool

    @property
    def P(self):
        return self.ol.shape[0]


def build_edge_ol(
    graphs: Sequence[Graph],
    triples: Sequence[tuple[int, int, int]],
    *,
    pad_graphs: int | None = None,
    max_occ: int | None = None,
) -> EdgeOL:
    """Preparation-phase construction (host, once per partition).

    ``triples`` must be the *directed* closure of the frequent-edge
    alphabet so every partition indexes the same table (the shared key
    space that replaces Hadoop's shuffle-by-string-key).

    Each edge (u, v) occurs twice, u -> v then v -> u.  A (triple,
    graph) cell lists its occurrences in edge order, the first
    ``max_occ`` of them when that is given.  The occurrences of all
    graphs are sorted into their cells at once and scattered.
    """
    tindex = {tuple(t): i for i, t in enumerate(triples)}
    G = pad_graphs or len(graphs)
    if len(graphs) > G:
        raise ValueError(f"pad_graphs={G} < {len(graphs)} graphs")
    T = len(triples)
    flat = FlatGraphs.of(graphs)
    lu, lv = flat.endpoint_labels()
    # every directed occurrence, in (graph, edge, orientation) order
    src = flat.edges.reshape(-1)
    dst = flat.edges[:, ::-1].reshape(-1)
    la = np.stack([lu, lv], axis=1).reshape(-1)
    lb = np.stack([lv, lu], axis=1).reshape(-1)
    el = np.repeat(flat.elabels, 2)
    graph = np.repeat(flat.graph, 2)
    # each occurrence's index in the table, -1 off it; a triple listed
    # twice keeps its last index, as ``tindex`` does
    table = np.asarray(triples, np.int64).reshape(-1, 3)
    ids, n_ids = row_ids(*(np.concatenate([table[:, k], col])
                           for k, col in enumerate((la, el, lb))))
    last = np.fromiter(tindex.values(), np.int64, len(tindex))
    lookup = np.full(n_ids, -1, np.int64)
    lookup[ids[last]] = last
    ti = lookup[ids[T:]]
    on = ti >= 0
    cell = ti[on] * G + graph[on]
    order = np.argsort(cell, kind="stable")
    cell, src, dst = cell[order], src[on][order], dst[on][order]
    # an occurrence's slot: its rank within its cell
    idx = np.arange(cell.shape[0])
    new = np.r_[True, cell[1:] != cell[:-1]] if cell.size else idx > 0
    slot = idx - np.maximum.accumulate(np.where(new, idx, 0))
    F = max(max_occ or (int(slot.max()) + 1 if slot.size else 1), 1)
    fits = slot < F
    cell, slot = cell[fits], slot[fits]
    out_src = np.full((T, G, F), PAD, np.int32)
    out_dst = np.full((T, G, F), PAD, np.int32)
    mask = np.zeros((T, G, F), bool)
    t, g = np.divmod(cell, G)
    out_src[t, g, slot] = src[fits]
    out_dst[t, g, slot] = dst[fits]
    mask[t, g, slot] = True
    return EdgeOL(np.asarray(triples, np.int32), out_src, out_dst, mask,
                  tindex, occurrences=int(fits.sum()),
                  truncated=int(fits.size - fits.sum()))


def level1_ol(
    codes: Sequence[Code],
    eol: EdgeOL,
    *,
    max_embeddings: int,
) -> LevelOL:
    """F_1 OLs from the edge-OL (preparation phase's emitted patterns),
    as host arrays: the one upload of the store comes after.

    A single-edge pattern (0,1,a,e,b) embeds at every directed occurrence
    of (a,e,b); when a == b the two orientations are distinct embeddings
    and already both present in the directed edge-OL.
    """
    P, M = len(codes), max_embeddings
    _, G, F = eol.src.shape
    ti = np.array([eol.triple_index[code[0][2:]] for code in codes],
                  np.int64)
    take = min(M, F)
    ol = np.full((P, G, M, 2), PAD, np.int32)
    mask = np.zeros((P, G, M), bool)
    ol[:, :, :take, 0] = eol.src[ti, :, :take]
    ol[:, :, :take, 1] = eol.dst[ti, :, :take]
    mask[:, :, :take] = eol.mask[ti, :, :take]
    return LevelOL(ol, mask)


def candidate_meta(cands: Sequence[Candidate], eol: EdgeOL) -> np.ndarray:
    """(C, 5) int32: [parent, stub, to, fwd, triple_idx]."""
    rows = []
    for c in cands:
        rows.append([c.parent, c.ext.stub, c.ext.to, int(c.ext.forward),
                     eol.triple_index[c.ext.triple]])
    return np.asarray(rows, np.int32).reshape(-1, 5)


# ---------------------------------------------------------------------------
# Reference (pure-jnp) join — semantics oracle for the Pallas kernel
# ---------------------------------------------------------------------------

def join_valid(
    parent_ol: jnp.ndarray,   # (G, M, K)
    parent_mask: jnp.ndarray,  # (G, M)
    src: jnp.ndarray,          # (G, F)
    dst: jnp.ndarray,          # (G, F)
    emask: jnp.ndarray,        # (G, F)
    stub: jnp.ndarray,         # () int32
    to: jnp.ndarray,           # () int32
    forward: jnp.ndarray,      # () int32 (0/1)
) -> jnp.ndarray:
    """Valid-match mask (G, M, F): parent embedding m ⋈ edge occurrence f."""
    K = parent_ol.shape[-1]
    onehot = (jnp.arange(K) == stub).astype(parent_ol.dtype)
    stub_vals = (parent_ol * onehot).sum(-1)          # (G, M)
    hit = (src[:, None, :] == stub_vals[:, :, None])  # (G, M, F)
    hit &= parent_mask[:, :, None] & emask[:, None, :]

    # forward: new endpoint must not already be in the embedding
    member = (dst[:, None, :, None] == parent_ol[:, :, None, :]).any(-1)
    fwd_ok = ~member
    # backward: other endpoint must be exactly embedding[to]
    onehot_to = (jnp.arange(K) == to).astype(parent_ol.dtype)
    to_vals = (parent_ol * onehot_to).sum(-1)          # (G, M)
    bwd_ok = dst[:, None, :] == to_vals[:, :, None]
    return hit & jnp.where(forward.astype(bool), fwd_ok, bwd_ok)


def local_supports_ref(
    level: LevelOL,
    eol_src: jnp.ndarray, eol_dst: jnp.ndarray, eol_mask: jnp.ndarray,
    meta: jnp.ndarray,     # (C, 5)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-candidate local support (#graphs with >=1 match) and total
    embedding count (the straggler-rebalance cost signal).  Pure jnp.
    """
    def one(cand):
        parent, stub, to, fwd, tidx = cand[0], cand[1], cand[2], cand[3], cand[4]
        pol = jnp.take(level.ol, parent, axis=0)        # (G, M, K)
        pmask = jnp.take(level.mask, parent, axis=0)    # (G, M)
        src = jnp.take(eol_src, tidx, axis=0)           # (G, F)
        dst = jnp.take(eol_dst, tidx, axis=0)
        em = jnp.take(eol_mask, tidx, axis=0)
        valid = join_valid(pol, pmask, src, dst, em, stub, to, fwd)
        per_graph = valid.any(axis=(1, 2))
        return per_graph.sum(dtype=jnp.int32), valid.sum(dtype=jnp.int32)

    sup, cnt = jax.lax.map(one, meta)
    return sup, cnt


def support_bits_ref(
    meta: jnp.ndarray,     # (C, 5)
    pol: jnp.ndarray,      # (P, G, M, K)
    pmask: jnp.ndarray,    # (P, G, M)
    src: jnp.ndarray,      # (T, G, F)
    dst: jnp.ndarray,
    emask: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Bitset-shaped support masks — the pure-jnp oracle for the packed
    fused kernel (DESIGN.md §12).

    Per candidate, the boolean per-graph verdict packs to a
    ``ceil(G/32)``-word uint32 bitset (LSB-first, pad bits zero) and
    local support is popcount over the words — bit-identical to
    ``local_supports_ref`` by construction.  Returns
    ``(sup (C,), emb (C,), vbits (C, ceil(G/32)))``.
    """
    from repro.kernels.bitset import pack_bits, popcount, tail_mask

    G = pol.shape[1]
    gmask = jnp.asarray(tail_mask(G))

    def one(cand):
        parent, stub, to, fwd, tidx = (cand[0], cand[1], cand[2], cand[3],
                                       cand[4])
        p = jnp.take(pol, parent, axis=0)
        pm = jnp.take(pmask, parent, axis=0).astype(bool)
        s = jnp.take(src, tidx, axis=0)
        d = jnp.take(dst, tidx, axis=0)
        em = jnp.take(emask, tidx, axis=0).astype(bool)
        valid = join_valid(p, pm, s, d, em, stub, to, fwd)
        bits = pack_bits(valid.any(axis=(1, 2))) & gmask
        return bits, valid.sum(dtype=jnp.int32)

    vbits, emb = jax.lax.map(one, meta)
    sup = popcount(vbits).sum(-1, dtype=jnp.int32)
    return sup, emb, vbits


def materialize_rows(
    pol: jnp.ndarray,           # (G, M, K) the candidate's parent OL
    pmask: jnp.ndarray,         # (G, M)
    src: jnp.ndarray,           # (G, F) the candidate's triple edge-OL
    dst: jnp.ndarray,
    em: jnp.ndarray,
    cand: jnp.ndarray,          # (5,) one candidate row
    *,
    max_embeddings: int,
    out_width: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Child OL of one candidate from its parent's and triple's rows —
    the single-slot building block of every materialization.

    ``out_width`` is the child's vertex-slot width W (default K+1, the
    exact unbucketed growth).  Under shape bucketing the parent store is
    already wider than its real pattern, so W may equal K — the new
    vertex then lands in a slot that held PAD — and must never shrink
    below it."""
    G, M, K = pol.shape
    F = src.shape[-1]
    Mc = max_embeddings
    W = K + 1 if out_width is None else out_width
    if W < K:
        raise ValueError(f"out_width={W} below parent vertex width {K}")

    stub, to, fwd = cand[1], cand[2], cand[3]
    valid = join_valid(pol, pmask, src, dst, em, stub, to, fwd)  # (G,M,F)

    # child embedding (m, f): parent row m extended by dst[f] (forward)
    # or unchanged (backward).  Backward duplicates (same m, several f)
    # are collapsed to the first f per m.
    first_f = (jnp.cumsum(valid, axis=-1) == 1) & valid
    vsel = jnp.where(fwd.astype(bool), valid, first_f)           # (G,M,F)

    flat = vsel.reshape(G, M * F)
    # stable compaction: output slot r holds the index of the (r+1)-th
    # valid entry of its graph row — a vectorized binary search over the
    # prefix sums.  Entries ranked past the Mc cap (and all invalid
    # entries) are masked off by ``picked``.  Replaces the earlier
    # rank->index scatter, which XLA lowers serially (measured ~4x
    # slower than the search on CPU).
    csum = jnp.cumsum(flat, axis=-1)                             # (G,MF)
    tgt = jnp.arange(1, Mc + 1)
    order = jax.vmap(lambda row: jnp.searchsorted(row, tgt))(csum)
    order = jnp.minimum(order, M * F - 1).astype(jnp.int32)      # (G,Mc)
    n_valid = csum[:, -1]                                        # (G,)
    picked = jnp.arange(Mc)[None, :] < n_valid[:, None]          # (G,Mc)
    m_idx, f_idx = order // F, order % F

    par_rows = jnp.take_along_axis(
        pol, m_idx[:, :, None], axis=1)                          # (G,Mc,K)
    new_v = jnp.take_along_axis(dst, f_idx, axis=-1)             # (G,Mc)
    # Pad to W slots, then scatter the new vertex at its DFS id
    # (= ext.to for forward edges; patterns with back edges have
    # n_v < K so the write position is NOT necessarily the last slot).
    # Under bucketing W may equal K: the parent slot at ``to`` is PAD
    # (the parent pattern has fewer than K real vertices), so the
    # overwrite is always into a free slot.
    if W > K:
        child = jnp.concatenate(
            [par_rows,
             jnp.full(par_rows.shape[:-1] + (W - K,), PAD,
                      par_rows.dtype)], axis=-1)
    else:
        child = par_rows
    slot = jnp.arange(W) == to                                   # (W,)
    child = jnp.where(slot[None, None, :] & fwd.astype(bool),
                      new_v[:, :, None], child)                  # (G,Mc,W)
    child = jnp.where(picked[:, :, None], child, PAD)
    overflow = (vsel.sum(dtype=jnp.int32)
                - picked.sum(dtype=jnp.int32))
    return child.astype(jnp.int32), picked, overflow


def materialize_prefix(cmeta, n_fill, pol, pmask, src, dst, emask, *,
                       n_slots: int, max_embeddings: int, out_width: int):
    """Child OL store of the first ``n_fill`` compact survivor slots.

    ``cmeta`` (n_slots, 5) holds the survivors' candidate rows; slots at
    and past ``n_fill`` (a traced count) keep the PAD / all-False fill.
    Each slot slices its parent's and triple's rows out of the device-
    local stores (PP, P, G, M, K) / (PP, T, G, F) and writes its child
    rows in place into the (PP, n_slots, G, Mc, W) output — no stacked
    per-slot buffer, no transposed copy of either store.  Returns
    ``(ol, mask, overflow)`` with the device-local overflow count."""
    PP, _, G, _, _ = pol.shape

    def one(i, carry):
        ol, mask, over = carry
        cand = cmeta[i]
        take = functools.partial(jax.lax.dynamic_index_in_dim, axis=1,
                                 keepdims=False)
        ch, mk, ov = jax.vmap(functools.partial(
            materialize_rows, cand=cand, max_embeddings=max_embeddings,
            out_width=out_width))(
                take(pol, cand[0]), take(pmask, cand[0]),
                take(src, cand[4]), take(dst, cand[4]),
                take(emask, cand[4]))
        ol = jax.lax.dynamic_update_index_in_dim(ol, ch, i, axis=1)
        mask = jax.lax.dynamic_update_index_in_dim(mask, mk, i, axis=1)
        return ol, mask, over + ov.sum()

    init = (jnp.full((PP, n_slots, G, max_embeddings, out_width), -1,
                     jnp.int32),
            jnp.zeros((PP, n_slots, G, max_embeddings), bool),
            jnp.zeros((), jnp.int32))
    return jax.lax.fori_loop(0, n_fill, one, init)


# ---------------------------------------------------------------------------
# Spill layout — an exact store past the dense M (embedding_store="spill")
# ---------------------------------------------------------------------------
#
# Under the spill store a (pattern, graph) pair lives in ONE of two places,
# whole:
#
#   dense  ol (P, G, M, W) / mask (P, G, M): a pair with at most M
#          embeddings, whose parent pair was dense too — the layout above;
#   spill  rows (R, W) int32 / key (R,) int32: every embedding of a pair
#          with more than M, and of every child of a spilled pair, one row
#          each, keyed ``pattern * G + graph`` and sorted by key; unused
#          rows carry ``NOKEY`` and sort last.  R is bucketed.
#
# A pair's slots in the dense store stay masked while it spills, so the
# dense join and the spill join see disjoint embeddings: a candidate's
# local support is the dense kernel's count plus the graphs in which a
# spilled pair of its parent matches.  The spill join reads the edge
# occurrences regrouped by their source vertex (``build_adjacency``):
# each row looks up only its own vertices' neighbours (D of them) where
# the dense join scans a triple's F occurrences per graph.

NOKEY = 1 << 30


def build_adjacency(src: np.ndarray, dst: np.ndarray, emask: np.ndarray
                    ) -> tuple[np.ndarray, np.ndarray]:
    """The edge occurrences (NP, T, G, F) regrouped by source vertex:
    ``nbr`` (NP, G, V, D) the destination vertex and ``code`` (NP, G, V,
    D) the triple index of each occurrence leaving vertex v of graph g,
    -1 padded.  Per source vertex the entries keep the edge OL's (triple,
    occurrence) order.  The spill join reads a backward edge as THE code
    of the occurrence between two vertices, so a graph may hold at most
    one occurrence per ordered vertex pair: a ``ValueError`` otherwise."""
    at = np.flatnonzero(emask)
    u = src.reshape(-1)[at].astype(np.int64)
    v = dst.reshape(-1)[at].astype(np.int64)
    n, t, g, _ = np.unravel_index(at, src.shape)
    NP, _, G, _ = src.shape
    V = int(max(u.max(), v.max())) + 1 if u.size else 1
    # the occurrences come in (n, t, g, f) order, so a stable sort by
    # (n, g, u) keeps each source vertex's entries in (t, f) order
    group = (n * G + g) * V + u
    order = np.argsort(group, kind="stable")
    group, n, t, g, u, v = (group[order], n[order], t[order], g[order],
                            u[order], v[order])
    pair = group * V + v
    if np.unique(pair).size != pair.size:
        raise ValueError("the spill store needs graphs with at most one "
                         "edge occurrence per ordered vertex pair")
    idx = np.arange(u.size)
    new = np.r_[True, group[1:] != group[:-1]] if u.size else idx > 0
    slot = idx - np.maximum.accumulate(np.where(new, idx, 0))
    D = int(slot.max()) + 1 if u.size else 1
    nbr = np.full((NP, G, V, D), PAD, np.int32)
    code = np.full((NP, G, V, D), PAD, np.int32)
    nbr[n, g, u, slot] = v
    code[n, g, u, slot] = t
    return nbr, code


def empty_spill(n_parts: int, width: int, rows: int
                ) -> tuple[np.ndarray, np.ndarray]:
    """A spill table with no rows: (NP, R, W) PAD rows, (NP, R) NOKEY."""
    return (np.full((n_parts, rows, width), PAD, np.int32),
            np.full((n_parts, rows), NOKEY, np.int32))


def _ext_one(rows, g, cand, nbr, code):
    """Extensions of one candidate per row: ``rows`` (N, W) embeddings of
    graphs ``g`` (N,), ``cand`` (N, 5) or (5,) candidate rows.  Returns
    ``(ok (N, D), nb (N, D))``: neighbour d of the stub vertex completes
    a child embedding (a new vertex for a forward edge, the ``to``
    vertex for a backward one) — ``join_valid``'s test, per neighbour."""
    cand = jnp.broadcast_to(cand, rows.shape[:1] + (5,))
    stub, to, fwd, t = cand[:, 1], cand[:, 2], cand[:, 3], cand[:, 4]
    u = jnp.take_along_axis(rows, stub[:, None], axis=1)[:, 0]
    uc = jnp.maximum(u, 0)
    nb = nbr[g, uc]                                            # (N, D)
    cd = jnp.where((u >= 0)[:, None], code[g, uc], PAD)
    fresh = ~(nb[:, :, None] == rows[:, None, :]).any(-1)
    tov = jnp.take_along_axis(rows, to[:, None], axis=1)
    ok = (cd == t[:, None]) & jnp.where(fwd[:, None].astype(bool), fresh,
                                        nb == tov)
    return ok, nb


def _n_children(ok, fwd):
    """Child embeddings per row: every match of a forward edge, at most
    one of a backward edge (``materialize_rows`` keeps its first f)."""
    return jnp.where(fwd.astype(bool), ok.sum(-1, dtype=jnp.int32),
                     ok.any(-1).astype(jnp.int32))


def _row_tables(rows, g, nbr, code, n_triples: int):
    """Per-row extension tables, shared by every candidate of the row's
    pattern: ``fcnt`` (N, W·T) forward children from vertex slot i along
    triple t (flat ``i·T + t``) and ``edge`` (N, W·W) the triple of the
    occurrence from slot i to slot j, -1 if none (flat ``i·W + j``)."""
    N, W = rows.shape
    uc = jnp.maximum(rows, 0)
    nb = nbr[g[:, None], uc]                                   # (N, W, D)
    cd = jnp.where((rows >= 0)[:, :, None], code[g[:, None], uc], PAD)
    fresh = ~(nb[..., None] == rows[:, None, None, :]).any(-1)
    fcnt = ((cd[..., None] == jnp.arange(n_triples)) & fresh[..., None]
            ).sum(2, dtype=jnp.int32)                           # (N, W, T)
    hit = (nb[..., None] == rows[:, None, None, :]) & (rows >= 0)[:, None,
                                                                  None, :]
    edge = jnp.where(hit, cd[..., None], PAD).max(2)           # (N, W, W)
    return fcnt.reshape(N, W * n_triples), edge.reshape(N, W * W)


def _pick(fcnt, edge, cm, n_triples: int, width: int):
    """Child counts in each row of the candidates ``cm`` (K, 5), read
    from ``_row_tables`` through one-hot selector matmuls (no per-element
    gather): (N, K) int32.  Table values are small integers, exact in
    the f32 products at the highest precision."""
    stub, to, fwd, t = cm[:, 1], cm[:, 2], cm[:, 3], cm[:, 4]

    def select(table, cell):
        sel = jnp.arange(table.shape[1])[:, None] == cell[None, :]
        return jnp.dot(table.astype(jnp.float32), sel.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)

    f = select(fcnt, stub * n_triples + t)
    b = select(edge, stub * width + to) == t[None, :].astype(jnp.float32)
    return jnp.where(fwd.astype(bool)[None, :], f.astype(jnp.int32),
                     b.astype(jnp.int32))


def _row_chunk(rows: int, width: int, n_triples: int) -> int:
    """Rows per step of a row loop: a power of two dividing ``rows``
    that keeps the (chunk, W, W, max(W, T)) intermediates near 2^26."""
    c = 1 << max(6, (2 ** 26 // max(1, width * width * max(width,
                                                        n_triples)))
                 .bit_length() - 1)
    while rows % c:
        c //= 2
    return max(1, min(c, rows))


def spill_support_rows(meta, by_parent, cpos, rows, key, nbr, code, *,
                       n_graphs: int, n_triples: int):
    """Spill half of one partition's local support: per candidate, the
    graphs in which some spilled pair of its parent holds an embedding
    the candidate extends.  ``meta`` (C, 5) canonical candidate rows,
    ``by_parent`` (P, Kc) the candidates of each parent (-1 padded),
    ``cpos`` (C,) each candidate's flat index ``parent·Kc + j`` there;
    ``rows``/``key`` the partition's spill table.  Returns (C,) int32.

    A parent's rows are contiguous (sorted keys), so each parent sweeps
    only its own rows, in chunks, against its own Kc candidates; each
    chunk's hits are summed per graph by a one-hot matmul, and a
    candidate's count is the graphs with a hit.  A parent with no
    spilled rows costs no sweep."""
    R, W = rows.shape
    P, Kc = by_parent.shape
    G, T = n_graphs, n_triples
    rc = _row_chunk(R, W, T)
    starts = jnp.searchsorted(key, jnp.arange(P + 1) * G).astype(jnp.int32)

    def parent(p):
        lo, hi = starts[p], starts[p + 1]
        c = by_parent[p]
        cm = jnp.take(meta, jnp.maximum(c, 0), axis=0)         # (Kc, 5)

        def sweep(i, acc):
            first = lo + i * rc
            at = jnp.minimum(first, R - rc)
            r = jax.lax.dynamic_slice_in_dim(rows, at, rc)
            g = jax.lax.dynamic_slice_in_dim(key, at, rc) % G
            idx = at + jnp.arange(rc)
            valid = (idx >= first) & (idx < hi)
            fcnt, edge = _row_tables(r, g, nbr, code, T)
            hit = (_pick(fcnt, edge, cm, T, W) > 0) & (c >= 0)[None, :]
            in_g = (g[:, None] == jnp.arange(G)[None, :]) & valid[:, None]
            return acc + jnp.dot(in_g.T.astype(jnp.float32),
                                 hit.astype(jnp.float32),
                                 precision=jax.lax.Precision.HIGHEST)

        acc = jax.lax.fori_loop(0, (hi - lo + rc - 1) // rc, sweep,
                                jnp.zeros((G, Kc), jnp.float32))
        return (acc > 0).sum(0, dtype=jnp.int32)

    counts = jax.lax.map(parent, jnp.arange(P))                 # (P, Kc)
    flat = jnp.concatenate([counts.reshape(-1),
                            jnp.zeros((1,), jnp.int32)])
    return jnp.take(flat, jnp.where(cpos < 0, P * Kc, cpos))


def _last_le(get, lo, hi, target, steps: int):
    """Largest index in [lo, hi) whose ``get`` is <= ``target``, for a
    non-decreasing ``get`` with ``get(lo) <= target``: a binary search
    of ``steps`` halvings, vectorized over the queries."""
    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) // 2
        le = get(mid) <= target
        go = hi - lo > 1
        return (jnp.where(go & le, mid, lo), jnp.where(go & ~le, mid, hi))

    return jax.lax.fori_loop(0, steps, body, (lo, hi))[0]


def spill_child_counts(cmeta, n_fill, pol, pmask, rows, key, nbr, code, *,
                       n_triples: int, max_embeddings: int):
    """Count pass of one partition's spill materialization.

    For survivor slot s (``cmeta`` (S, 5), the first ``n_fill`` real)
    and graph g: ``cdx`` (S, G, M+1) the exclusive prefix over the dense
    parent rows of their child counts, ``csx`` (R+1, S) the same over the
    spill rows, ``bounds`` (P, G+1) where each parent pair's rows start
    in the spill table, and ``spilled`` (S, G) whether the child pair
    goes to the spill (more than M children, or a spilled parent).
    Returns those and ``(rows, pairs)`` the child spill table's size."""
    P, G, Mp, W = pol.shape
    S = cmeta.shape[0]
    R = rows.shape[0]
    valid_s = jnp.arange(S) < n_fill

    def dense(xs):
        cand, live = xs
        r = jnp.take(pol, cand[0], axis=0).reshape(G * Mp, W)
        m = jnp.take(pmask, cand[0], axis=0).reshape(G * Mp)
        ok, _ = _ext_one(r, jnp.repeat(jnp.arange(G), Mp), cand, nbr, code)
        n = jnp.where(m, _n_children(ok, cand[3]), 0).reshape(G, Mp)
        return jnp.where(live, n, 0)

    cd = jax.lax.map(
        lambda xs: jax.lax.cond(xs[1], dense,
                                lambda _: jnp.zeros((G, Mp), jnp.int32), xs),
        (cmeta, valid_s))
    cdx = jnp.concatenate([jnp.zeros((S, G, 1), jnp.int32),
                           jnp.cumsum(cd, axis=-1)], axis=-1)
    rc = _row_chunk(R, W, n_triples)

    def sweep(xs):
        r, k = xs
        fcnt, edge = _row_tables(r, k % G, nbr, code, n_triples)
        own = (cmeta[None, :, 0] == (k // G)[:, None]) & valid_s[None]
        return jnp.where(own & (k < NOKEY)[:, None],
                         _pick(fcnt, edge, cmeta, n_triples, W), 0)

    def chunk(xs):
        return jax.lax.cond(xs[1][0] < NOKEY, sweep,
                            lambda _: jnp.zeros((rc, S), jnp.int32), xs)

    cs = jax.lax.map(chunk, (rows.reshape(R // rc, rc, W),
                             key.reshape(R // rc, rc))).reshape(R, S)
    csx = jnp.concatenate([jnp.zeros((1, S), jnp.int32),
                           jnp.cumsum(cs, axis=0)], axis=0)
    bounds = jnp.searchsorted(
        key, (jnp.arange(P)[:, None] * G + jnp.arange(G + 1)[None, :]),
        side="left").astype(jnp.int32)                           # (P, G+1)
    pb = jnp.take(bounds, cmeta[:, 0], axis=0)                  # (S, G+1)
    col = jnp.arange(S)[:, None]
    n_sp = csx[pb[:, 1:], col] - csx[pb[:, :-1], col]           # (S, G)
    n_d = cdx[..., -1]
    spilled = valid_s[:, None] & ((n_sp > 0) | (n_d > max_embeddings))
    n = jnp.where(spilled, n_d + n_sp, 0)
    return (cdx, csx, bounds, spilled,
            n.sum(dtype=jnp.int32), spilled.sum(dtype=jnp.int32))


def spill_write(cmeta, cdx, csx, bounds, spilled, pol, rows, key, nbr,
                code, *, out_rows: int, out_width: int):
    """Write pass: the child spill table of one partition, ``out_rows``
    rows of width ``out_width`` sorted by (survivor slot, graph), from
    the count pass's outputs.  Output row o is the k-th child of its
    pair: found by a binary search over the pair's parent rows (dense or
    spilled) in their child-count prefix, then the (k - before)-th
    extension of that parent row."""
    P, G, Mp, W = pol.shape
    S = cmeta.shape[0]
    R = rows.shape[0]
    n_d = cdx[..., -1]
    pb = jnp.take(bounds, cmeta[:, 0], axis=0)
    col = jnp.arange(S)[:, None]
    n_sp = csx[pb[:, 1:], col] - csx[pb[:, :-1], col]
    per = jnp.where(spilled, n_d + n_sp, 0).reshape(-1)         # (S·G,)
    incl = jnp.cumsum(per)
    total = incl[-1]
    qc = min(out_rows, 16384)
    pol_flat = pol.reshape(P * G * Mp, W)
    csx_flat = csx.reshape(-1)
    cdx_flat = cdx.reshape(-1)

    def chunk(o):
        return jax.lax.cond(
            o[0] < total, write, lambda _: (
                jnp.full((qc, out_width), PAD, jnp.int32),
                jnp.full((qc,), NOKEY, jnp.int32)), o)

    def write(o):
        q = jnp.minimum(jnp.searchsorted(incl, o, side="right"), S * G - 1)
        s, g = q // G, q % G
        k = o - (incl[q] - per[q])
        cand = jnp.take(cmeta, s, axis=0)                        # (qc, 5)
        p = cand[:, 0]
        # dense parent rows of the pair: the m-th row holds child k
        base_d = (s * G + g) * (Mp + 1)
        m = _last_le(lambda i: jnp.take(cdx_flat, base_d + i),
                     jnp.zeros_like(k), jnp.full_like(k, Mp), k,
                     (Mp + 1).bit_length())
        j_d = k - jnp.take(cdx_flat, base_d + m)
        row_d = jnp.take(pol_flat, (p * G + g) * Mp + m, axis=0)
        # spilled parent rows of the pair: rows [b0, b1) of the table
        b0 = jnp.take(bounds.reshape(-1), p * (G + 1) + g)
        b1 = jnp.take(bounds.reshape(-1), p * (G + 1) + g + 1)
        base_s = jnp.take(csx_flat, b0 * S + s)
        r = _last_le(lambda i: jnp.take(csx_flat, i * S + s), b0,
                     jnp.maximum(b1, b0 + 1), base_s + k,
                     (R + 1).bit_length())
        j_s = base_s + k - jnp.take(csx_flat, r * S + s)
        row_s = jnp.take(rows, jnp.minimum(r, R - 1), axis=0)
        from_spill = (b1 > b0)[:, None]
        row = jnp.where(from_spill, row_s, row_d)
        j = jnp.where(from_spill[:, 0], j_s, j_d)
        ok, nb = _ext_one(row, g, cand, nbr, code)
        d = jnp.argmax(ok & (jnp.cumsum(ok, axis=-1) == (j + 1)[:, None]),
                       axis=-1)
        new_v = jnp.take_along_axis(nb, d[:, None], axis=1)[:, 0]
        child = jnp.concatenate(
            [row, jnp.full((qc, out_width - W), PAD, jnp.int32)], axis=1)
        fwd = cand[:, 3].astype(bool)
        child = jnp.where((jnp.arange(out_width) == cand[:, 2:3])
                          & fwd[:, None], new_v[:, None], child)
        live = o < total
        return (jnp.where(live[:, None], child, PAD),
                jnp.where(live, s * G + g, NOKEY).astype(jnp.int32))

    out, keys = jax.lax.map(chunk, jnp.arange(out_rows).reshape(-1, qc))
    return out.reshape(out_rows, out_width), keys.reshape(out_rows)
