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
from .graphdb import Graph
from .host_miner import OccurrenceList

__all__ = [
    "EdgeOL", "LevelOL", "CandidateMeta",
    "build_edge_ol", "level1_ol", "candidate_meta",
    "join_valid", "local_supports_ref", "support_bits_ref",
    "materialize_rows", "materialize_prefix",
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

    @property
    def shape(self):
        return self.src.shape


@dataclasses.dataclass
class LevelOL:
    """Stacked OLs for all frequent patterns of one level."""

    ol: jnp.ndarray        # (P, G, M, K) int32, PAD-filled
    mask: jnp.ndarray      # (P, G, M) bool

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
    """
    tindex = {tuple(t): i for i, t in enumerate(triples)}
    G = pad_graphs or len(graphs)
    occs: list[list[list[tuple[int, int]]]] = [
        [[] for _ in range(G)] for _ in range(len(triples))]
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
    return EdgeOL(np.asarray(triples, np.int32), src, dst, mask, tindex)


def level1_ol(
    codes: Sequence[Code],
    eol: EdgeOL,
    *,
    max_embeddings: int,
) -> LevelOL:
    """F_1 OLs from the edge-OL (preparation phase's emitted patterns).

    A single-edge pattern (0,1,a,e,b) embeds at every directed occurrence
    of (a,e,b); when a == b the two orientations are distinct embeddings
    and already both present in the directed edge-OL.
    """
    P, M = len(codes), max_embeddings
    _, G, F = eol.src.shape
    ol = np.full((P, G, M, 2), PAD, np.int32)
    mask = np.zeros((P, G, M), bool)
    for pi, code in enumerate(codes):
        (i, j, a, e, b) = code[0]
        ti = eol.triple_index[(a, e, b)]
        take = min(M, F)
        ol[pi, :, :take, 0] = eol.src[ti, :, :take]
        ol[pi, :, :take, 1] = eol.dst[ti, :, :take]
        mask[pi, :, :take] = eol.mask[ti, :, :take]
    return LevelOL(jnp.asarray(ol), jnp.asarray(mask))


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
