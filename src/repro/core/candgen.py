"""Rightmost-path candidate generation (paper §IV-A.1).

Iteration k turns each frequent size-k pattern into size-(k+1) candidates
by adjoining one frequent edge:

  * **forward edge** — from any vertex on the rightmost path (RMP) to a
    brand-new vertex, which receives the next DFS id;
  * **back edge** — from the rightmost vertex (RMV) to another RMP vertex,
    provided the edge does not already exist (no multigraphs — paper
    Fig. 4 discussion).

The adjoined edge's label triple must belong to the globally frequent
edge alphabet (``F_1``), the Apriori prune.  Every candidate then passes
the min-dfs-code canonicality test (`dfscode.canonical_prefix`, the walk
behind `dfscode.is_canonical`): of all generation paths of a pattern
exactly one survives, so the candidate space is duplicate-free
(completeness + no recount).

Candidates are *metadata* (host-side, tiny).  Each carries the join recipe
(`Extension`) the device layer executes against partition-local occurrence
lists.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import tracing
from .dfscode import (Code, Edge5, canonical_prefix, code_to_graph,
                      rightmost_path, code_array_rightmost_path,
                      code_array_vertex_labels, min_dfs_canonical_array)

__all__ = ["Extension", "Candidate", "EdgeAlphabet", "generate_candidates",
           "filter_speculative", "CandidateSchedule", "schedule_candidates",
           "pad_schedule", "device_candidates", "device_schedule",
           "device_candgen_jit", "candidates_from_arrays"]


@dataclasses.dataclass(frozen=True)
class Extension:
    """Join recipe for the device layer.

    forward:  child_emb = parent_emb + [v]  for edge occurrences (u, v) of
              ``triple`` with u == parent_emb[stub] and v not in parent_emb
    backward: child_emb = parent_emb        if an occurrence (u, v) of
              ``triple`` has u == parent_emb[stub] and v == parent_emb[to]
    """

    forward: bool
    stub: int            # dfs id of the existing attachment vertex
    to: int              # dfs id of other endpoint (new id if forward)
    triple: tuple[int, int, int]  # (l_stub, l_edge, l_other)


@dataclasses.dataclass(frozen=True)
class Candidate:
    code: Code           # parent code + one edge (already canonical)
    parent: int          # index into F_k
    ext: Extension

    @property
    def size(self) -> int:
        return len(self.code)


class EdgeAlphabet:
    """Globally frequent single-edge label triples (= F_1 keys).

    Stored symmetrically: ``(a, e, b)`` present iff ``(b, e, a)`` present.
    The *canonical* triple has ``a <= b``.
    """

    def __init__(self, triples: Iterable[tuple[int, int, int]]):
        s = set()
        for (a, e, b) in triples:
            s.add((int(a), int(e), int(b)))
            s.add((int(b), int(e), int(a)))
        self._set = frozenset(s)
        self.vlabels = sorted({a for (a, _, _) in s})
        self.elabels = sorted({e for (_, e, _) in s})

    def __contains__(self, triple: tuple[int, int, int]) -> bool:
        return tuple(int(x) for x in triple) in self._set

    def __len__(self) -> int:
        return len(self._set)

    def canonical(self) -> list[tuple[int, int, int]]:
        return sorted(t for t in self._set if t[0] <= t[2])

    def partners(self, label: int) -> list[tuple[int, int]]:
        """All (edge_label, other_vertex_label) adjoinable to ``label``."""
        return sorted({(e, b) for (a, e, b) in self._set if a == label})


def generate_candidates(
    frequent: Sequence[Code],
    alphabet: EdgeAlphabet,
) -> list[Candidate]:
    """All canonical size-(k+1) candidates from the frequent size-k set.

    Host-bound and on the critical path of the mining loop: the device
    idles while it runs, unless the loop's speculation hides it in a
    level program's shadow.  It costs O(|F_k| · RMP · alphabet) plus one
    canonicality walk per raw candidate, seconds on a wide level.  The
    counters ``canon_tested`` (raw candidates walked) and ``canon_early``
    (those rejected before their last position) are added once per call.
    """
    out: list[Candidate] = []
    tested = early = 0
    for pidx, code in enumerate(frequent):
        g = code_to_graph(code)
        rmp = rightmost_path(code)
        rmv = rmp[-1]
        existing = {(min(int(u), int(v)), max(int(u), int(v)))
                    for (u, v) in g.edges}
        vl = g.vlabels
        n_v = g.n_vertices

        # ---- back edges: RMV -> strict-ancestor RMP vertex
        for w in rmp[:-1]:
            if (min(rmv, w), max(rmv, w)) in existing:
                continue  # would duplicate an edge (multigraph) — skip
            for (e_lab, other) in alphabet.partners(int(vl[rmv])):
                if other != int(vl[w]):
                    continue
                edge: Edge5 = (rmv, w, int(vl[rmv]), e_lab, int(vl[w]))
                child = code + (edge,)
                tested += 1
                at = canonical_prefix(child)
                early += at < len(code)
                if at == len(child):
                    out.append(Candidate(child, pidx,
                                         Extension(False, rmv, w,
                                                   (int(vl[rmv]), e_lab, int(vl[w])))))

        # ---- forward edges: any RMP vertex -> new vertex (id = n_v)
        for w in rmp:
            for (e_lab, other) in alphabet.partners(int(vl[w])):
                edge = (int(w), n_v, int(vl[w]), e_lab, other)
                child = code + (edge,)
                tested += 1
                at = canonical_prefix(child)
                early += at < len(code)
                if at == len(child):
                    out.append(Candidate(child, pidx,
                                         Extension(True, int(w), n_v,
                                                   (int(vl[w]), e_lab, other))))
    tracing.count("canon_tested", tested)
    tracing.count("canon_early", early)
    return out


def filter_speculative(spec: Sequence[Candidate],
                       keep: Sequence[int]) -> list[Candidate]:
    """Narrow a speculatively generated candidate list to the surviving
    parents (the overlapped-candgen path, DESIGN.md §11).

    ``spec`` was generated from level k's FULL candidate list — a
    superset of the frequent set F_k, available before the device
    program reports which candidates survived.  ``keep`` holds the
    surviving indices, ascending.  Because ``generate_candidates``
    visits parents in list order and each parent's extensions (RMP,
    existing-edge set, canonicality) depend on that parent's code alone,
    dropping non-survivors and remapping ``parent`` to its rank in
    ``keep`` yields EXACTLY ``generate_candidates([F[i] for i in keep],
    alphabet)`` — same candidates, same order.  The equivalence is
    pinned by a conformance test; the speculation itself is therefore
    semantically free, costing only wasted host work when survival is
    sparse."""
    rank = {int(p): r for r, p in enumerate(keep)}
    return [dataclasses.replace(c, parent=rank[c.parent])
            for c in spec if c.parent in rank]


# ---------------------------------------------------------------------------
# Parent-grouped candidate scheduling (fused map-phase feed)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CandidateSchedule:
    """Tile-aligned candidate order for the fused level kernel.

    Candidates sorted by ``(parent, triple)`` and padded per group so
    every ``tile_c``-row block shares one parent OL and one edge-OL —
    the kernel streams those HBM tiles once per *block* instead of once
    per candidate.  ``inv[i]`` is the scheduled row of canonical
    candidate ``i``; gathering scheduled outputs with ``inv`` restores
    canonical order (the permutation round-trip the miner relies on).
    """

    meta: np.ndarray     # (Cs, 6) int32 [parent, stub, to, fwd, triple, valid]
    tiles: np.ndarray    # (Cs/tile_c, 2) int32 [parent, triple] per block
    inv: np.ndarray      # (C,) int32 — scheduled row of canonical candidate i
    tile_c: int

    @property
    def n_tiles(self) -> int:
        return self.tiles.shape[0]


def _padded_size(group_sizes: np.ndarray, tc: int) -> int:
    return int((-(-group_sizes // tc) * tc).sum())


def schedule_candidates(meta: np.ndarray, tile_c: int = 8, *,
                        max_inflation: float = 1.5) -> CandidateSchedule:
    """Host-side pass: group ``(C, 5)`` candidate metadata into uniform
    ``(parent, triple)`` tiles of ``tile_c`` rows.

    Stable-sorts by parent (major) then triple (minor), chunks each group
    into ``tile_c`` blocks, and pads the last block of each group with
    ``valid=0`` rows carrying the group's own (parent, triple) so block
    descriptors stay uniform.

    The tile size ADAPTS to the grouping structure: padding inflates the
    scheduled row count by one partial tile per distinct (parent, triple)
    pair, and padded rows burn real kernel compute (they are masked, not
    skipped).  Starting from ``tile_c`` and halving, the largest tile
    size whose padded row count stays within ``max_inflation``·C is
    chosen — candidate sets with heavy sibling sharing (the common case:
    every parent emits one candidate per alphabet partner) get wide
    blocks and maximal HBM-tile reuse, while adversarially scattered sets
    degrade gracefully to ``tile_c=1`` (still single-launch, still no
    (C, G) intermediates) instead of 8×-ing the map-phase work.

    Shape bucketing pads the finished schedule via ``pad_schedule``
    (whole invalid tiles + a parked inverse-permutation tail) — see
    ``core/buckets.py`` and the bucketed path of ``run_level``.
    """
    meta = np.asarray(meta, np.int32).reshape(-1, 5)
    C = meta.shape[0]
    if tile_c < 1:
        raise ValueError(f"tile_c={tile_c} must be >= 1")
    if C == 0:                       # emit one fully-padded tile
        return CandidateSchedule(
            np.tile(np.asarray([0, 0, 0, 1, 0, 0], np.int32), (tile_c, 1)),
            np.zeros((1, 2), np.int32), np.empty(0, np.int32), tile_c)

    order = np.lexsort((meta[:, 4], meta[:, 0]))     # triple minor, parent major
    keys = meta[order][:, [0, 4]]
    boundaries = np.any(keys[1:] != keys[:-1], axis=1)
    group_sizes = np.diff(np.concatenate(
        [[0], np.flatnonzero(boundaries) + 1, [C]]))
    while tile_c > 1 and _padded_size(group_sizes, tile_c) > max_inflation * C:
        tile_c = tile_c // 2

    starts = np.cumsum(group_sizes) - group_sizes    # into `order`
    tiles_per_group = -(-group_sizes // tile_c)
    padded = tiles_per_group * tile_c
    offsets = np.cumsum(padded) - padded             # group start row in sched
    Cs = int(padded.sum())

    group_keys = keys[starts]                        # (n_groups, 2) [parent, triple]
    tiles = np.repeat(group_keys, tiles_per_group, axis=0)

    sched = np.empty((Cs, 6), np.int32)              # pad rows first …
    sched[:, [0, 4]] = np.repeat(group_keys, padded, axis=0)
    sched[:, [1, 2]] = 0
    sched[:, 3] = 1
    sched[:, 5] = 0
    # … then overwrite the leading rows of each group span with the real
    # candidates (padding sits only at group tails, so every tile_c block
    # stays within one group)
    pos = np.repeat(offsets, group_sizes) + (np.arange(C)
                                             - np.repeat(starts, group_sizes))
    sched[pos, :5] = meta[order]
    sched[pos, 5] = 1
    inv = np.empty(C, np.int32)
    inv[order] = pos
    return CandidateSchedule(sched, tiles.astype(np.int32), inv, tile_c)


def pad_schedule(sched: CandidateSchedule, *, rows_to: int | None = None,
                 inv_to: int | None = None) -> CandidateSchedule:
    """Bucket-pad an existing schedule (see ``schedule_candidates``):
    whole invalid tiles up to ``rows_to`` scheduled rows, and the
    inverse permutation out to ``inv_to`` padded candidates."""
    meta, tiles, inv = _pad_schedule(sched.meta, sched.tiles, sched.inv,
                                     sched.tile_c, rows_to, inv_to)
    return CandidateSchedule(meta, tiles, inv, sched.tile_c)


def _pad_schedule(sched: np.ndarray, tiles: np.ndarray, inv: np.ndarray,
                  tile_c: int, pad_rows_to: int | None,
                  pad_inv_to: int | None
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Bucket padding: whole invalid tiles on the row axis, parked
    pointers on the inverse permutation (see ``schedule_candidates``)."""
    Cs = sched.shape[0]
    target = Cs
    if pad_rows_to is not None:
        target = max(Cs, -(-pad_rows_to // tile_c) * tile_c)
    need_inv = pad_inv_to is not None and pad_inv_to > inv.shape[0]
    if need_inv and target == Cs and not (sched[:, 5] == 0).any():
        target += tile_c             # guarantee a row to park inv padding
    if target > Cs:
        pad_row = np.asarray([0, 0, 0, 1, 0, 0], np.int32)
        sched = np.concatenate([sched,
                                np.tile(pad_row, (target - Cs, 1))])
        tiles = np.concatenate(
            [tiles, np.zeros(((target - Cs) // tile_c, 2), np.int32)])
    if need_inv:
        # an invalid row always exists here (appended above if needed),
        # so padded candidates can never read a real candidate's support
        park = int(np.flatnonzero(sched[:, 5] == 0)[0])
        inv = np.concatenate(
            [inv, np.full(pad_inv_to - inv.shape[0], park, np.int32)])
    return sched, tiles, inv


# ---------------------------------------------------------------------------
# Device-side candidate generation + schedule (pipeline="device_loop",
# DESIGN.md §13) — `generate_candidates` and `schedule_candidates` recast
# as fixed-shape jnp programs so the level loop can stay on device.
# ---------------------------------------------------------------------------

def _compact_mask(mask, cap: int):
    """Prefix-sum compact a flat bool mask into ``cap`` index slots.

    Returns (idx (cap,) int32 — flat indices of the first ``cap`` set
    entries in order, 0-filled past ``n``; n; overflow)."""
    pos = jnp.cumsum(mask) - 1
    n = mask.sum()
    dest = jnp.where(mask, pos, cap)
    idx = jnp.zeros((cap,), jnp.int32).at[dest].set(
        jnp.arange(mask.shape[0], dtype=jnp.int32), mode="drop")
    return idx, n.astype(jnp.int32), n > cap


def _parent_slots(code, pvalid, triples, n_vertex_slots: int):
    """All structural extension slots of one parent code (pre-canonicality).

    Slot order matches `generate_candidates` exactly: back-edge slots
    (RMP ancestors root-first × alphabet rows) then forward slots (RMP
    vertices root-first × alphabet rows); the triples table is the sorted
    directed closure of the alphabet, so masking rows on the stub label
    leaves the same sorted ``partners`` subsequence the host iterates.

    Returns (ok (SLOTS,), edge (SLOTS, 5), meta (SLOTS, 4) [stub, to,
    fwd, triple]) with SLOTS = (2·NV − 1)·T.
    """
    NV = n_vertex_slots
    L = code.shape[0]
    T = triples.shape[0]
    valid_e = code[:, 0] >= 0
    ne = valid_e.sum()
    vl = code_array_vertex_labels(code, NV)
    rmp, rmp_len, n_v = code_array_rightmost_path(code, NV)
    rmv = n_v - 1
    umin = jnp.minimum(code[:, 0], code[:, 1])
    umax = jnp.maximum(code[:, 0], code[:, 1])

    ta, te, tb = triples[:, 0], triples[:, 1], triples[:, 2]
    l_rmv = vl[jnp.clip(rmv, 0, NV - 1)]

    # ---- back-edge slots: (w_pos, t) for w_pos in [0, NV-2]
    wb = rmp[:NV - 1]                                     # (NV-1,)
    lb = vl[jnp.clip(wb, 0, NV - 1)]
    edge_dup = (valid_e[None, :] & (umin[None, :] == wb[:, None])
                & (umax[None, :] == rmv)).any(axis=1)     # (NV-1,)
    okb = ((jnp.arange(NV - 1) < rmp_len - 1)[:, None]
           & pvalid & (ne < L)
           & (ta[None, :] == l_rmv) & (tb[None, :] == lb[:, None])
           & ~edge_dup[:, None])                          # (NV-1, T)
    bi = jnp.broadcast_to(rmv, (NV - 1, T))
    bj = jnp.broadcast_to(wb[:, None], (NV - 1, T))
    b_edge = jnp.stack([bi, bj,
                        jnp.broadcast_to(ta[None, :], (NV - 1, T)),
                        jnp.broadcast_to(te[None, :], (NV - 1, T)),
                        jnp.broadcast_to(tb[None, :], (NV - 1, T))], axis=-1)
    b_meta = jnp.stack([bi, bj, jnp.zeros((NV - 1, T), jnp.int32),
                        jnp.broadcast_to(jnp.arange(T)[None, :],
                                         (NV - 1, T))], axis=-1)

    # ---- forward slots: (w_pos, t) for w_pos in [0, NV-1]
    wf = rmp                                              # (NV,)
    lf = vl[jnp.clip(wf, 0, NV - 1)]
    okf = ((jnp.arange(NV) < rmp_len)[:, None]
           & pvalid & (ne < L) & (n_v < NV)
           & (ta[None, :] == lf[:, None]))                # (NV, T)
    fi = jnp.broadcast_to(wf[:, None], (NV, T))
    fj = jnp.broadcast_to(n_v, (NV, T))
    f_edge = jnp.stack([fi, fj,
                        jnp.broadcast_to(ta[None, :], (NV, T)),
                        jnp.broadcast_to(te[None, :], (NV, T)),
                        jnp.broadcast_to(tb[None, :], (NV, T))], axis=-1)
    f_meta = jnp.stack([fi, fj, jnp.ones((NV, T), jnp.int32),
                        jnp.broadcast_to(jnp.arange(T)[None, :],
                                         (NV, T))], axis=-1)

    ok = jnp.concatenate([okb.reshape(-1), okf.reshape(-1)])
    edge = jnp.concatenate([b_edge.reshape(-1, 5), f_edge.reshape(-1, 5)])
    meta = jnp.concatenate([b_meta.reshape(-1, 4), f_meta.reshape(-1, 4)])
    return ok, edge.astype(jnp.int32), meta.astype(jnp.int32)


def device_candidates(codes, n_par, triples, *, n_vertex_slots: int,
                      raw_budget: int, budget: int, max_states: int):
    """Device twin of `generate_candidates` over array-shaped codes.

    Two-stage compaction keeps the expensive canonicality machine off
    label-mismatched slots: structural slots are prefix-sum compacted
    into ``raw_budget`` rows first, `min_dfs_canonical_array` is vmapped
    only over those, and canonical survivors compact again into
    ``budget`` rows — parent-major and order-preserving, so row r is
    EXACTLY the r-th candidate the host generator would emit.

    Returns (meta (budget, 5) [parent, stub, to, fwd, triple] pad rows
    [0,0,0,1,0]; child_codes (budget, L, 5) -1-padded; n_cand; flags
    (3,) bool [raw overflow, canonical overflow, state overflow]).
    """
    SP, L = codes.shape[0], codes.shape[1]
    NV = n_vertex_slots
    pvalid = jnp.arange(SP) < n_par
    ok, edge, meta4 = jax.vmap(
        lambda c, pv: _parent_slots(c, pv, triples, NV))(codes, pvalid)
    SLOTS = ok.shape[1]

    raw_idx, n_raw, raw_ovf = _compact_mask(ok.reshape(-1), raw_budget)
    raw_real = jnp.arange(raw_budget) < n_raw
    p_r = raw_idx // SLOTS                                # (CBR,)
    pcode = codes[p_r]                                    # (CBR, L, 5)
    e_r = edge.reshape(-1, 5)[raw_idx]
    m_r = meta4.reshape(-1, 4)[raw_idx]
    ne_r = (pcode[:, :, 0] >= 0).sum(axis=1)
    rows = jnp.arange(L)
    child = jnp.where((rows[None, :, None] == ne_r[:, None, None]),
                      e_r[:, None, :], pcode)             # (CBR, L, 5)

    canon, st_ovf = jax.vmap(
        lambda c: min_dfs_canonical_array(
            c, n_vertex_slots=NV, max_states=max_states))(child)

    can_idx, n_cand, can_ovf = _compact_mask(canon & raw_real, budget)
    can_real = jnp.arange(budget) < n_cand
    meta = jnp.where(
        can_real[:, None],
        jnp.concatenate([p_r[can_idx, None], m_r[can_idx]], axis=1),
        jnp.asarray([0, 0, 0, 1, 0], jnp.int32)[None, :])
    out_codes = jnp.where(can_real[:, None, None], child[can_idx], -1)
    flags = jnp.stack([raw_ovf, can_ovf, (st_ovf & raw_real).any()])
    return meta, out_codes, n_cand, flags


@functools.lru_cache(maxsize=64)
def device_candgen_jit(L: int, n_vertex_slots: int, raw_budget: int,
                       budget: int, max_states: int):
    """Cached jitted `device_candidates` for the candgen="device"
    stepping stone (standalone, outside the whole-run loop)."""
    return jax.jit(functools.partial(
        device_candidates, n_vertex_slots=n_vertex_slots,
        raw_budget=raw_budget, budget=budget, max_states=max_states))


def candidates_from_arrays(meta: np.ndarray, child_codes: np.ndarray,
                           n_cand: int,
                           triples: Sequence[tuple[int, int, int]]
                           ) -> list[Candidate]:
    """Rebuild host `Candidate` objects from `device_candidates` output
    (same candidates, same order — pinned by tests/test_device_loop.py)."""
    from .dfscode import array_to_code  # local: avoid cycle at import time
    out = []
    for r in range(int(n_cand)):
        p, stub, to, fwd, tri = (int(x) for x in meta[r])
        a, e, b = triples[tri]
        out.append(Candidate(array_to_code(child_codes[r]), p,
                             Extension(bool(fwd), stub, to,
                                       (int(a), int(e), int(b)))))
    return out


def device_schedule(meta, n_cand, *, tile_c: int, n_triples: int, rows: int):
    """Device twin of `schedule_candidates` under fixed shapes.

    Stable-sorts candidate slots by (parent, triple), sizes each group's
    tile-aligned span with a prefix sum, and emits the same
    (sched_meta, tiles, inv) triple the fused kernel consumes — all jnp,
    so it runs inside the while_loop body.  ``rows``/``tile_c`` are
    static; if the tile-padded row count exceeds ``rows`` the overflow
    flag is set (the driver bails to the host pipeline).  Padding slots
    of ``inv`` park at row 0 — downstream gathers mask on c_real.
    """
    CB = meta.shape[0]
    tc = tile_c
    NT = rows // tc
    BIG = jnp.int32(1 << 30)
    valid = jnp.arange(CB) < n_cand
    key = meta[:, 0] * n_triples + meta[:, 4]
    skey_in = jnp.where(valid, key, BIG)
    order = jnp.argsort(skey_in)                     # stable
    skey = skey_in[order]
    svalid = valid[order]

    first = svalid & ((jnp.arange(CB) == 0) | (skey != jnp.roll(skey, 1)))
    gid = jnp.cumsum(first) - 1                      # group id per sorted row
    n_groups = first.sum()
    gs = jnp.zeros((CB,), jnp.int32).at[
        jnp.where(svalid, gid, CB)].add(1, mode="drop")
    tpg = -(-gs // tc)                               # tiles per group
    padded = tpg * tc
    goff = jnp.cumsum(padded) - padded               # group start sched row
    gstart = jnp.cumsum(gs) - gs                     # group start sorted row
    cg = jnp.clip(gid, 0, CB - 1)
    srows = goff[cg] + (jnp.arange(CB) - gstart[cg])
    ovf = padded.sum() > rows

    inv = jnp.zeros((CB,), jnp.int32).at[order].set(
        jnp.where(svalid, jnp.clip(srows, 0, rows - 1), 0))

    gkeys = jnp.zeros((CB,), jnp.int32).at[
        jnp.where(first, gid, CB)].set(skey, mode="drop")
    tend = jnp.cumsum(tpg)
    tgid = jnp.searchsorted(tend, jnp.arange(NT), side="right")
    tkey = jnp.where(tgid < n_groups, gkeys[jnp.clip(tgid, 0, CB - 1)], 0)
    tiles = jnp.stack([tkey // n_triples, tkey % n_triples], axis=1)

    rkey = tkey[jnp.arange(rows) // tc]              # (rows,)
    zero = jnp.zeros((rows,), jnp.int32)
    sched = jnp.stack([rkey // n_triples, zero, zero, zero + 1,
                       rkey % n_triples, zero], axis=1)
    vals = jnp.concatenate(
        [meta[order], jnp.ones((CB, 1), jnp.int32)], axis=1)
    dest = jnp.where(svalid & (srows < rows), srows, rows)
    sched = sched.at[dest].set(vals, mode="drop")
    return sched, tiles.astype(jnp.int32), inv, ovf
