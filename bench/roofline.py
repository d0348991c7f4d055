"""Least HBM bytes the support work of a level must move, from the
level's shapes alone.

The support kernel joins each candidate's parent occurrence list with
the edge occurrence list of the candidate's label triple, in every
graph of every partition.  Whatever the tiling, it has to read each
distinct parent's store and each distinct triple's edge store at least
once, and write one support and one embedding count per candidate and
partition.  Two counts of the parents' and triples' stores:

:func:`support_bytes`, the dense store the program holds, one slot for
each of M embeddings in every (pattern, graph) pair:

    parents: NP * G * P * (M * K * 4 + M)   int32 vertex ids + int8 mask
    edges:   NP * G * T * F * 9             int32 src, int32 dst, int8 mask
    outputs: NP * C * 8                     int32 support + int32 count

:func:`least_support_bytes`, the embeddings that exist, whatever store
holds them:

    parents: ids * 4 + rows                 vertex ids + one byte each
    edges:   edge_rows * 9
    outputs: NP * C * 8

G is the graphs per partition, P and T the distinct parents and
triples the level's candidates touch, C the candidates, M the
embeddings per graph the dense store holds (the configured cap doubled
until every parent embedding fits, as exactness demands), K the
parents' most vertices and F the most occurrences of one triple in one
graph.  ``rows`` is the touched parents' real embeddings summed over
every graph, ``ids`` the same with each embedding counted by its
parent's own vertex count, and ``edge_rows`` the touched triples' real
occurrences summed over every graph.  Every shape comes from the
reference miner's level, never from the program's schedule, so a
change of tiling cannot change either count.
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class LevelShape:
    partitions: int      # NP
    graphs: int          # G, per partition
    parents: int         # P
    triples: int         # T
    candidates: int      # C
    embeddings: int      # M
    vertices: int        # K
    occurrences: int     # F
    rows: int            # real embeddings of the touched parents
    ids: int             # their vertex ids
    edge_rows: int       # real occurrences of the touched triples


def support_bytes(s: LevelShape) -> int:
    per_graph = (s.parents * (s.embeddings * s.vertices * 4 + s.embeddings)
                 + s.triples * s.occurrences * 9)
    return s.partitions * (s.graphs * per_graph + s.candidates * 8)


def least_support_bytes(s: LevelShape) -> int:
    """The floor for any store layout, dense, paged or spilled: a join
    that finds every child embedding must read every vertex id of every
    real parent embedding (the stub and the other end to match the
    edge, all of them to keep the new vertex out of the embedding) and
    both ends of every real edge occurrence, wherever they lie.  Which
    (pattern, graph) pair a row belongs to is counted at one byte a
    row, the dense store's mask.  Padding, as the dense store keeps
    it, only adds."""
    return (s.ids * 4 + s.rows + s.edge_rows * 9
            + s.partitions * s.candidates * 8)


def level_shapes(ref, n_graphs: int, n_partitions: int,
                 m_floor: int) -> list[LevelShape]:
    """One shape per level with candidates, from a
    :class:`bench.ref.miner.Reference`."""
    G = math.ceil(n_graphs / n_partitions)
    F = max(len(occ) for ol in ref.edge_occ.values() for occ in ol.values())
    out = []
    for parent_level, level in zip(ref.levels, ref.levels[1:]):
        cands = level.candidates
        if not cands:
            continue
        parents = sorted(parent_level.frequent)
        touched = {c.parent for c in cands}
        triples = {c.triple for c in cands}
        most = max(len(embs) for p in touched
                   for embs in parent_level.frequent[parents[p]].values())
        M = m_floor
        while M < most:
            M *= 2
        width = {p: 1 + max(max(e[0], e[1]) for e in parents[p])
                 for p in touched}
        real = {p: sum(len(embs) for embs in
                       parent_level.frequent[parents[p]].values())
                for p in touched}
        out.append(LevelShape(
            partitions=n_partitions, graphs=G, parents=len(touched),
            triples=len(triples), candidates=len(cands),
            embeddings=M, vertices=max(width.values()), occurrences=F,
            rows=sum(real.values()),
            ids=sum(real[p] * width[p] for p in touched),
            edge_rows=sum(len(occ) for t in triples
                          for occ in ref.edge_occ[t].values())))
    return out
