"""Least HBM bytes the support work of a level must move, from the
level's shapes alone.

The support kernel joins each candidate's parent occurrence list with
the edge occurrence list of the candidate's label triple, in every
graph of every partition.  Whatever the tiling, it has to read each
distinct parent's store and each distinct triple's edge store at least
once, and write one support and one embedding count per candidate and
partition:

    parents: NP * G * P * (M * K * 4 + M)   int32 vertex ids + int8 mask
    edges:   NP * G * T * F * 9             int32 src, int32 dst, int8 mask
    outputs: NP * C * 8                     int32 support + int32 count

G is the graphs per partition, P and T the distinct parents and
triples the level's candidates touch, C the candidates, M the
embeddings per graph the dense store holds (the configured cap doubled
until every parent embedding fits, as exactness demands), K the
parents' vertex count and F the most occurrences of one triple in one
graph.  Every shape comes from the reference miner's level, never from
the program's schedule, so a change of tiling cannot change the count.
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


def support_bytes(s: LevelShape) -> int:
    per_graph = (s.parents * (s.embeddings * s.vertices * 4 + s.embeddings)
                 + s.triples * s.occurrences * 9)
    return s.partitions * (s.graphs * per_graph + s.candidates * 8)


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
        most = max(len(embs) for p in touched
                   for embs in parent_level.frequent[parents[p]].values())
        M = m_floor
        while M < most:
            M *= 2
        K = max(1 + max(max(e[0], e[1]) for e in parents[p])
                for p in touched)
        out.append(LevelShape(
            partitions=n_partitions, graphs=G, parents=len(touched),
            triples=len({c.triple for c in cands}), candidates=len(cands),
            embeddings=M, vertices=K, occurrences=F))
    return out
