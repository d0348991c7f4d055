"""Dense (device) OL algebra vs the exact host miner."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.candgen import generate_candidates
from repro.core.embedding import (build_edge_ol, candidate_meta, join_valid,
                                  level1_ol, local_supports_ref,
                                  materialize_prefix, LevelOL)
from repro.core.graphdb import paper_toy_db, random_db
from repro.core.host_miner import frequent_edges, mine_host


def dense_mine_levels(graphs, minsup, max_size, max_embeddings=64, max_occ=None):
    """Single-partition dense mining loop using only embedding.py ops."""
    alphabet, _ = frequent_edges(graphs, minsup)
    triples = sorted({t for c in alphabet.canonical()
                      for t in (c, (c[2], c[1], c[0]))})
    eol = build_edge_ol(graphs, triples, max_occ=max_occ)
    src, dst, em = map(jnp.asarray, (eol.src, eol.dst, eol.mask))

    # F_1 from alphabet (already globally frequent)
    codes = [((0, 1, a, e, b),) for (a, e, b) in alphabet.canonical()]
    level = level1_ol(codes, eol, max_embeddings=max_embeddings)
    levels = [list(codes)]
    supports = {}
    for c in codes:
        ti = eol.triple_index[c[0][2:]]
        supports[c] = int(np.asarray(eol.mask[ti].any(axis=-1).sum()))

    total_overflow = 0
    k = 1
    while levels[-1] and k < max_size:
        cands = generate_candidates(levels[-1], alphabet)
        if not cands:
            break
        meta = jnp.asarray(candidate_meta(cands, eol))
        sup, _cnt = local_supports_ref(level, src, dst, em, meta)
        sup = np.asarray(sup)
        keep = [i for i in range(len(cands)) if sup[i] >= minsup]
        if not keep:
            break
        keep_meta = jnp.asarray(candidate_meta([cands[i] for i in keep], eol))
        # the production materializer, on one partition (PP=1)
        ol, mask, over = materialize_prefix(
            keep_meta, len(keep), level.ol[None], level.mask[None],
            src[None], dst[None], em[None], n_slots=len(keep),
            max_embeddings=max_embeddings, out_width=level.ol.shape[-1] + 1)
        level = LevelOL(ol[0], mask[0])
        total_overflow += int(over)
        levels.append([cands[i].code for i in keep])
        for i in keep:
            supports[cands[i].code] = int(sup[i])
        k += 1
    return levels, supports, total_overflow


@pytest.mark.parametrize("graphs,minsup", [
    (paper_toy_db(), 2),
    (random_db(8, n_vertices=6, extra_edge_prob=0.4, n_vlabels=3,
               n_elabels=2, seed=4), 3),
    (random_db(12, n_vertices=8, extra_edge_prob=0.2, n_vlabels=4,
               n_elabels=1, seed=9), 4),
])
def test_dense_matches_host(graphs, minsup):
    ref = mine_host(graphs, minsup, max_size=4)
    levels, supports, overflow = dense_mine_levels(graphs, minsup, max_size=4)
    assert overflow == 0, "M cap must not bind at this scale"
    ref_levels = [set(l) for l in ref.levels]
    got_levels = [set(l) for l in levels]
    assert got_levels == ref_levels
    for code, sup in supports.items():
        assert sup == ref.frequent[code].support, code


def test_paper_toy_dense_13():
    levels, supports, _ = dense_mine_levels(paper_toy_db(), 2, max_size=8)
    assert sum(len(l) for l in levels) == 13


def test_overflow_is_lower_bound():
    """With a tiny M cap, dense supports are a lower bound on true support
    (the documented exactness valve semantics)."""
    graphs = random_db(10, n_vertices=8, extra_edge_prob=0.5, n_vlabels=2,
                       n_elabels=1, seed=2)
    ref = mine_host(graphs, 2, max_size=3)
    _, supports, overflow = dense_mine_levels(graphs, 2, max_size=3,
                                              max_embeddings=2)
    for code, sup in supports.items():
        assert sup <= ref.frequent[code].support


def test_join_valid_backward_semantics():
    """Hand-built: triangle closure on a square + diagonal graph."""
    # parent = path 0-1-2 embedded as (a,b,c); backward edge 2->0 exists
    parent = jnp.asarray(np.array([[[0, 1, 2], [1, 2, 3]]], np.int32))  # (1,2,3)
    pmask = jnp.asarray(np.array([[True, True]]))
    src = jnp.asarray(np.array([[2, 0]], np.int32))   # edge occs (2,0),(0,2)
    dst = jnp.asarray(np.array([[0, 2]], np.int32))
    em = jnp.asarray(np.array([[True, True]]))
    valid = join_valid(parent, pmask, src, dst, em,
                       jnp.int32(2), jnp.int32(0), jnp.int32(0))
    v = np.asarray(valid)
    assert v[0, 0, 0] and not v[0, 0, 1]   # emb (0,1,2): occ (2,0) closes it
    assert not v[0, 1].any()               # emb (1,2,3): no 3->1 edge occ
