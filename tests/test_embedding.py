"""Dense (device) OL algebra vs the exact host miner."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.candgen import generate_candidates
from repro.core.buckets import bucket_size
from repro.core.embedding import (NOKEY, build_adjacency, build_edge_ol,
                                  candidate_meta, join_valid, level1_ol,
                                  local_supports_ref, materialize_prefix,
                                  materialize_rows, spill_child_counts,
                                  spill_write, LevelOL)
from repro.core.graphdb import Graph, paper_toy_db, pubchem_like_db, random_db
from repro.core.host_miner import frequent_edges, mine_host

import prep_reference


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


def _spill_layout(ol, mask, m):
    """A compact dense store (P, G, Mbig, K) in the spill layout at M=m:
    each pair with more than m embeddings moves whole to the key-sorted
    spill table, and its dense slots are masked."""
    P, G, _, K = ol.shape
    spilled = mask.sum(-1) > m
    keep = mask & ~spilled[..., None]
    dense_ol = np.where(keep[..., None], ol, -1)[:, :, :m]
    rows = [ol[p, g, r] for p in range(P) for g in range(G)
            for r in np.flatnonzero(mask[p, g]) if spilled[p, g]]
    keys = [p * G + g for p in range(P) for g in range(G)
            for _ in np.flatnonzero(mask[p, g]) if spilled[p, g]]
    R = bucket_size(max(len(rows), 1), 8)
    table = np.full((R, K), -1, np.int32)
    key = np.full((R,), NOKEY, np.int32)
    table[:len(rows)] = rows
    key[:len(keys)] = keys
    return dense_ol, keep[:, :, :m], table, key


def _by_graph(rows, graphs):
    out = {}
    for r, g in zip(np.asarray(rows).tolist(), np.asarray(graphs).tolist()):
        out.setdefault(g, []).append(tuple(r))
    return {g: sorted(v) for g, v in out.items()}


@pytest.mark.parametrize("seed", [3, 8])
def test_spill_materialization_matches_materialize_rows(seed):
    """Dense + spill materialization of each candidate (the dense store
    at M=2 beside the spill table, through ``materialize_prefix`` and
    ``spill_write``) yields, per graph, exactly the child embeddings
    ``materialize_rows`` finds with an M that holds them all."""
    graphs = random_db(6, n_vertices=8, extra_edge_prob=0.6, n_vlabels=2,
                       n_elabels=1, seed=seed)
    alphabet, _ = frequent_edges(graphs, 2)
    triples = sorted({t for c in alphabet.canonical()
                      for t in (c, (c[2], c[1], c[0]))})
    eol = build_edge_ol(graphs, triples)
    codes = [((0, 1, a, e, b),) for (a, e, b) in alphabet.canonical()]
    full = level1_ol(codes, eol, max_embeddings=64)
    ol, mask = np.asarray(full.ol), np.asarray(full.mask)
    m, big, width = 2, 256, 3
    dense_ol, dense_mask, table, key = _spill_layout(ol, mask, m)
    nbr, code = (jnp.asarray(a[0]) for a in build_adjacency(
        eol.src[None], eol.dst[None], eol.mask[None]))
    dense_ol, dense_mask, table, key = map(
        jnp.asarray, (dense_ol, dense_mask, table, key))
    src, dst, em = map(jnp.asarray, (eol.src, eol.dst, eol.mask))
    spilled_any = False
    for cand in candidate_meta(generate_candidates(codes, alphabet), eol):
        p, t = int(cand[0]), int(cand[4])
        child, picked, _ = materialize_rows(
            ol[p], mask[p], src[t], dst[t], em[t], jnp.asarray(cand),
            max_embeddings=big, out_width=width)
        gi, ri = np.nonzero(np.asarray(picked))
        want = _by_graph(np.asarray(child)[gi, ri], gi)

        cmeta = jnp.asarray(cand[None])
        cdx, csx, bounds, spilled, n_rows, _ = spill_child_counts(
            cmeta, 1, dense_ol, dense_mask, table, key, nbr, code,
            n_triples=len(triples), max_embeddings=m)
        d_ol, d_mask, _ = materialize_prefix(
            cmeta, 1, dense_ol[None], dense_mask[None], src[None],
            dst[None], em[None], n_slots=1, max_embeddings=m,
            out_width=width)
        d_mask = np.asarray(d_mask[0, 0]) & ~np.asarray(spilled[0])[:, None]
        s_rows, s_key = spill_write(
            cmeta, cdx, csx, bounds, spilled, dense_ol, table, key, nbr,
            code, out_rows=bucket_size(max(int(n_rows), 1), 8),
            out_width=width)
        live = np.asarray(s_key) < NOKEY
        assert int(live.sum()) == int(n_rows)
        assert (np.diff(np.asarray(s_key)) >= 0).all()
        gd, rd = np.nonzero(d_mask)
        got = _by_graph(
            np.concatenate([np.asarray(d_ol[0, 0])[gd, rd],
                            np.asarray(s_rows)[live]]),
            np.concatenate([gd, np.asarray(s_key)[live] % ol.shape[1]]))
        assert got == want, cand
        spilled_any |= int(n_rows) > 0
    assert spilled_any


# ---------------------------------------------------------------------------
# host preparation on flat arrays == the frozen loop builders
# ---------------------------------------------------------------------------

def _dense_one_label():
    # one vertex and one edge label: every triple is (0, 0, 0), a == b
    return random_db(8, n_vertices=8, extra_edge_prob=0.9, n_vlabels=1,
                     n_elabels=1, seed=7)


_PREP_CASES = {
    # name: (graphs, minsup, extra padded graphs, max_occ, M, table order)
    "three_edge_labels": (random_db(10, n_vertices=7, extra_edge_prob=0.5,
                                    n_vlabels=3, n_elabels=3, seed=21),
                          3, 0, None, 64, "sorted"),
    "one_label_a_eq_b": (_dense_one_label(), 2, 5, None, 64, "sorted"),
    "truncated_at_max_occ": (_dense_one_label(), 2, 3, 3, 64, "sorted"),
    "level1_narrower_than_F": (_dense_one_label(), 2, 0, None, 2,
                               "sorted"),
    "paper_toy": (paper_toy_db(), 2, 2, None, 8, "sorted"),
    "molecules_reversed_table": (pubchem_like_db(12, seed=5, avg_edges=12),
                                 3, 4, 4, 16, "reversed"),
    "infrequent_edges_off_table": (random_db(9, n_vertices=6,
                                             extra_edge_prob=0.3,
                                             n_vlabels=4, n_elabels=2,
                                             seed=13), 4, 1, None, 8,
                                   "sorted"),
    "edgeless_and_isolated": ([Graph([0], np.empty((0, 2)), []),
                               Graph([0, 1, 1], [(0, 1)], [0]),
                               Graph([1, 0, 1], [(0, 1), (1, 2)], [0, 0])],
                              1, 1, None, 4, "sorted"),
}


@pytest.mark.parametrize("case", list(_PREP_CASES))
def test_prep_arrays_equal_the_loop_builders(case):
    """``build_edge_ol``, ``build_adjacency`` and ``level1_ol`` return
    exactly what the loop builders return: same dtypes, shapes, values
    and triple index, the raw DB's infrequent occurrences left off."""
    graphs, minsup, extra, max_occ, M, order = _PREP_CASES[case]
    alphabet, _ = frequent_edges(graphs, minsup)
    triples = sorted({t for c in alphabet.canonical()
                      for t in (c, (c[2], c[1], c[0]))})
    if order == "reversed":
        triples = triples[::-1]
    G = len(graphs) + extra
    eol = build_edge_ol(graphs, triples, pad_graphs=G, max_occ=max_occ)
    src, dst, mask, tindex = prep_reference.build_edge_ol(
        graphs, triples, pad_graphs=G, max_occ=max_occ)
    for got, want in ((eol.src, src), (eol.dst, dst), (eol.mask, mask)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want)
    assert eol.triple_index == tindex
    np.testing.assert_array_equal(eol.triples, np.asarray(triples, np.int32))
    whole = prep_reference.build_edge_ol(graphs, triples, pad_graphs=G)[2]
    assert eol.occurrences == int(mask.sum())
    assert eol.truncated == int(whole.sum() - mask.sum())
    assert (eol.truncated > 0) == (max_occ is not None)

    for got, want in zip(
            build_adjacency(eol.src[None], eol.dst[None], eol.mask[None]),
            build_adjacency(src[None], dst[None], mask[None])):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)

    codes = [((0, 1, a, e, b),) for (a, e, b) in alphabet.canonical()]
    level = level1_ol(codes, eol, max_embeddings=M)
    assert isinstance(level.ol, np.ndarray)
    assert isinstance(level.mask, np.ndarray)
    ref_ol, ref_mask = prep_reference.level1_ol(codes, src, dst, mask,
                                                tindex, max_embeddings=M)
    for got, want in ((level.ol, ref_ol), (level.mask, ref_mask)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want)


def test_build_edge_ol_rejects_a_pad_below_the_graphs():
    with pytest.raises(ValueError, match="pad_graphs"):
        build_edge_ol(paper_toy_db(), [(0, 0, 1)], pad_graphs=2)
