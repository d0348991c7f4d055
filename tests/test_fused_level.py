"""Fused single-launch map phase: scheduling round-trip + parity sweeps
(fused vs ref vs legacy two-launch interpret) + end-to-end mining."""
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core.candgen import schedule_candidates
from repro.core.graphdb import paper_toy_db, random_db
from repro.core.host_miner import mine_host
from repro.core.mining import Mirage, MirageConfig
from repro.kernels.ops import fused_level_supports, level_supports


def _random_level(rng, C=5, P=3, G=16, M=8, K=3, T=4, F=6):
    """Random-but-consistent join inputs (ids in [0, 32), PAD=-1)."""
    pol = rng.integers(0, 32, (P, G, M, K)).astype(np.int32)
    pmask = (rng.random((P, G, M)) < 0.7)
    kill = rng.random((P, G, M, K)) < 0.15
    pol = np.where(kill, -1, pol)
    src = rng.integers(0, 32, (T, G, F)).astype(np.int32)
    dst = rng.integers(0, 32, (T, G, F)).astype(np.int32)
    emask = (rng.random((T, G, F)) < 0.7)
    src = np.where(emask, src, -1)
    dst = np.where(emask, dst, -1)
    meta = np.stack([
        rng.integers(0, P, C),
        rng.integers(0, K, C),
        rng.integers(0, K, C),
        rng.integers(0, 2, C),
        rng.integers(0, T, C),
    ], axis=1).astype(np.int32)
    return meta, pol, pmask, src, dst, emask


# ---------------------------------------------------------------------------
# schedule_candidates
# ---------------------------------------------------------------------------

def test_schedule_blocks_are_uniform_and_tile_aligned():
    rng = np.random.default_rng(7)
    meta, *_ = _random_level(rng, C=23, P=4, T=3)
    sched = schedule_candidates(meta, tile_c=4)
    tc = sched.tile_c
    assert 1 <= tc <= 4
    assert sched.meta.shape[0] == sched.n_tiles * tc
    for t in range(sched.n_tiles):
        block = sched.meta[t * tc:(t + 1) * tc]
        assert (block[:, 0] == sched.tiles[t, 0]).all()   # one parent/block
        assert (block[:, 4] == sched.tiles[t, 1]).all()   # one triple/block
    # every canonical candidate appears exactly once, metadata intact
    valid_rows = np.flatnonzero(sched.meta[:, 5])
    assert len(valid_rows) == meta.shape[0]
    assert sorted(sched.inv.tolist()) == sorted(valid_rows.tolist())


def test_schedule_adapts_tile_to_grouping():
    """Scattered (parent, triple) pairs must not inflate the schedule;
    heavily shared pairs must keep wide tiles."""
    # 16 all-distinct pairs -> singleton groups -> tile_c collapses to 1
    scattered = np.zeros((16, 5), np.int32)
    scattered[:, 0] = np.arange(16)          # distinct parents
    s = schedule_candidates(scattered, tile_c=8)
    assert s.tile_c == 1
    assert s.meta.shape[0] == 16             # zero padding
    # 2 groups of 8 -> tile_c stays 8, two blocks
    grouped = np.zeros((16, 5), np.int32)
    grouped[8:, 0] = 1
    g = schedule_candidates(grouped, tile_c=8)
    assert g.tile_c == 8
    assert g.n_tiles == 2


def test_schedule_permutation_round_trip():
    """Gathering scheduled rows with inv must reproduce canonical meta."""
    rng = np.random.default_rng(13)
    meta, *_ = _random_level(rng, C=17, P=5, T=4)
    sched = schedule_candidates(meta, tile_c=8)
    np.testing.assert_array_equal(sched.meta[sched.inv, :5], meta)
    assert (sched.meta[sched.inv, 5] == 1).all()


def test_schedule_groups_duplicate_parents():
    """Candidates sharing (parent, triple) must land in shared blocks."""
    meta = np.asarray([[1, 0, 1, 1, 2]] * 5 + [[0, 0, 1, 1, 0]] * 3,
                      np.int32)
    sched = schedule_candidates(meta, tile_c=4)
    # group (1,2): 5 cands -> 2 tiles; group (0,0): 3 cands -> 1 tile
    assert sched.n_tiles == 3
    counts = {(int(p), int(t)): 0 for p, t in sched.tiles}
    for p, t in sched.tiles:
        counts[(int(p), int(t))] += 1
    assert counts == {(1, 2): 2, (0, 0): 1}


def test_schedule_empty():
    sched = schedule_candidates(np.zeros((0, 5), np.int32), tile_c=4)
    assert sched.meta.shape == (4, 6)
    assert (sched.meta[:, 5] == 0).all()
    assert sched.inv.shape == (0,)


# ---------------------------------------------------------------------------
# kernel parity: fused vs ref vs legacy two-launch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,tc,tg", [
    # C not divisible by tile_c
    (dict(C=7, P=3, G=16, M=8, K=4, T=4, F=8), 4, 8),
    # G not divisible by tile_g (ops pads the graph axis)
    (dict(C=8, P=2, G=12, M=4, K=3, T=3, F=5), 4, 8),
    # both misaligned + non-pow2 everything
    (dict(C=9, P=4, G=24, M=5, K=3, T=5, F=7), 8, 16),
    # single candidate, single graph tile
    (dict(C=1, P=2, G=8, M=4, K=2, T=2, F=4), 8, 8),
    # several 128-lane graph tiles, the last overhanging G
    (dict(C=6, P=2, G=300, M=4, K=3, T=2, F=4), 4, 128),
])
def test_fused_matches_ref_and_two_launch(shape, tc, tg):
    rng = np.random.default_rng(100 + shape["G"])
    meta, pol, pmask, src, dst, emask = _random_level(rng, **shape)
    args = tuple(map(jnp.asarray, (meta, pol, pmask, src, dst, emask)))
    s_ref, e_ref = level_supports(*args, backend="ref")
    s_two, e_two = level_supports(*args, backend="interpret",
                                  tile_g=tg, tile_c=tc)
    s_f, e_f = level_supports(*args, backend="fused_interpret",
                              tile_g=tg, tile_c=tc)
    assert_allclose(np.asarray(s_f), np.asarray(s_ref))
    assert_allclose(np.asarray(e_f), np.asarray(e_ref))
    assert_allclose(np.asarray(s_f), np.asarray(s_two))
    assert_allclose(np.asarray(e_f), np.asarray(e_two))


def test_fused_duplicate_parent_batches():
    """Many candidates sharing one (parent, triple) — the case the
    parent-grouped schedule optimizes — must stay exact."""
    rng = np.random.default_rng(3)
    meta, pol, pmask, src, dst, emask = _random_level(
        rng, C=12, P=3, G=16, M=6, K=3, T=3, F=6)
    meta[:, 0] = np.asarray([1] * 9 + [2] * 3)   # heavy parent skew
    meta[:, 4] = np.asarray([0] * 6 + [2] * 6)
    args = tuple(map(jnp.asarray, (meta, pol, pmask, src, dst, emask)))
    s_ref, e_ref = level_supports(*args, backend="ref")
    s_f, e_f = level_supports(*args, backend="fused_interpret",
                              tile_g=8, tile_c=4)
    assert_allclose(np.asarray(s_f), np.asarray(s_ref))
    assert_allclose(np.asarray(e_f), np.asarray(e_ref))


def test_fused_multi_partition_stacks():
    """The (PP, ...) single-launch covers all partitions — must equal
    per-partition ref results stacked."""
    rng = np.random.default_rng(17)
    meta, pol, pmask, src, dst, emask = _random_level(
        rng, C=6, P=3, G=8, M=4, K=3, T=3, F=5)
    pol2 = np.stack([pol, np.roll(pol, 1, axis=1)])        # (2, P, G, M, K)
    pmask2 = np.stack([pmask, np.roll(pmask, 1, axis=1)])
    src2, dst2, emask2 = (np.stack([a, a]) for a in (src, dst, emask))

    sched = schedule_candidates(meta, tile_c=4)
    sup, emb = fused_level_supports(
        jnp.asarray(sched.meta), jnp.asarray(sched.tiles),
        jnp.asarray(pol2), jnp.asarray(pmask2), jnp.asarray(src2),
        jnp.asarray(dst2), jnp.asarray(emask2), tile_g=8, interpret=True)
    sup = np.asarray(sup)[:, sched.inv]                    # canonical order
    emb = np.asarray(emb)[:, sched.inv]
    for pp in range(2):
        s_ref, e_ref = level_supports(
            jnp.asarray(meta), jnp.asarray(pol2[pp]), jnp.asarray(pmask2[pp]),
            jnp.asarray(src2[pp]), jnp.asarray(dst2[pp]),
            jnp.asarray(emask2[pp]), backend="ref")
        assert_allclose(sup[pp], np.asarray(s_ref))
        assert_allclose(emb[pp], np.asarray(e_ref))


# ---------------------------------------------------------------------------
# end-to-end: fused backend through the distributed driver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("reduce", ["psum", "reduce_scatter"])
def test_mirage_fused_backend_toy_db(reduce):
    graphs = paper_toy_db()
    ref = mine_host(graphs, 2)
    cfg = MirageConfig(minsup=2, n_partitions=2, max_embeddings=8,
                       backend="fused_interpret", reduce=reduce)
    res = Mirage(cfg).fit(graphs)
    assert sum(res.counts()) == 13
    assert [set(l) for l in res.levels] == [set(l) for l in ref.levels]
    for code, sup in res.supports.items():
        assert sup == ref.frequent[code].support, code


def test_mirage_fused_backend_random_db():
    graphs = random_db(24, n_vertices=7, extra_edge_prob=0.3, n_vlabels=3,
                       n_elabels=2, seed=11)
    ref = mine_host(graphs, 5, max_size=4)
    res = Mirage(MirageConfig(minsup=5, n_partitions=4, max_size=4,
                              backend="fused_interpret")).fit(graphs)
    assert [set(l) for l in res.levels] == [set(l) for l in ref.levels]
    for code, sup in res.supports.items():
        assert sup == ref.frequent[code].support, code
