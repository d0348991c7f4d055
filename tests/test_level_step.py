"""Single-sync level program: wire parity vs the legacy two-program
driver, the one-transfer-per-level contract, on-device LPT, survivor-cap
retry, and donation-mode correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import jax._src.array as _jarr

from repro.core.graphdb import paper_toy_db, random_db
from repro.core.host_miner import mine_host
from repro.core.level_step import lpt_permutation, run_level
from repro.core.mapreduce import MiningMesh, map_reduce_supports
from repro.core.mining import Mirage, MirageConfig, _lpt_order
from repro.core.partition import make_partitions
from repro.core.embedding import build_edge_ol, candidate_meta, level1_ol
from repro.core.candgen import generate_candidates


def _prep(graphs, minsup, n_parts):
    """Phase 1+2 of the driver, host-side (mirrors Mirage.fit prep)."""
    part = make_partitions(graphs, minsup, n_parts)
    alphabet = part.alphabet
    triples = sorted({t for c in alphabet.canonical()
                      for t in (c, (c[2], c[1], c[0]))})
    G = max(len(p) for p in part.partitions)
    eols = [build_edge_ol(p, triples, pad_graphs=G) for p in part.partitions]
    F = max(e.src.shape[-1] for e in eols)

    def padf(a, fill):
        w = [(0, 0)] * (a.ndim - 1) + [(0, F - a.shape[-1])]
        return np.pad(a, w, constant_values=fill)

    src = np.stack([padf(e.src, -1) for e in eols])
    dst = np.stack([padf(e.dst, -1) for e in eols])
    emask = np.stack([padf(e.mask, False) for e in eols])
    codes = [((0, 1, a, e, b),) for (a, e, b) in alphabet.canonical()]
    lvl1 = [level1_ol(codes, e, max_embeddings=max(8, F)) for e in eols]
    pol = np.stack([np.asarray(l.ol) for l in lvl1])
    pmask = np.stack([np.asarray(l.mask) for l in lvl1])
    cands = generate_candidates(codes, alphabet)
    meta = candidate_meta(cands, eols[0])
    return meta, pol, pmask, src, dst, emask, part.minsup


def test_run_level_wire_matches_legacy_supports():
    """The wire's support vector must equal the legacy map_reduce
    round's, for every backend that runs on this host."""
    graphs = random_db(12, n_vertices=6, extra_edge_prob=0.3, n_vlabels=2,
                       n_elabels=2, seed=5)
    meta, pol, pmask, src, dst, emask, minsup = _prep(graphs, 3, 2)
    mesh = MiningMesh.single_device()
    C = meta.shape[0]
    arrs = tuple(map(jnp.asarray, (pol, pmask, src, dst, emask)))
    for backend in ("ref", "interpret", "fused_interpret"):
        gsup_ref, _, _ = map_reduce_supports(
            mesh, meta, *arrs, minsup=minsup, backend=backend)
        out = run_level(mesh, meta, C, *arrs, minsup=minsup,
                        backend=backend, reduce="psum", max_embeddings=16,
                        survivor_cap=C, rebalance=False, threshold=1.25,
                        donate=False)
        np.testing.assert_array_equal(out.wire.gsup, gsup_ref[:C], backend)
        assert out.wire.n_keep == int((gsup_ref[:C] >= minsup).sum())


def test_exactly_one_transfer_per_level():
    """The single-sync contract: mining N levels performs exactly N
    device→host transfers (counted at jax's ArrayImpl fetch point), with
    zero escalations/retries in play."""
    graphs = random_db(24, n_vertices=7, extra_edge_prob=0.3, n_vlabels=3,
                       n_elabels=2, seed=11)
    cfg = MirageConfig(minsup=5, n_partitions=4, max_size=4,
                       predict_survivors=False)

    counts = {"n": 0}
    orig = _jarr.ArrayImpl._value

    def counting(self):
        counts["n"] += 1
        return orig.fget(self)

    _jarr.ArrayImpl._value = property(counting)
    try:
        res = Mirage(cfg).fit(graphs)
    finally:
        _jarr.ArrayImpl._value = orig

    assert sum(st.escalations for st in res.stats) == 0
    assert counts["n"] == len(res.stats), (
        f"{counts['n']} device→host transfers for {len(res.stats)} levels")

    # the legacy pipeline crosses the boundary strictly more often
    counts["n"] = 0
    _jarr.ArrayImpl._value = property(counting)
    try:
        res_legacy = Mirage(
            MirageConfig(minsup=5, n_partitions=4, max_size=4,
                         pipeline="legacy")).fit(graphs)
    finally:
        _jarr.ArrayImpl._value = orig
    assert counts["n"] > len(res_legacy.stats)
    assert sorted(res.supports.items()) == sorted(res_legacy.supports.items())


def test_lpt_permutation_matches_host_balance():
    """Device LPT must produce a valid permutation whose per-worker loads
    match the host LPT's (both are LPT — identical bucket loads even if
    tie order differs)."""
    rng = np.random.default_rng(3)
    for w in (2, 4):
        cost = rng.integers(1, 100, 8).astype(np.float32)
        perm_d = np.asarray(lpt_permutation(jnp.asarray(cost), w))
        perm_h = _lpt_order(cost.astype(np.float64), w)
        assert sorted(perm_d.tolist()) == list(range(8))
        loads_d = cost[perm_d].reshape(w, -1).sum(-1)
        loads_h = cost[perm_h].reshape(w, -1).sum(-1)
        np.testing.assert_allclose(sorted(loads_d), sorted(loads_h))


def test_survivor_cap_miss_retries_exactly(monkeypatch):
    """A survivor cap below the true survivor count must take the
    materialize-only retry path (observable via _materialize_exact) and
    still produce exact results."""
    graphs = paper_toy_db()
    ref = mine_host(graphs, 2)
    # force a cap miss at every level: S=1 while levels keep >1 survivor
    monkeypatch.setattr(Mirage, "_survivor_cap",
                        lambda self, C, Cp, ratios: 1)
    retries = {"n": 0}
    orig = Mirage._materialize_exact

    def counting(self, *a, **kw):
        retries["n"] += 1
        return orig(self, *a, **kw)

    monkeypatch.setattr(Mirage, "_materialize_exact", counting)
    cfg = MirageConfig(minsup=2, n_partitions=2, max_embeddings=8)
    res = Mirage(cfg).fit(graphs)
    assert retries["n"] > 0, "the cap-miss retry branch must fire"
    assert sum(res.counts()) == 13
    for code, sup in res.supports.items():
        assert sup == ref.frequent[code].support, code


def test_survivor_cap_rounds_to_bucket_family():
    """Bucketed cap predictions must land in the floor·2^i family,
    clamp at the (bucketed) Cp ceiling, and — the anti-thrash
    property — map near-boundary predictions to ONE bucket instead of
    flipping the compiled program between adjacent raw caps.

    History entries are (n_parents, n_candidates, n_keep) of the
    previous level; the cap predicts from the measured per-parent
    fanout."""
    cfg = MirageConfig(minsup=2, n_partitions=1, bucket_shapes=True,
                       bucket_s_floor=8, bucket_c_floor=16)
    m = Mirage(cfg)
    raw_miner = Mirage(MirageConfig(minsup=2, n_partitions=1,
                                    bucket_shapes=False))
    Cp, C = 64, 60
    family = {8, 16, 32, 64}
    assert m._survivor_cap(C, Cp, []) in family
    for keep_prev in (1, 5, 12, 25, 40, 59):
        hist = [(10, 60, keep_prev)]
        s = m._survivor_cap(C, Cp, hist)
        assert s in family, (keep_prev, s)
        assert s <= Cp
        # never below the unbucketed prediction (a cap that can hold
        # fewer survivors than predicted would guarantee retries)
        raw = raw_miner._survivor_cap(C, Cp, hist)
        assert s >= min(raw, Cp), (keep_prev, s, raw)
    # two near-boundary histories whose RAW caps differ must share a
    # bucket
    raw_a = raw_miner._survivor_cap(C, Cp, [(10, 60, 11)])
    raw_b = raw_miner._survivor_cap(C, Cp, [(10, 60, 12)])
    assert raw_a != raw_b
    assert (m._survivor_cap(C, Cp, [(10, 60, 11)])
            == m._survivor_cap(C, Cp, [(10, 60, 12)]))


_SLOT = 3 * 2 * 100 * 8 * (4 * 5 + 1)   # 3 stores x (NP, G, M, 4W+1)


@pytest.mark.parametrize("free,want", [
    (None, 64),              # no memory limit reported (the CPU): S kept
    (10 ** 12, 64),          # ample memory: S kept
    (_SLOT * 20, 16),        # clamp rounds DOWN into the S family
    (_SLOT * 8, 8),          # exactly the family floor
    (_SLOT * 5, 5),          # fit below the floor is kept, not raised
    (_SLOT // 2, 1),         # not even one slot free: one slot
])
def test_fit_cap_clamps_to_free_device_memory(monkeypatch, free, want):
    """The survivor cap's child stores (its own plus a retry's at up to
    twice M) must fit the least free memory over the mesh's devices,
    and rounding into the bucket family never raises it past that."""
    from repro.core import mining

    monkeypatch.setattr(mining, "_free_device_bytes", lambda devices: free)
    m = Mirage(MirageConfig(minsup=2, n_partitions=2, bucket_shapes=True,
                            bucket_s_floor=8))
    pol = np.zeros((2, 4, 100, 8, 4), np.int32)   # (NP, P, G, M, K)
    got = m._fit_cap(64, pol, 8, None)            # W defaults to K + 1
    assert got == want
    if free is not None and free >= _SLOT:
        assert got * _SLOT <= free


def test_free_device_bytes_takes_the_least_free_device():
    from repro.core.mining import _free_device_bytes

    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    a = Dev({"bytes_limit": 100, "bytes_in_use": 30})
    b = Dev({"bytes_limit": 100, "bytes_in_use": 55})
    assert _free_device_bytes([a, b]) == 45
    assert _free_device_bytes([a, Dev(None)]) is None
    assert _free_device_bytes([Dev({"bytes_in_use": 5})]) is None
    assert _free_device_bytes(jax.devices()) is None   # the CPU


def test_survivor_cap_tightens_from_fanout_without_retries():
    """ISSUE-8 regression: the cap must predict from the previous
    level's per-parent FANOUT, not the survival ratio times the current
    (ballooning) candidate count — on a deep expanding run the old
    formula over-padded the child arena while the fanout predictor
    tightens it, and tightening must not buy extra materialize-only
    retries (escalations are ruled out by a roomy M)."""
    graphs = random_db(20, n_vertices=8, extra_edge_prob=0.5,
                       n_vlabels=2, n_elabels=1, seed=7)
    cfg = MirageConfig(minsup=6, n_partitions=1, max_size=5,
                       max_embeddings=64, bucket_shapes=False)
    res = Mirage(cfg).fit(graphs)
    deep = [s for s in res.stats if s.level >= 3]
    assert deep, "run must mine at least one level with cap history"
    assert not any(s.retried for s in res.stats), \
        "the tightened cap must not force materialize-only retries"
    # replay the pre-fix formula (slack x worst recent survival ratio
    # x C) over the run's own history and compare the caps it would
    # have dispatched with
    slack = cfg.survivor_slack
    ratios: list[float] = []
    tighter = 0
    for s in res.stats:
        if ratios:
            r = max(ratios[-2:])
            old = min(s.n_candidates,
                      max(1, int(np.ceil(slack * r * s.n_candidates)) + 16))
            assert s.survivor_cap <= old, (s.level, s.survivor_cap, old)
            if s.survivor_cap < old:
                tighter += 1
            # the cap still covered the real survivors (no miss)
            assert s.n_frequent <= s.survivor_cap
        ratios.append(s.n_frequent / s.n_candidates)
    assert tighter >= 1, "fanout predictor never tightened the cap"


def test_bucketed_cap_miss_retry_stays_in_family(monkeypatch):
    """A forced cap miss under bucketing must take the materialize-only
    retry, re-bucket the survivor store into the S family (so the next
    level's shapes stay cached), and still produce exact results."""
    graphs = paper_toy_db()
    ref = mine_host(graphs, 2)
    monkeypatch.setattr(Mirage, "_survivor_cap",
                        lambda self, C, Cp, ratios: 1)
    retries = {"n": 0}
    orig = Mirage._materialize_exact

    def counting(self, *a, **kw):
        retries["n"] += 1
        return orig(self, *a, **kw)

    monkeypatch.setattr(Mirage, "_materialize_exact", counting)
    cfg = MirageConfig(minsup=2, n_partitions=2, max_embeddings=8,
                       bucket_shapes=True, bucket_s_floor=4,
                       bucket_c_floor=8)
    stores = []
    orig_run = Mirage._level_single_sync

    def spy(self, *a, **kw):
        out = orig_run(self, *a, **kw)
        stores.append(int(out.pol.shape[1]))
        return out

    monkeypatch.setattr(Mirage, "_level_single_sync", spy)
    res = Mirage(cfg).fit(graphs)
    assert retries["n"] > 0, "the cap-miss retry branch must fire"
    for p in stores[:-1]:       # last level may be the empty fixpoint
        assert p % 4 == 0 and (p // 4) & (p // 4 - 1) == 0, (
            f"retried store P={p} escaped the 4·2^i family")
    assert sum(res.counts()) == 13
    for code, sup in res.supports.items():
        assert sup == ref.frequent[code].support, code


def test_donation_arena_aliases_without_warning(recwarn):
    """With bucketing aligning consecutive levels' store shapes and
    donation engaged (no retry possible), XLA must actually alias the
    donated parent store — the 'donated buffers were not usable'
    warning is the tripwire for a broken arena."""
    import warnings
    graphs = random_db(16, n_vertices=6, extra_edge_prob=0.3, n_vlabels=2,
                       n_elabels=2, seed=9)
    # floors chosen so EVERY level of this DB lands in one bucket
    # (C <= 128 throughout, level-1 pattern count <= 128, K <= 8):
    # all level programs then share literally one store shape
    cfg = MirageConfig(minsup=4, n_partitions=2, max_size=4,
                       max_embeddings=64, escalate_on_overflow=False,
                       predict_survivors=False, donate=True,
                       bucket_shapes=True, bucket_c_floor=128,
                       bucket_s_floor=128, bucket_k_floor=8)
    ref = mine_host(graphs, 4, max_size=4)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res = Mirage(cfg).fit(graphs)
    unusable = [w for w in caught
                if "donated buffers were not usable" in str(w.message)]
    assert not unusable, [str(w.message)[:200] for w in unusable]
    for code, sup in res.supports.items():
        assert sup == ref.frequent[code].support, code


def test_donation_mode_correct():
    """With the escalation valve off and no cap prediction the program
    donates its input buffers — results must be unchanged."""
    graphs = random_db(16, n_vertices=6, extra_edge_prob=0.3, n_vlabels=2,
                       n_elabels=2, seed=9)
    ref = mine_host(graphs, 4, max_size=4)
    cfg = MirageConfig(minsup=4, n_partitions=2, max_size=4,
                       max_embeddings=64, escalate_on_overflow=False,
                       predict_survivors=False, donate=True)
    res = Mirage(cfg).fit(graphs)
    assert res.total_overflow == 0
    assert [set(l) for l in res.levels] == [set(l) for l in ref.levels]
    for code, sup in res.supports.items():
        assert sup == ref.frequent[code].support, code
