"""Device-resident mining loop (DESIGN.md §13).

Three layers of differential coverage:

 1. the device building blocks against their host oracles —
    ``min_dfs_canonical_array`` vs ``is_canonical``, ``device_candidates``
    vs ``generate_candidates`` (exact order), ``device_schedule`` vs
    ``schedule_candidates``;
 2. ``pipeline="device_loop"`` end-to-end against single_sync and the
    host miner: level ORDER and supports must match bit-for-bit across
    packed x backend x worker count, with early termination, the
    unrolled stepping stone, run-granular M escalation, chunked
    checkpoints + resume, and the bail -> single_sync fallback;
 3. the residency contract itself — during a completed device_loop run
    the host candgen runs exactly once (the budget-sizing call) and the
    per-level dispatcher never runs.
"""
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import candgen, dfscode, mining
from repro.core.candgen import EdgeAlphabet, generate_candidates
from repro.core.graphdb import random_db
from repro.core.host_miner import mine_host
from repro.core.mining import Mirage, MirageConfig
from repro.core.supervisor import (DEVICE_LOOP_LADDER, LADDER,
                                   MiningSupervisor, SupervisorConfig,
                                   ladder_for)
from repro.runtime import checkpoint as ckpt
from repro.runtime import faults


@pytest.fixture(scope="module")
def db():
    """18-graph DB with 3 frequent levels at minsup 3 ([12, 16, 2])."""
    return random_db(18, n_vertices=6, extra_edge_prob=0.35,
                     n_vlabels=3, n_elabels=2, seed=42)


@pytest.fixture(scope="module")
def canon(db):
    ref = mine_host(db, 3, max_size=4)
    return sorted((c, i.support) for c, i in ref.frequent.items())


def _mine_dl(db, canon, expect_completed=True, **kw):
    cfg = MirageConfig(minsup=3, n_partitions=2, max_size=4,
                       backend="ref", pipeline="device_loop", **kw)
    m = Mirage(cfg)
    res = m.fit(db)
    assert sorted(res.supports.items()) == canon
    assert m.last_device_loop["completed"] == expect_completed, \
        m.last_device_loop
    return m, res


# ---------------------------------------------------------------------------
# 1. device building blocks vs host oracles
# ---------------------------------------------------------------------------

def test_device_canonicality_matches_host():
    """min_dfs_canonical_array agrees with is_canonical on a code pile
    that includes the NON-canonical children host candgen filters."""
    codes = []
    for seed in range(2):
        graphs = random_db(10, n_vertices=6, extra_edge_prob=0.4,
                           n_vlabels=3, n_elabels=2, seed=seed)
        res = mine_host(graphs, 2, max_size=4)
        alpha = EdgeAlphabet((c[0][2], c[0][3], c[0][4])
                             for c in res.frequent if len(c) == 1)
        for code in res.frequent:
            rmp = dfscode.rightmost_path(code)
            n_v = max(max(e[0], e[1]) for e in code) + 1
            vl = {}
            for (i, j, li, _le, lj) in code:
                vl[i] = li
                vl[j] = lj
            existing = {(min(e[0], e[1]), max(e[0], e[1])) for e in code}
            rmv = rmp[-1]
            for w in rmp[:-1]:
                if (min(rmv, w), max(rmv, w)) in existing:
                    continue
                for (e_lab, other) in alpha.partners(vl[rmv]):
                    if other == vl[w]:
                        codes.append(
                            code + ((rmv, w, vl[rmv], e_lab, vl[w]),))
            for w in rmp:
                for (e_lab, other) in alpha.partners(vl[w]):
                    codes.append(code + ((w, n_v, vl[w], e_lab, other),))
    assert len(codes) > 300
    L = max(len(c) for c in codes)
    arr = np.stack([dfscode.code_to_array(c, L) for c in codes])
    fn = jax.jit(jax.vmap(
        lambda a: dfscode.min_dfs_canonical_array(
            a, n_vertex_slots=L + 1, max_states=64)))
    canon_d, ovf_d = map(np.asarray, fn(jnp.asarray(arr)))
    assert not ovf_d.any()
    host = np.array([dfscode.is_canonical(c) for c in codes])
    mism = np.flatnonzero(host != canon_d.astype(bool))
    assert mism.size == 0, [codes[i] for i in mism[:5]]


def test_device_candgen_matches_host_order():
    """device_candidates reproduces generate_candidates exactly —
    same candidates, same parent/extension metadata, same ORDER."""
    for seed in (42, 43):
        graphs = random_db(18, n_vertices=6, extra_edge_prob=0.35,
                           n_vlabels=3, n_elabels=2, seed=seed)
        res = mine_host(graphs, 5, max_size=4)
        alpha = EdgeAlphabet((c[0][2], c[0][3], c[0][4])
                             for c in res.frequent if len(c) == 1)
        triples = sorted({t for c in alpha.canonical()
                          for t in (c, (c[2], c[1], c[0]))})
        tri_arr = jnp.asarray(np.array(triples, np.int32))
        by_level = {}
        for c in res.frequent:
            by_level.setdefault(len(c), []).append(c)
        checked = 0
        for lvl in sorted(by_level):
            parents = sorted(by_level[lvl])
            host = generate_candidates(parents, alpha)
            L = lvl + 1
            codes = jnp.asarray(np.stack(
                [dfscode.code_to_array(c, L) for c in parents]))
            cb = max(8, 2 * len(host))
            fn = candgen.device_candgen_jit(L, L + 1, 4 * cb, cb, 64)
            meta, ccodes, n_cand, flags = fn(
                codes, jnp.int32(len(parents)), tri_arr)
            assert not np.asarray(flags).any()
            assert int(n_cand) == len(host), (seed, lvl)
            dev = candgen.candidates_from_arrays(
                np.asarray(meta), np.asarray(ccodes), int(n_cand), triples)
            for d, h in zip(dev, host):
                assert d.code == h.code
                assert d.parent == h.parent
                assert d.ext == h.ext
            checked += len(host)
        assert checked > 0


def test_device_schedule_matches_host():
    """device_schedule reproduces schedule_candidates' tiling (meta,
    tiles, inverse map) and flags overflow when rows run out."""
    rng = np.random.default_rng(0)
    for trial in range(10):
        C = int(rng.integers(1, 60))
        T = int(rng.integers(2, 12))
        NP = int(rng.integers(1, 20))
        meta = np.stack([
            rng.integers(0, NP, C), rng.integers(0, 4, C),
            rng.integers(0, 5, C), rng.integers(0, 2, C),
            rng.integers(0, T, C)], axis=1).astype(np.int32)
        meta = meta[np.argsort(meta[:, 0], kind="stable")]
        tc = int(rng.choice([1, 2, 4, 8]))
        host = candgen.schedule_candidates(meta, tc,
                                           max_inflation=float("inf"))
        cb = C + int(rng.integers(0, 16))
        rows = max(host.meta.shape[0], cb) + tc * int(rng.integers(0, 3))
        rows = -(-rows // tc) * tc
        pmeta = np.concatenate(
            [meta,
             np.tile(np.asarray([0, 0, 0, 1, 0], np.int32), (cb - C, 1))])
        sched, tiles, inv, ovf = candgen.device_schedule(
            jnp.asarray(pmeta), jnp.int32(C), tile_c=tc, n_triples=T,
            rows=rows)
        sched, tiles, inv = map(np.asarray, (sched, tiles, inv))
        assert not bool(ovf), trial
        hs = host.meta.shape[0]
        assert np.array_equal(sched[:hs], host.meta), trial
        assert (sched[hs:, 5] == 0).all(), trial
        assert np.array_equal(tiles[:hs // tc], host.tiles), trial
        assert np.array_equal(inv[:C], host.inv), trial
    # 16 singleton parent groups x tile_c=8 cannot fit 16 rows
    meta = np.stack([np.arange(16), *([np.zeros(16, int)] * 3),
                     np.zeros(16, int)], axis=1).astype(np.int32)
    _, _, _, ovf = candgen.device_schedule(
        jnp.asarray(meta), jnp.int32(16), tile_c=8, n_triples=4, rows=16)
    assert bool(ovf)


# ---------------------------------------------------------------------------
# 2. device_loop end-to-end conformance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("packed", [False, True])
def test_device_loop_matches_single_sync_and_host(db, canon, packed):
    cfg_ss = MirageConfig(minsup=3, n_partitions=2, max_size=4,
                          backend="ref", packed_support=packed)
    res_ss = Mirage(cfg_ss).fit(db)
    m, res_dl = _mine_dl(db, canon, packed_support=packed)
    # level ORDER, not just set equality — the wire preserves min-dfs order
    assert [list(l) for l in res_dl.levels] == \
        [list(l) for l in res_ss.levels]
    assert sorted(res_ss.supports.items()) == canon
    assert m.last_device_loop["chunks"] == 1
    assert [(s.level, s.n_candidates, s.n_frequent) for s in res_dl.stats] \
        == [(s.level, s.n_candidates, s.n_frequent) for s in res_ss.stats]


def test_device_loop_fused_interpret():
    """The fused kernel path inside the loop body (interpret-mode Pallas
    unrolls the grid at trace time, so: tiny DB)."""
    tiny = random_db(8, n_vertices=4, extra_edge_prob=0.3, n_vlabels=2,
                     n_elabels=1, seed=3)
    ref = mine_host(tiny, 3, max_size=3)
    tcanon = sorted((c, i.support) for c, i in ref.frequent.items())
    cfg = MirageConfig(minsup=3, n_partitions=2, max_size=3,
                       backend="fused_interpret", pipeline="device_loop")
    m = Mirage(cfg)
    res = m.fit(tiny)
    assert m.last_device_loop["completed"], m.last_device_loop
    assert sorted(res.supports.items()) == tcanon


_MULTIWORKER_SNIPPET = textwrap.dedent("""
    import os, sys
    W = int(sys.argv[1])
    os.environ["XLA_FLAGS"] = \\
        "--xla_force_host_platform_device_count=%d" % W
    from repro.core.graphdb import random_db
    from repro.core.host_miner import mine_host
    from repro.core.mapreduce import MiningMesh
    from repro.core.mining import Mirage, MirageConfig
    from repro.runtime import jax_compat

    graphs = random_db(18, n_vertices=6, extra_edge_prob=0.35,
                       n_vlabels=3, n_elabels=2, seed=42)
    ref = mine_host(graphs, 3, max_size=4)
    canon = sorted((c, i.support) for c, i in ref.frequent.items())
    mesh = MiningMesh(jax_compat.make_mesh((W,), ("w",)))
    cfg = MirageConfig(minsup=3, n_partitions=4, max_size=4,
                       backend="ref", pipeline="device_loop")
    m = Mirage(cfg, mesh)
    res = m.fit(graphs)
    assert m.last_device_loop["completed"], m.last_device_loop
    assert sorted(res.supports.items()) == canon
    print("W-OK")
""")


@pytest.mark.parametrize("workers", [2, 4])
def test_device_loop_multiworker(workers):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run(
        [sys.executable, "-c", _MULTIWORKER_SNIPPET, str(workers)],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "W-OK" in out.stdout


def test_device_loop_early_termination(db, canon):
    """max_size far past the fixpoint: the while_loop exits when a level
    yields no survivors; unexecuted slots never reach the decode."""
    cfg = MirageConfig(minsup=3, n_partitions=2, max_size=8,
                       backend="ref", pipeline="device_loop")
    m = Mirage(cfg)
    res = m.fit(db)
    assert m.last_device_loop["completed"]
    assert sorted(res.supports.items()) == canon
    assert [len(l) for l in res.levels] == [12, 16, 2]
    assert res.stats[-1].level == 4, "loop must exit at the fixpoint"


def test_device_loop_unrolled_matches_while(db, canon):
    for unroll in (1, 2):
        _mine_dl(db, canon, device_loop_unroll=unroll)


def test_device_loop_escalation_valve():
    """Run-granular M escalation: overflow at the chunk boundary doubles
    the uniform M and reruns; the result matches the exact host miner."""
    dense = random_db(8, n_vertices=8, extra_edge_prob=0.9, n_vlabels=1,
                      n_elabels=1, seed=7)
    ref = mine_host(dense, 4, max_size=3)
    dcanon = sorted((c, i.support) for c, i in ref.frequent.items())
    cfg = MirageConfig(minsup=4, n_partitions=2, max_size=3,
                       backend="ref", pipeline="device_loop",
                       max_embeddings=2, max_embeddings_limit=4096)
    m = Mirage(cfg)
    res = m.fit(dense)
    assert m.last_device_loop["completed"], m.last_device_loop
    assert sorted(res.supports.items()) == dcanon
    assert m.last_device_loop["escalations"] > 0
    assert sum(s.escalations for s in res.stats) > 0
    assert res.total_overflow == 0


def test_device_loop_chunked_checkpoint_resume(db, canon, tmp_path):
    ckdir = str(tmp_path / "dl_ck")
    m, _ = _mine_dl(db, canon, device_loop_ckpt_every=1,
                    checkpoint_dir=ckdir)
    assert m.last_device_loop["chunks"] == 3, m.last_device_loop
    cadence = ckpt.ChunkCadence(1, 4, 1)
    assert m.last_device_loop["chunks"] == cadence.n_chunks
    # lose everything past the level-2 checkpoint, resume mid-run
    steps = ckpt.all_steps(ckdir)
    assert steps, "no checkpoints written"
    for s in steps:
        if s > 2:
            shutil.rmtree(os.path.join(ckdir, f"step_{s:010d}"))
    cfg = MirageConfig(minsup=3, n_partitions=2, max_size=4,
                       backend="ref", pipeline="device_loop",
                       checkpoint_dir=ckdir)
    m2 = Mirage(cfg)
    res2 = m2.fit(db, resume=True)
    assert sorted(res2.supports.items()) == canon
    assert m2.last_device_loop["completed"], m2.last_device_loop


def test_device_loop_tiny_budget_falls_back(db, canon):
    """A hopeless candidate budget bails with a flag; the supervisor-free
    driver falls back to single_sync and the result is still exact."""
    m, _ = _mine_dl(db, canon, expect_completed=False, device_c_budget=8)
    assert m.last_device_loop["fallback"]
    assert "flags" in m.last_device_loop["fallback"]


def test_device_loop_wire_bitflip_refetch(db, canon):
    """A checksum-failing run wire is refetched, and the injected fault
    is consumed exactly once."""
    sched = faults.FaultSchedule.parse("wire_bitflip@4")
    faults.install(sched)
    try:
        m, _ = _mine_dl(db, canon)
        assert all(s._remaining == 0 for s in sched.specs), \
            "wire_bitflip fault never consumed"
    finally:
        faults.clear()


def test_supervisor_degrades_device_loop_to_single_sync(db, canon):
    """The device_loop ladder inserts a single_sync rung before the
    backend/pipeline rungs of the stock ladder."""
    assert ladder_for(MirageConfig(minsup=3, max_size=4,
                                   pipeline="device_loop")) \
        == DEVICE_LOOP_LADDER
    assert ladder_for(MirageConfig(minsup=3)) == LADDER
    sched = faults.FaultSchedule.parse("kernel_fault@2*4")
    faults.install(sched)
    try:
        cfg = MirageConfig(minsup=3, n_partitions=2, max_size=4,
                           backend="ref", pipeline="device_loop")
        sup = MiningSupervisor(cfg, SupervisorConfig(max_retries=8,
                                                     backoff_base=0.0))
        res = sup.mine(db)
        assert sorted(res.supports.items()) == canon
        rungs = [e.detail for e in sup.events if e.action == "degrade"]
        assert any("single_sync" in d for d in rungs), rungs
    finally:
        faults.clear()


def test_candgen_device_stepping_stone(db, canon):
    """candgen="device" swaps the per-level host generator for the
    device kernel inside the host-driven pipelines."""
    for pipeline in ("single_sync", "legacy"):
        cfg = MirageConfig(minsup=3, n_partitions=2, max_size=4,
                           backend="ref", pipeline=pipeline,
                           candgen="device")
        res = Mirage(cfg).fit(db)
        assert sorted(res.supports.items()) == canon, pipeline


# ---------------------------------------------------------------------------
# 3. the residency contract
# ---------------------------------------------------------------------------

def test_no_host_candgen_mid_loop(db, canon, monkeypatch):
    """During a completed device_loop run the host candgen runs exactly
    once (the budget-sizing call on the start level) and the per-level
    dispatcher never runs — there is no host work between levels."""
    calls = []
    real = mining.generate_candidates

    def counting(*a, **kw):
        calls.append(a)
        return real(*a, **kw)

    def boom(*a, **kw):
        raise AssertionError("dispatch_level ran under device_loop")

    monkeypatch.setattr(mining, "generate_candidates", counting)
    monkeypatch.setattr(mining, "dispatch_level", boom)
    m, _ = _mine_dl(db, canon)
    assert len(calls) == 1, f"{len(calls)} host candgen calls"


def test_device_loop_config_validation():
    with pytest.raises(ValueError, match="max_size"):
        MirageConfig(minsup=3, pipeline="device_loop")
    with pytest.raises(ValueError, match="bucket_shapes"):
        MirageConfig(minsup=3, max_size=4, pipeline="device_loop",
                     bucket_shapes=False)
    with pytest.raises(ValueError, match="escalate_on_overflow"):
        MirageConfig(minsup=3, max_size=4, pipeline="device_loop",
                     escalate_on_overflow=False)
    with pytest.raises(ValueError, match="candgen"):
        MirageConfig(minsup=3, candgen="quantum")
    # host speculation is statically impossible under device candgen
    assert not MirageConfig(minsup=3, max_size=4,
                            pipeline="device_loop").overlap_candgen
    assert not MirageConfig(minsup=3, candgen="device").overlap_candgen
    assert MirageConfig(minsup=3).overlap_candgen


def test_chunk_cadence():
    c = ckpt.ChunkCadence(1, 6, 2)
    assert c.boundaries() == [3, 5, 6]
    assert c.n_chunks == 3
    assert c.max_fetches() == 3 + 2 * 2
    whole = ckpt.ChunkCadence(1, 6, None)
    assert whole.boundaries() == [6]
    assert whole.max_fetches() == 1
    assert ckpt.ChunkCadence(3, 4, 1).boundaries() == [4]


def test_escalation_when_run_reaches_fixpoint():
    """A run that ends at its fixpoint (no survivors left) after capping
    embeddings on the way must escalate M and rerun, not fall back:
    4-leaf stars hold 12-24 embeddings of each star pattern, far past
    the M=4 level-1 cap, and level 5 (a 5-leaf star) has no support."""
    from repro.core.graphdb import Graph

    graphs = [Graph([1, 0, 0, 0, 0], [(0, i) for i in range(1, 5)],
                    [0] * 4) for _ in range(4)]
    ref = mine_host(graphs, 2)
    miner = Mirage(MirageConfig(
        minsup=2, n_partitions=2, pipeline="device_loop",
        max_size=len(ref.levels) + 1, max_embeddings=2,
        device_max_states=128))
    res = miner.fit(graphs)
    info = miner.last_device_loop
    assert info["completed"] and info["fallback"] is None, info
    assert info["escalations"] >= 1
    assert [set(lv) for lv in res.levels] == [set(lv) for lv in ref.levels]
    assert res.supports == {c: p.support for c, p in ref.frequent.items()}
