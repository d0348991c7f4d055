"""Distributed miner (shard_map) vs exact host miner + fault tolerance."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.graphdb import paper_toy_db, pubchem_like_db, random_db
from repro.core.host_miner import mine_host
from repro.core.mining import Mirage, MirageConfig
from repro.core.naive import mine_naive

import prep_reference


def assert_same_result(dist, ref):
    assert [set(l) for l in dist.levels] == [set(l) for l in ref.levels]
    for code, sup in dist.supports.items():
        assert sup == ref.frequent[code].support, code


@pytest.mark.parametrize("reduce", ["psum", "reduce_scatter"])
def test_toy_db_single_device(reduce):
    graphs = paper_toy_db()
    ref = mine_host(graphs, 2)
    cfg = MirageConfig(minsup=2, n_partitions=2, max_embeddings=8,
                       reduce=reduce)
    res = Mirage(cfg).fit(graphs)
    assert sum(res.counts()) == 13
    assert_same_result(res, ref)
    assert res.total_overflow == 0


@pytest.mark.parametrize("scheme", [1, 2])
def test_random_db_schemes(scheme):
    graphs = random_db(24, n_vertices=7, extra_edge_prob=0.3, n_vlabels=3,
                       n_elabels=2, seed=11)
    ref = mine_host(graphs, 5, max_size=4)
    cfg = MirageConfig(minsup=5, n_partitions=4, scheme=scheme, max_size=4)
    res = Mirage(cfg).fit(graphs)
    assert_same_result(res, ref)


def test_fractional_minsup():
    graphs = random_db(20, n_vertices=6, seed=3)
    ref = mine_host(graphs, 5, max_size=3)     # ceil(0.25 * 20) = 5
    res = Mirage(MirageConfig(minsup=0.25, n_partitions=4, max_size=3)).fit(graphs)
    assert_same_result(res, ref)


def test_overflow_escalation_keeps_exactness():
    """Start with M=2 (too small); the valve must escalate and stay exact."""
    graphs = random_db(10, n_vertices=8, extra_edge_prob=0.5, n_vlabels=2,
                       n_elabels=1, seed=2)
    ref = mine_host(graphs, 2, max_size=3)
    cfg = MirageConfig(minsup=2, n_partitions=2, max_size=3,
                       max_embeddings=2, escalate_on_overflow=True,
                       max_embeddings_limit=256)
    res = Mirage(cfg).fit(graphs)
    assert res.total_overflow == 0
    assert_same_result(res, ref)


def test_checkpoint_resume(tmp_path):
    graphs = pubchem_like_db(20, seed=5, avg_edges=10)
    ref = mine_host(graphs, 6, max_size=4)
    cfg = MirageConfig(minsup=6, n_partitions=4, max_size=4,
                       checkpoint_dir=str(tmp_path / "ck"))
    full = Mirage(cfg).fit(graphs)
    assert_same_result(full, ref)

    # simulate a crash after level 2: wipe later checkpoints, resume
    from repro.runtime import checkpoint as ckpt
    steps = ckpt.all_steps(cfg.checkpoint_dir)
    assert steps, "mining must have checkpointed"
    import shutil
    for s in steps[1:]:
        shutil.rmtree(os.path.join(cfg.checkpoint_dir, f"step_{s:010d}"))
    resumed = Mirage(cfg).fit(graphs, resume=True)
    assert_same_result(resumed, ref)


def test_make_partitions_rejects_empty_partitions():
    graphs = paper_toy_db()
    from repro.core.partition import make_partitions
    with pytest.raises(ValueError, match="exceeds the database size"):
        make_partitions(graphs, 2, len(graphs) + 1)
    with pytest.raises(ValueError, match="must be >= 1"):
        make_partitions(graphs, 2, 0)


def test_scheme2_spreads_zero_edge_graphs():
    """LPT ties (graphs fully stripped by the edge filter) must not
    starve partitions empty."""
    from repro.core.graphdb import Graph
    from repro.core.partition import make_partitions
    # distinct singleton labels -> every edge is infrequent at minsup 2
    graphs = [Graph([i, i], [(0, 1)], [i]) for i in range(8)]
    part = make_partitions(graphs, 2, 4, scheme=2)
    assert all(len(p) > 0 for p in part.partitions)


def test_mirage_clamps_excess_partitions():
    """n_partitions > |G| auto-clamps (instead of silently padding empty
    partitions) and still matches the oracle."""
    graphs = paper_toy_db()
    ref = mine_host(graphs, 2)
    cfg = MirageConfig(minsup=2, n_partitions=64, max_embeddings=8)
    res = Mirage(cfg).fit(graphs)
    assert_same_result(res, ref)


def test_resume_reuses_checkpointed_partition_count(tmp_path):
    """A resumed run must reproduce the WRITER's partitioning, even when
    the clamp is active (n_partitions > |G|) — the partition count is
    baked into the checkpointed OL store."""
    graphs = pubchem_like_db(5, seed=3, avg_edges=9)
    ref = mine_host(graphs, 2, max_size=4)
    cfg = MirageConfig(minsup=2, n_partitions=16, max_size=2,
                       checkpoint_dir=str(tmp_path / "ck"))
    Mirage(cfg).fit(graphs)                      # clamps to 5 partitions
    cfg2 = MirageConfig(minsup=2, n_partitions=16, max_size=4,
                        checkpoint_dir=str(tmp_path / "ck"))
    res = Mirage(cfg2).fit(graphs, resume=True)
    assert_same_result(res, ref)


def test_mirage_config_rejects_bad_partitions():
    with pytest.raises(ValueError, match="must be >= 1"):
        MirageConfig(minsup=2, n_partitions=0)
    with pytest.raises(ValueError, match="pipeline"):
        MirageConfig(minsup=2, pipeline="bogus")


def test_naive_baseline_duplicates():
    """Hill et al. baseline emits duplicates; MIRAGE's distinct set matches."""
    graphs = paper_toy_db()
    ref = mine_host(graphs, 2)
    naive = mine_naive(graphs, 2, n_iterations=6)
    assert naive.distinct_frequent == len(ref.frequent) == 13
    assert naive.duplicate_ratio > 1.0, "must demonstrate the duplication blowup"


MULTIDEV_SNIPPET = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import numpy as np
    from repro.core.graphdb import pubchem_like_db
    from repro.core.host_miner import mine_host
    from repro.core.mapreduce import MiningMesh
    from repro.core.mining import Mirage, MirageConfig

    assert jax.device_count() == 8
    from repro.runtime import jax_compat
    mesh = MiningMesh(jax_compat.make_mesh((2, 4), ("data", "model")))
    graphs = pubchem_like_db(48, seed=7, avg_edges=10)
    ref = mine_host(graphs, 12, max_size=4)
    for reduce in ("psum", "reduce_scatter"):
        cfg = MirageConfig(minsup=12, n_partitions=16, max_size=4,
                           reduce=reduce, rebalance=True,
                           rebalance_threshold=1.05)
        res = Mirage(cfg, mesh).fit(graphs)
        assert [set(l) for l in res.levels] == [set(l) for l in ref.levels]
        for code, sup in res.supports.items():
            assert sup == ref.frequent[code].support

    # regression: resume AFTER a rebalance permuted the partitions —
    # checkpoints must store the OL store in canonical order
    ck = tempfile.mkdtemp()
    cfg = MirageConfig(minsup=12, n_partitions=16, max_size=2,
                       rebalance=True, rebalance_threshold=1.0,
                       checkpoint_dir=ck)
    Mirage(cfg, mesh).fit(graphs)
    cfg2 = MirageConfig(minsup=12, n_partitions=16, max_size=4,
                        rebalance=True, rebalance_threshold=1.0,
                        checkpoint_dir=ck)
    res = Mirage(cfg2, mesh).fit(graphs, resume=True)
    assert [set(l) for l in res.levels] == [set(l) for l in ref.levels]
    for code, sup in res.supports.items():
        assert sup == ref.frequent[code].support
    print("MULTIDEV-OK")
""")


def test_multidevice_mining_subprocess():
    """8 fake devices, 2x4 mesh, 16 partitions, both reduce variants,
    rebalancing enabled — full distributed semantics check."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", MULTIDEV_SNIPPET],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "MULTIDEV-OK" in out.stdout


def _emptied_by_filtering():
    # the first six graphs hold only singleton triples, which no minsup
    # of 2 or more keeps; the isolated vertex of the last goes with its
    # graph's edges either way
    from repro.core.graphdb import Graph
    return ([Graph([i, i + 1], [(0, 1)], [i]) for i in range(6)]
            + random_db(8, n_vertices=6, extra_edge_prob=0.4, n_vlabels=2,
                        n_elabels=2, seed=17)
            + [Graph([1, 0, 1], [(0, 2)], [0])])


_PARTITION_DBS = {
    "three_labels_int_minsup": (random_db(15, n_vertices=7,
                                          extra_edge_prob=0.5, n_vlabels=3,
                                          n_elabels=3, seed=31), 3, 4),
    "three_labels_float_minsup": (random_db(15, n_vertices=7,
                                            extra_edge_prob=0.5,
                                            n_vlabels=3, n_elabels=3,
                                            seed=31), 0.3, 4),
    "emptied_by_filtering": (_emptied_by_filtering(), 2, 3),
    "molecules": (pubchem_like_db(20, seed=2, avg_edges=10), 0.25, 5),
    "one_partition": (paper_toy_db(), 2, 1),
}


@pytest.mark.parametrize("scheme", [1, 2, "density"])
@pytest.mark.parametrize("db", list(_PARTITION_DBS))
def test_make_partitions_equals_the_loop_reference(db, scheme):
    """Graph ids (LPT and density tie-breaks included), alphabet,
    threshold and every filtered graph, with its re-densified vertex
    ids, equal those of the loop implementation."""
    from repro.core.partition import filter_infrequent_edges, make_partitions
    graphs, minsup, n_parts = _PARTITION_DBS[db]
    got = make_partitions(graphs, minsup, n_parts, scheme=scheme)
    want = prep_reference.make_partitions(graphs, minsup, n_parts,
                                          scheme=scheme)
    assert got.graph_ids == want.graph_ids
    assert all(type(i) is int for ids in got.graph_ids for i in ids)
    assert got.alphabet._set == want.alphabet._set
    assert got.alphabet.canonical() == want.alphabet.canonical()
    assert (got.alphabet.vlabels, got.alphabet.elabels) == (
        want.alphabet.vlabels, want.alphabet.elabels)
    assert (got.minsup, got.n_graphs) == (want.minsup, want.n_graphs)
    filtered, alphabet = filter_infrequent_edges(graphs, got.minsup)
    ref_filtered, _ = prep_reference.filter_infrequent_edges(graphs,
                                                             got.minsup)
    assert alphabet._set == want.alphabet._set
    pairs = [(g, w) for gp, wp in zip(got.partitions, want.partitions)
             for g, w in zip(gp, wp)] + list(zip(filtered, ref_filtered))
    assert len(pairs) == 2 * len(graphs)
    for g, w in pairs:
        for name in ("vlabels", "edges", "elabels"):
            a, b = getattr(g, name), getattr(w, name)
            assert a.dtype == b.dtype and a.shape == b.shape, name
            np.testing.assert_array_equal(a, b)
