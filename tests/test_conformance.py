"""Differential conformance: the distributed miner vs the exact host
oracle, pattern-for-pattern and support-for-support, across the full
backend × reduce-mode × partition-scheme matrix.

The compiled kernel backends (``fused``, ``pallas``) only lower on TPU;
off-TPU each resolves to its interpret-mode twin so the matrix always
runs end-to-end with identical semantics (the interpret kernels execute
the same Pallas program, un-jitted).

A deeper Hypothesis-driven sweep rides along when hypothesis is
installed (random DBs, random configs); the seeded matrix above is the
always-on floor.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.graphdb import Graph, random_db
from repro.core.host_miner import mine_host
from repro.core.mining import Mirage, MirageConfig

BACKENDS = ["fused", "fused_interpret", "pallas", "ref"]
_ON_TPU = jax.default_backend() == "tpu"
_CPU_TWIN = {"fused": "fused_interpret", "pallas": "interpret"}


def resolve_backend(backend: str) -> str:
    if _ON_TPU:
        return backend
    return _CPU_TWIN.get(backend, backend)


def canon_host(res):
    return sorted((c, i.support) for c, i in res.frequent.items())


def canon_dist(res):
    return sorted(res.supports.items())


_DBS = {}


def conformance_db():
    """One shared seeded DB + host-oracle result for the whole matrix."""
    if "db" not in _DBS:
        graphs = random_db(18, n_vertices=6, extra_edge_prob=0.35,
                           n_vlabels=3, n_elabels=2, seed=42)
        _DBS["db"] = (graphs, mine_host(graphs, 5, max_size=3))
    return _DBS["db"]


@pytest.mark.parametrize("scheme", [1, 2])
@pytest.mark.parametrize("reduce", ["psum", "reduce_scatter"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_conformance_matrix(backend, reduce, scheme):
    graphs, ref = conformance_db()
    cfg = MirageConfig(minsup=5, n_partitions=2, scheme=scheme,
                       max_size=3, reduce=reduce,
                       backend=resolve_backend(backend))
    res = Mirage(cfg).fit(graphs)
    assert [set(l) for l in res.levels] == [set(l) for l in ref.levels], (
        backend, reduce, scheme)
    assert canon_dist(res) == canon_host(ref), (backend, reduce, scheme)


@pytest.mark.parametrize("pipeline", ["single_sync", "legacy"])
def test_conformance_pipelines_agree(pipeline):
    """Both driver pipelines must produce the oracle result — the legacy
    two-program driver doubles as a differential check on the fused
    level program."""
    graphs, ref = conformance_db()
    cfg = MirageConfig(minsup=5, n_partitions=2, max_size=3,
                       pipeline=pipeline)
    res = Mirage(cfg).fit(graphs)
    assert canon_dist(res) == canon_host(ref), pipeline


def test_escalation_valve_adversarial_overflow():
    """Adversarial DB: one vertex/edge label and dense wiring make the
    level-2 embedding counts blow straight through the initial M cap.
    The valve must escalate (observable in stats) and land on exact
    supports with zero residual overflow."""
    graphs = random_db(8, n_vertices=8, extra_edge_prob=0.9, n_vlabels=1,
                       n_elabels=1, seed=7)
    ref = mine_host(graphs, 4, max_size=3)
    cfg = MirageConfig(minsup=4, n_partitions=2, max_size=3,
                       max_embeddings=2, escalate_on_overflow=True,
                       max_embeddings_limit=4096)
    res = Mirage(cfg).fit(graphs)
    assert sum(st.escalations for st in res.stats) > 0, (
        "the M cap must actually overflow for this DB")
    assert res.total_overflow == 0
    assert [set(l) for l in res.levels] == [set(l) for l in ref.levels]
    assert canon_dist(res) == canon_host(ref)


def test_escalation_valve_respects_ceiling():
    """With a hard ceiling below the need, overflow must be *reported*
    (exactness telemetry), never silently swallowed."""
    graphs = random_db(8, n_vertices=8, extra_edge_prob=0.9, n_vlabels=1,
                       n_elabels=1, seed=7)
    cfg = MirageConfig(minsup=4, n_partitions=2, max_size=3,
                       max_embeddings=2, escalate_on_overflow=True,
                       max_embeddings_limit=4)
    res = Mirage(cfg).fit(graphs)
    assert res.total_overflow > 0


@pytest.mark.parametrize("max_embeddings", [2, 4])
@pytest.mark.parametrize("n_partitions", [1, 2])
def test_spill_store_is_exact_below_the_need(n_partitions, max_embeddings):
    """The adversarial DB under embedding_store="spill": a fixed M far
    below the need spills most pairs, and the answer is still the
    oracle's, with nothing cut off."""
    graphs = random_db(8, n_vertices=8, extra_edge_prob=0.9, n_vlabels=1,
                       n_elabels=1, seed=7)
    ref = mine_host(graphs, 4, max_size=3)
    cfg = MirageConfig(minsup=4, n_partitions=n_partitions, max_size=3,
                       max_embeddings=max_embeddings,
                       embedding_store="spill")
    res = Mirage(cfg).fit(graphs)
    assert [set(l) for l in res.levels] == [set(l) for l in ref.levels]
    assert canon_dist(res) == canon_host(ref)
    assert res.total_overflow == 0
    assert sum(st.spill_rows for st in res.stats) > 0
    assert all(st.escalations == 0 for st in res.stats)


@pytest.mark.parametrize("pipeline", ["device_loop", "legacy"])
def test_spill_store_needs_single_sync(pipeline):
    with pytest.raises(ValueError, match="single_sync"):
        MirageConfig(minsup=4, max_size=3, pipeline=pipeline,
                     embedding_store="spill")


def test_spill_store_degrades_on_its_own_pipeline():
    """The supervisor's last rung drops a spill-store run to the "ref"
    backend on single_sync, the one pipeline that mines it."""
    from repro.core.supervisor import _degrade

    cfg = _degrade(MirageConfig(minsup=4, embedding_store="spill"),
                   "legacy")
    assert (cfg.pipeline, cfg.backend, cfg.embedding_store) == (
        "single_sync", "ref", "spill")


def test_spill_store_takes_no_checkpoints(tmp_path):
    with pytest.raises(ValueError, match="checkpoint_dir"):
        MirageConfig(minsup=4, checkpoint_dir=str(tmp_path),
                     embedding_store="spill")


# ---------------------------------------------------------------------------
# checkpoint/resume across bucket boundaries (runtime/checkpoint.py
# padding round-trips: save and resume may disagree on bucket floors —
# or on bucketing at all — AND on worker count)
# ---------------------------------------------------------------------------

RESUME_BUCKET_SNIPPET = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    from repro.core.graphdb import pubchem_like_db
    from repro.core.host_miner import mine_host
    from repro.core.mapreduce import MiningMesh
    from repro.core.mining import Mirage, MirageConfig
    from repro.runtime import jax_compat

    ck = sys.argv[1]
    graphs = pubchem_like_db(24, seed=31, avg_edges=10)
    ref = mine_host(graphs, 6, max_size=4)

    def mesh(w):
        return MiningMesh(jax_compat.make_mesh((w,), ("w",)))

    def check(res, tag):
        assert [set(l) for l in res.levels] == \\
            [set(l) for l in ref.levels], tag
        for code, sup in res.supports.items():
            assert sup == ref.frequent[code].support, (tag, code)

    # phase 1: 2 levels on TWO workers under SMALL bucket floors
    cfg = MirageConfig(minsup=6, n_partitions=4, max_size=2,
                       checkpoint_dir=ck, bucket_shapes=True,
                       bucket_c_floor=8, bucket_s_floor=4,
                       bucket_k_floor=4)
    Mirage(cfg, mesh(2)).fit(graphs)

    # phase 2: resume to completion on ONE worker at a DIFFERENT bucket
    # boundary (every floor changed) — the checkpoint's canonical store
    # must re-pad into the new family
    cfg2 = MirageConfig(minsup=6, n_partitions=4, max_size=4,
                        checkpoint_dir=ck, bucket_shapes=True,
                        bucket_c_floor=32, bucket_s_floor=16,
                        bucket_k_floor=8)
    check(Mirage(cfg2, mesh(1)).fit(graphs, resume=True), "rebucket")

    # phase 3: the SAME checkpoint resumed with bucketing OFF on two
    # workers — padding must not have leaked into the saved state
    cfg3 = MirageConfig(minsup=6, n_partitions=4, max_size=4,
                        checkpoint_dir=ck, bucket_shapes=False)
    check(Mirage(cfg3, mesh(2)).fit(graphs, resume=True), "unbucketed")

    # phase 4: an UNBUCKETED checkpoint resumed bucketed (reverse trip)
    ck2 = ck + "-rev"
    cfg4 = MirageConfig(minsup=6, n_partitions=4, max_size=2,
                        checkpoint_dir=ck2, bucket_shapes=False)
    Mirage(cfg4, mesh(1)).fit(graphs)
    cfg5 = MirageConfig(minsup=6, n_partitions=4, max_size=4,
                        checkpoint_dir=ck2, bucket_shapes=True,
                        bucket_c_floor=16, bucket_s_floor=8,
                        bucket_k_floor=8)
    check(Mirage(cfg5, mesh(2)).fit(graphs, resume=True), "adopt")
    print("RESUME-BUCKET-OK")
""")


def _run_snippet(snippet, *argv, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run(
        [sys.executable, "-c", snippet, *map(str, argv)],
        capture_output=True, text=True, env=env, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_resume_across_bucket_boundaries(tmp_path):
    """A checkpoint written at one bucket boundary resumes at another
    (and with a different worker count, and with bucketing toggled both
    ways) bit-identically to the host oracle."""
    assert "RESUME-BUCKET-OK" in _run_snippet(
        RESUME_BUCKET_SNIPPET, tmp_path / "ck")


# ---------------------------------------------------------------------------
# hypothesis sweep (optional dependency)
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings, strategies as st
    _HAVE_HYP = True
except ImportError:                                        # pragma: no cover
    _HAVE_HYP = False

if _HAVE_HYP:
    @st.composite
    def small_dbs(draw):
        n = draw(st.integers(6, 14))
        seed = draw(st.integers(0, 2**31 - 1))
        return random_db(n, n_vertices=6, vertex_jitter=1,
                         extra_edge_prob=0.3, n_vlabels=3, n_elabels=2,
                         seed=seed)

    @settings(max_examples=6, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(small_dbs(),
           st.sampled_from(["fused_interpret", "ref"]),
           st.sampled_from(["psum", "reduce_scatter"]),
           st.sampled_from([1, 2]),
           st.sampled_from([1, 2]))
    def test_conformance_hypothesis(graphs, backend, reduce, scheme, parts):
        minsup = max(2, len(graphs) // 3)
        ref = mine_host(graphs, minsup, max_size=3)
        cfg = MirageConfig(minsup=minsup, n_partitions=parts, scheme=scheme,
                           max_size=3, reduce=reduce,
                           backend=resolve_backend(backend))
        res = Mirage(cfg).fit(graphs)
        assert canon_dist(res) == canon_host(ref), (backend, reduce, scheme)

    @settings(max_examples=8, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(small_dbs(),
           st.sampled_from([4, 16, 64]),        # bucket_c_floor
           st.sampled_from([2, 8, 32]),         # bucket_s_floor (2 forces
           st.sampled_from([4, 8]),             #   cap-miss retries)
           st.sampled_from(["fused_interpret", "ref"]),
           st.booleans())
    def test_bucketing_never_leaks_hypothesis(graphs, c_floor, s_floor,
                                              k_floor, backend, predict):
        """For ANY bucket-floor family, the bucketed pipeline, the
        unbucketed pipeline, and the host oracle return identical
        frequent sets and supports — padding must never reach verdicts,
        caps, or the compaction."""
        minsup = max(2, len(graphs) // 3)
        ref = mine_host(graphs, minsup, max_size=3)
        base = dict(minsup=minsup, n_partitions=2, max_size=3,
                    backend=resolve_backend(backend),
                    predict_survivors=predict)
        res_b = Mirage(MirageConfig(
            bucket_shapes=True, bucket_c_floor=c_floor,
            bucket_s_floor=s_floor, bucket_k_floor=k_floor,
            **base)).fit(graphs)
        res_u = Mirage(MirageConfig(bucket_shapes=False, **base)).fit(graphs)
        key = (c_floor, s_floor, k_floor, backend, predict)
        assert canon_dist(res_b) == canon_dist(res_u), key
        assert canon_dist(res_b) == canon_host(ref), key
