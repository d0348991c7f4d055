"""Bit-packed support path (ISSUE 8, DESIGN.md §12): bitset primitive
units, packed-kernel parity vs the dense kernel and the host oracle,
the packed wire codec, checkpoint packed<->dense cross-resume, and the
multi-worker packed conformance matrix.

The always-on floor is seeded; a Hypothesis sweep over random DBs with
G % 32 != 0 rides along when hypothesis is installed (CI has it, the
dev container may not)."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graphdb import random_db
from repro.core.host_miner import mine_host
from repro.core.level_step import (reassemble_wire, wire_checksum,
                                   wire_cost_model, wire_words)
from repro.core.mining import Mirage, MirageConfig
from repro.kernels import bitset
from repro.kernels.ops import level_supports

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYP = True
except ImportError:
    _HAVE_HYP = False


# ---------------------------------------------------------------------------
# bitset primitives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [0, 1, 31, 32, 33, 64, 100])
def test_pack_unpack_roundtrip_ragged(n):
    rng = np.random.default_rng(n)
    bits = rng.random((3, n)) < 0.5
    words = bitset.pack_bits(bits)
    assert words.dtype == np.uint32
    assert words.shape == (3, bitset.n_words(n))
    np.testing.assert_array_equal(bitset.unpack_bits(words, n), bits)
    # pad bits in the last word are ZERO (the layout contract)
    np.testing.assert_array_equal(words & ~bitset.tail_mask(n), 0)


def test_popcount_matches_python():
    rng = np.random.default_rng(5)
    w = rng.integers(0, 1 << 32, 64, dtype=np.uint32)
    got = bitset.popcount(w)
    want = np.array([bin(int(x)).count("1") for x in w], np.int32)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    # the extremes SWAR gets wrong first
    np.testing.assert_array_equal(
        bitset.popcount(np.array([0, 0xFFFFFFFF, 0x80000001], np.uint32)),
        [0, 32, 2])


@pytest.mark.parametrize("n", [1, 17, 32, 45])
def test_packed_any_count_equals_dense(n):
    rng = np.random.default_rng(n)
    bits = rng.random((4, n)) < 0.4
    words = bitset.pack_bits(bits)
    np.testing.assert_array_equal(
        bitset.packed_any_count(words, n), bits.sum(-1).astype(np.int32))
    # ...even after a foreign lane-OR dirtied the pad tail
    dirty = bitset.lane_or(words, ~bitset.tail_mask(n))
    np.testing.assert_array_equal(
        bitset.packed_any_count(dirty, n), bits.sum(-1).astype(np.int32))


def test_lane_and_is_intersection():
    rng = np.random.default_rng(9)
    a = rng.random(70) < 0.5
    b = rng.random(70) < 0.5
    np.testing.assert_array_equal(
        bitset.unpack_bits(
            bitset.lane_and(bitset.pack_bits(a), bitset.pack_bits(b)), 70),
        a & b)


def test_bitset_ops_work_on_jax_arrays():
    bits = np.arange(40) % 3 == 0
    words = bitset.pack_bits(jnp.asarray(bits))
    assert isinstance(words, jnp.ndarray)
    np.testing.assert_array_equal(
        np.asarray(bitset.unpack_bits(words, 40)), bits)
    assert int(bitset.packed_any_count(words, 40)) == int(bits.sum())


def test_support_path_cost_model_packed_undercuts_dense():
    """The modeled support-path bytes behind the CI packed gate: >= 8x
    HBM reduction at word-aligned G, and the packed total must undercut
    dense at every worker count."""
    for w in (1, 2, 4, 8):
        dense = bitset.support_path_cost_model(64, 256, w, packed=False)
        packed = bitset.support_path_cost_model(64, 256, w, packed=True)
        assert dense["hbm_bytes"] / packed["hbm_bytes"] >= 8
        assert packed["total_bytes"] < dense["total_bytes"]
        if w > 1:
            assert packed["collective_bytes"] < dense["collective_bytes"]


# ---------------------------------------------------------------------------
# packed kernel parity (interpret mode on CPU, same program as TPU)
# ---------------------------------------------------------------------------

def _random_level(rng, C=7, P=5, G=20, M=8, K=4, T=6, F=8):
    """Random-but-consistent join inputs, deliberately misaligned
    (C % tile_c != 0, G % 32 != 0)."""
    pol = rng.integers(0, 32, (P, G, M, K)).astype(np.int32)
    pmask = rng.random((P, G, M)) < 0.7
    pol = np.where(rng.random((P, G, M, K)) < 0.15, -1, pol)
    src = rng.integers(0, 32, (T, G, F)).astype(np.int32)
    dst = rng.integers(0, 32, (T, G, F)).astype(np.int32)
    emask = rng.random((T, G, F)) < 0.7
    src = np.where(emask, src, -1)
    dst = np.where(emask, dst, -1)
    meta = np.stack([rng.integers(0, P, C), rng.integers(0, K, C),
                     rng.integers(0, K, C), rng.integers(0, 2, C),
                     rng.integers(0, T, C)], axis=1).astype(np.int32)
    return meta, pol, pmask, src, dst, emask


@pytest.mark.parametrize("seed", [0, 3, 11])
def test_fused_packed_backend_matches_ref_and_dense(seed):
    rng = np.random.default_rng(seed)
    meta, pol, pmask, src, dst, emask = _random_level(rng)
    args = (jnp.asarray(meta), jnp.asarray(pol), jnp.asarray(pmask),
            jnp.asarray(src), jnp.asarray(dst), jnp.asarray(emask))
    sup_r, emb_r = level_supports(*args, backend="ref")
    sup_d, emb_d = level_supports(*args, backend="fused_interpret")
    sup_p, emb_p = level_supports(*args, backend="fused_packed_interpret")
    np.testing.assert_array_equal(np.asarray(sup_p), np.asarray(sup_r))
    np.testing.assert_array_equal(np.asarray(sup_p), np.asarray(sup_d))
    np.testing.assert_array_equal(np.asarray(emb_p), np.asarray(emb_r))
    np.testing.assert_array_equal(np.asarray(emb_p), np.asarray(emb_d))


@pytest.mark.parametrize("G", [37, 300])
def test_packed_kernel_vbits_match_oracle_bitsets(G):
    """The kernel's in-VMEM bit packing (``_pack_words``, one graph tile
    at a time) must give the host oracle's per-graph verdict bitsets
    bit for bit (pad tail zero included) — they are what the
    AND+popcount support count is computed from — and the kernel's
    supports must be their popcounts.  G=300 spans three 128-lane graph
    tiles, the last one overhanging the graph axis."""
    from repro.core.candgen import schedule_candidates
    from repro.core.embedding import support_bits_ref
    from repro.kernels.embedding_join import DEFAULT_TILE_G
    from repro.kernels.fused_level import _pack_words, graph_tile
    from repro.kernels.ops import fused_level_supports_packed

    rng = np.random.default_rng(4)
    meta, pol, pmask, src, dst, emask = _random_level(rng, C=9, G=G)
    sup_o, _, vbits_o = support_bits_ref(
        jnp.asarray(meta), jnp.asarray(pol), jnp.asarray(pmask),
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(emask))
    gw = bitset.n_words(G)
    tg = graph_tile(DEFAULT_TILE_G, G)
    n_g = -(-G // tg)
    bits = np.zeros((16, n_g * tg), np.int32)   # 8-row candidate blocks
    bits[:9, :G] = bitset.unpack_bits(np.asarray(vbits_o), G)
    words = np.concatenate(
        [np.asarray(_pack_words(jnp.asarray(bits[:, g * tg:(g + 1) * tg]),
                                bitset.n_words(tg)))
         for g in range(n_g)], axis=1)
    np.testing.assert_array_equal(words[:9, :gw], np.asarray(vbits_o))
    # words past n_words(G) (graph-tile padding) and pad rows are zero
    np.testing.assert_array_equal(words[:9, gw:], 0)
    np.testing.assert_array_equal(words[9:], 0)

    sched = schedule_candidates(meta)
    sup_k, _ = fused_level_supports_packed(
        jnp.asarray(sched.meta), jnp.asarray(sched.tiles),
        jnp.asarray(pol)[None], jnp.asarray(pmask)[None],
        jnp.asarray(src)[None], jnp.asarray(dst)[None],
        jnp.asarray(emask)[None], interpret=True)
    inv = np.asarray(sched.inv)
    np.testing.assert_array_equal(
        np.asarray(sup_k)[0][inv], np.asarray(sup_o))
    np.testing.assert_array_equal(
        np.asarray(sup_o), bitset.popcount(np.asarray(vbits_o)).sum(-1))


# ---------------------------------------------------------------------------
# end-to-end conformance: packed == dense == host oracle, G % 32 != 0
# ---------------------------------------------------------------------------

def _conform(graphs, minsup, max_size, **kw):
    ref = mine_host(graphs, minsup, max_size=max_size)
    want = sorted((c, i.support) for c, i in ref.frequent.items())
    base = dict(minsup=minsup, max_size=max_size, **kw)
    packed = Mirage(MirageConfig(**base)).fit(graphs)
    dense = Mirage(MirageConfig(packed_support=False, **base)).fit(graphs)
    assert sorted(packed.supports.items()) == want
    assert sorted(dense.supports.items()) == want
    assert [set(l) for l in packed.levels] == [set(l) for l in dense.levels]


@pytest.mark.parametrize("seed,backend", [(42, None), (42, "fused_interpret"),
                                          (7, None), (7, "fused_interpret")])
def test_packed_conformance_seeded(seed, backend):
    graphs = random_db(18, n_vertices=6, extra_edge_prob=0.35,
                       n_vlabels=3, n_elabels=2, seed=seed)
    assert len(graphs) % 32 != 0
    _conform(graphs, 5, 3, n_partitions=4, backend=backend)


def test_packed_default_on_for_single_sync():
    m = Mirage(MirageConfig(minsup=2))
    assert m._packed_support(100) is True
    assert m._packed_support((1 << 16) - 1) is True
    # uint16 wire bound: a DB too large for 2x-uint16 packing stays dense
    assert m._packed_support(1 << 16) is False
    assert Mirage(MirageConfig(
        minsup=2, packed_support=False))._packed_support(100) is False
    assert Mirage(MirageConfig(
        minsup=2, pipeline="legacy"))._packed_support(100) is False
    with pytest.raises(ValueError, match="packed_support"):
        MirageConfig(minsup=2, pipeline="legacy", packed_support=True)


if _HAVE_HYP:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 10_000),
           st.sampled_from([9, 18, 33, 41]),      # all G % 32 != 0
           st.sampled_from([2, 4]))
    def test_packed_conformance_hypothesis(seed, n_graphs, n_parts):
        graphs = random_db(n_graphs, n_vertices=6, extra_edge_prob=0.35,
                           n_vlabels=2, n_elabels=2, seed=seed)
        _conform(graphs, max(2, n_graphs // 6), 3, n_partitions=n_parts)


# ---------------------------------------------------------------------------
# packed wire codec
# ---------------------------------------------------------------------------

def _pack_gsup_host(gsup):
    """Host mirror of the device _pack_wire gsup packing: 2x uint16 per
    int32 word, little end first."""
    u = gsup.astype(np.uint32)
    if u.shape[0] % 2:
        u = np.concatenate([u, np.zeros(1, np.uint32)])
    return (u[0::2] | (u[1::2] << np.uint32(16))).astype(np.int64).astype(
        np.uint32).view(np.int32)


def _make_packed_wire(cp, n_partitions, n_shards, *, seed=0):
    rng = np.random.default_rng(seed)
    gsup = rng.integers(0, 1 << 16, cp).astype(np.int32)
    scalars = np.array([7, 0, 1, 1 << 15, 0], np.int32)
    perm = np.arange(n_partitions, dtype=np.int32)[::-1].copy()
    shards = []
    for s in np.split(gsup, n_shards):
        body = np.concatenate([_pack_gsup_host(s), scalars, perm])
        shards.append(np.concatenate([body, [wire_checksum(body)]]))
    dense_body = np.concatenate([gsup, scalars, perm])
    return np.concatenate(shards).astype(np.int32), dense_body


@pytest.mark.parametrize("n_shards", [1, 2, 4])
@pytest.mark.parametrize("cp", [16, 20])
def test_packed_wire_roundtrip(cp, n_shards):
    """The packed wire (2 supports per word, checksum over PACKED
    words) must reassemble to the exact dense body, odd shard slices
    included."""
    if (cp // n_shards) % 2 and n_shards > 1:
        pytest.skip("odd per-shard slice width with multiple shards")
    n_partitions = 4
    host, dense_body = _make_packed_wire(cp, n_partitions, n_shards)
    assert host.shape[0] == wire_words(cp, n_partitions, n_shards,
                                       packed=True)
    out = reassemble_wire(host, n_partitions, n_shards, packed=True, cp=cp)
    np.testing.assert_array_equal(out, dense_body)


def test_packed_wire_smaller_and_corruption_caught():
    cp, n_partitions = 64, 4
    for n_shards in (1, 2):
        assert wire_words(cp, n_partitions, n_shards, packed=True) < \
            wire_words(cp, n_partitions, n_shards)
        host, _ = _make_packed_wire(cp, n_partitions, n_shards)
        for w in {0, host.shape[0] // 2, host.shape[0] - 1}:
            bad = host.copy()
            bad[w] ^= np.int32(1 << 5)
            assert reassemble_wire(bad, n_partitions, n_shards,
                                   packed=True, cp=cp) is None, (n_shards, w)


def test_packed_wire_cost_model_undercuts_dense():
    for w in (1, 2, 4):
        for sharded in (False, True) if w > 1 else (False,):
            d = wire_cost_model(256, 8, w, reduce="reduce_scatter",
                                sharded=sharded)
            p = wire_cost_model(256, 8, w, reduce="reduce_scatter",
                                sharded=sharded, packed=True)
            assert p["host_bytes"] < d["host_bytes"], (w, sharded)
            assert p["total_bytes"] < d["total_bytes"], (w, sharded)


# ---------------------------------------------------------------------------
# checkpoint: save packed -> resume dense, and vice versa
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("first,second", [(None, False), (False, None)])
def test_checkpoint_cross_resume_packed_dense(tmp_path, first, second):
    """A run checkpointed with the packed path enabled must resume with
    it disabled (and vice versa) bit-identically: checkpoints store the
    canonical OL store (bool masks bit-packed at rest), so the support
    path is free to differ across the save/resume boundary."""
    graphs = random_db(20, n_vertices=8, extra_edge_prob=0.5,
                       n_vlabels=2, n_elabels=1, seed=7)
    ref = mine_host(graphs, 6, max_size=5)
    ck = str(tmp_path / "ck")
    base = dict(minsup=6, n_partitions=4, checkpoint_dir=ck)
    Mirage(MirageConfig(max_size=3, packed_support=first, **base)
           ).fit(graphs)
    res = Mirage(MirageConfig(max_size=5, packed_support=second, **base)
                 ).fit(graphs, resume=True)
    assert res.stats[0].level == 4, "must resume, not restart"
    for code, sup in res.supports.items():
        assert sup == ref.frequent[code].support, code
    assert [set(l) for l in res.levels] == [set(l) for l in ref.levels]


def test_checkpoint_bool_leaves_bitpacked_on_disk(tmp_path):
    from repro.runtime import checkpoint as ckpt

    tree = {"pmask": np.ones((4, 8, 33), bool), "pol": np.zeros(3, np.int32)}
    p = str(tmp_path / "ck")
    ckpt.save_pytree(p, tree)
    with np.load(os.path.join(p, "data.npz")) as z:
        leaves = [z[k] for k in z.files]
    packed_leaves = [a for a in leaves if a.dtype == np.uint8]
    assert len(packed_leaves) == 1, "the bool mask must be stored packed"
    assert packed_leaves[0].nbytes == -(-4 * 8 * 33 // 8)  # 1 bit per flag
    back, _ = ckpt.load_pytree(p)
    np.testing.assert_array_equal(back["pmask"], tree["pmask"])
    assert back["pmask"].dtype == bool


# ---------------------------------------------------------------------------
# multi-worker packed matrix (subprocess: W simulated devices)
# ---------------------------------------------------------------------------

PACKED_MATRIX_SNIPPET = textwrap.dedent("""
    import itertools, os, sys
    W = int(sys.argv[1])
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={W}")
    import jax
    from repro.core.graphdb import random_db
    from repro.core.host_miner import mine_host
    from repro.core.mapreduce import MiningMesh
    from repro.core.mining import Mirage, MirageConfig
    from repro.runtime import jax_compat

    assert jax.device_count() == W
    graphs = random_db(18, n_vertices=6, extra_edge_prob=0.35,
                       n_vlabels=3, n_elabels=2, seed=42)
    ref = mine_host(graphs, 5, max_size=3)
    want = sorted((c, i.support) for c, i in ref.frequent.items())
    mesh = MiningMesh(jax_compat.make_mesh((W,), ("w",)))

    for packed, sharded, reduce in itertools.product(
            (None, False), (True, False), ("reduce_scatter", "psum")):
        if sharded and reduce != "reduce_scatter":
            continue
        cfg = MirageConfig(minsup=5, n_partitions=8, max_size=3,
                           reduce=reduce, sharded_wire=sharded,
                           packed_support=packed)
        res = Mirage(cfg, mesh).fit(graphs)
        key = (W, packed, sharded, reduce)
        assert sorted(res.supports.items()) == want, key
    print("PACKED-MATRIX-OK")
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


@pytest.mark.parametrize("workers", [2, 4, 8])
def test_multiworker_packed_matrix(workers):
    """packed (default-on) x sharded-wire x reduce mode, all
    bit-identical to the host oracle at W=2,4,8 — the packed verdict
    gather and the 2x-uint16 wire slice both cross real device
    boundaries here."""
    assert "PACKED-MATRIX-OK" in _run_snippet(PACKED_MATRIX_SNIPPET,
                                              workers)
