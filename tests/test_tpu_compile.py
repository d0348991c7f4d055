"""Compile the main path for a described TPU v5e chip — no chip needed.

The TPU compiler is installed with jaxlib: it compiles for a chip that
is described, not attached, and refuses what the chip would refuse
(block shapes off the (8, 128) tiling, too much VMEM, a program larger
than HBM).  Interpret-mode tests check none of that.  Each case compiles
one program at the widths ``chip_smoke.py`` runs (8 partitions of a
10,000-graph molecule DB: G=1,250 graphs lane-aligned to 1,280, M=32
embeddings, K=8 vertex slots, T=45 label triples, F=32 occurrences) and
runs nothing.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and every xdist worker
imports this file.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

PP, P, G, M, K, T, F = 8, 32, 1280, 32, 8, 45, 32
HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # keep compiler logs off disk
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as exc:               # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep it out of the cache entirely
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _store_shapes(sharding, p=P):
    return [_shape(sharding, (PP, p, G, M, K), jnp.int32),
            _shape(sharding, (PP, p, G, M), jnp.bool_),
            _shape(sharding, (PP, T, G, F), jnp.int32),
            _shape(sharding, (PP, T, G, F), jnp.int32),
            _shape(sharding, (PP, T, G, F), jnp.bool_)]


def _copies_of(hlo: str, n_elements: int) -> int:
    """Copy ops in compiled HLO whose result holds ``n_elements``."""
    n = 0
    for line in hlo.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\{[^}]*\} copy\(", line)
        if m and math.prod(int(d) for d in m.group(1).split(",")) \
                == n_elements:
            n += 1
    return n


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize("tile_c", [1, 8])
def test_fused_kernel_compiles_for_v5e(one_chip, packed, tile_c):
    from repro.kernels.ops import (fused_level_supports,
                                   fused_level_supports_packed)

    n_tiles = 512 // tile_c
    fn = fused_level_supports_packed if packed else fused_level_supports
    compiled = jax.jit(fn).lower(
        _shape(one_chip, (n_tiles * tile_c, 6), jnp.int32),
        _shape(one_chip, (n_tiles, 2), jnp.int32),
        *_store_shapes(one_chip)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo            # the Mosaic kernel, compiled
    # the graph-minor operand views are layout bitcasts, not store copies
    assert _copies_of(hlo, PP * P * G * M * K) == 0
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (64 << 20)


def test_level_program_compiles_for_v5e(topo):
    """The single_sync level program at level 2 of the smoke run: 342
    candidates bucketed to Cp=512, survivor cap S=128."""
    from repro.core.level_step import _level_program
    from repro.core.mapreduce import MiningMesh
    from repro.runtime import jax_compat

    mesh = MiningMesh(jax_compat.make_mesh((1,), ("w",),
                                           devices=topo.devices[:1]))
    rep = NamedSharding(mesh.mesh, mesh.replicated())
    parts = NamedSharding(mesh.mesh, mesh.spec_parts())
    cp, s, tile_c = 512, 128, 8
    fn = _level_program(mesh, 1000, "fused", "reduce_scatter", M, s, True,
                        1.25, False, K, True, True, 8 * 1250)
    compiled = fn.lower(
        _shape(rep, (), jnp.int32), _shape(rep, (P,), jnp.int32),
        _shape(rep, (cp, 6), jnp.int32),
        _shape(rep, (cp // tile_c, 2), jnp.int32),
        _shape(rep, (cp,), jnp.int32), *_store_shapes(parts)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES // 2, mem


def test_named_scopes_leave_the_v5e_level_program_unchanged(topo,
                                                            monkeypatch):
    """The ``mirage/`` scopes change the op metadata of the compiled
    level program and nothing else of it."""
    from repro.core.level_step import _level_program
    from repro.core.mapreduce import MiningMesh
    from repro.runtime import jax_compat
    from test_tracing import SCOPES, NoScope, code_only

    mesh = MiningMesh(jax_compat.make_mesh((1,), ("w",),
                                           devices=topo.devices[:1]))
    rep = NamedSharding(mesh.mesh, mesh.replicated())
    parts = NamedSharding(mesh.mesh, mesh.spec_parts())
    cp, s, tile_c = 512, 128, 8
    args = (_shape(rep, (), jnp.int32), _shape(rep, (P,), jnp.int32),
            _shape(rep, (cp, 6), jnp.int32),
            _shape(rep, (cp // tile_c, 2), jnp.int32),
            _shape(rep, (cp,), jnp.int32), *_store_shapes(parts))

    def compiled_text():
        fn = _level_program.__wrapped__(
            mesh, 1000, "fused", "reduce_scatter", M, s, True, 1.25, False,
            K, True, True, 8 * 1250)
        return fn.lower(*args).compile().as_text()

    scoped = compiled_text()
    monkeypatch.setattr(jax, "named_scope", lambda name: NoScope())
    plain = compiled_text()
    for scope in SCOPES:
        assert f"mirage/{scope}/" in scoped, scope
    assert "mirage/" not in plain
    assert code_only(scoped) == code_only(plain)


def test_spill_programs_compile_for_v5e(topo):
    """The spill store's three programs (embedding_store="spill") at the
    widths of a 4,110-molecule NCI1 fit at its widest level: 8
    partitions of 514 graphs lane-aligned to 640, M=32, W=16 vertex
    slots, 72 triples of up to 60 occurrences, 43 vertices of up to 12
    neighbours, 4,096 candidates of up to 256 a parent, a 65,536-row
    spill table a partition."""
    from repro.core import mapreduce as mr
    from repro.runtime import jax_compat

    mesh = mr.MiningMesh(jax_compat.make_mesh((1,), ("w",),
                                              devices=topo.devices[:1]))
    rep = NamedSharding(mesh.mesh, mesh.replicated())
    parts = NamedSharding(mesh.mesh, mesh.spec_parts())
    g, m, w, t, f, v, d = 640, 32, 16, 72, 60, 43, 12
    r, kc, cp, s, rows = 65536, 256, 4096, 32, 1024
    store = [_shape(parts, (PP, P, g, m, w), jnp.int32),
             _shape(parts, (PP, P, g, m), jnp.bool_),
             _shape(parts, (PP, t, g, f), jnp.int32),
             _shape(parts, (PP, t, g, f), jnp.int32),
             _shape(parts, (PP, t, g, f), jnp.bool_)]
    table = [_shape(parts, (PP, r, w), jnp.int32),
             _shape(parts, (PP, r), jnp.int32),
             _shape(parts, (PP, g, v, d), jnp.int32),
             _shape(parts, (PP, g, v, d), jnp.int32)]
    counts = [_shape(parts, (PP, s, g, m + 1), jnp.int32),
              _shape(parts, (PP, r + 1, s), jnp.int32),
              _shape(parts, (PP, P, g + 1), jnp.int32),
              _shape(parts, (PP, s, g), jnp.bool_)]
    cmeta = [_shape(rep, (s, 5), jnp.int32), _shape(rep, (), jnp.int32)]
    programs = [
        (mr._spill_support_program(mesh, "fused", True),
         [_shape(rep, (cp, 5), jnp.int32), _shape(rep, (P, kc), jnp.int32),
          _shape(rep, (cp,), jnp.int32), _shape(rep, (rows, 6), jnp.int32),
          _shape(rep, (rows // 8, 2), jnp.int32),
          _shape(rep, (cp,), jnp.int32), *store, *table]),
        (mr._spill_count_program(mesh, m, t),
         [*cmeta, *store[:2], *table]),
        (mr._spill_write_program(mesh, m, w, r),
         [*cmeta, *counts, *store, *table]),
    ]
    for i, (fn, specs) in enumerate(programs):
        compiled = fn.lower(*specs).compile()
        if i == 0:
            assert "tpu_custom_call" in compiled.as_text()
        mem = compiled.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes)
        assert total < HBM_BYTES // 2, (i, mem)
