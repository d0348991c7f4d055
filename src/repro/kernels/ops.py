"""Jit'd dispatch wrappers around the mining kernels.

``backend`` selection:
  "ref"             pure-jnp (XLA) — default on CPU, also the test oracle
  "fused"           single-launch fused Pallas map phase — production TPU
                    path (join + per-candidate reduction in one kernel,
                    parent-grouped candidate schedule; DESIGN.md §5-6)
  "fused_interpret" the fused kernel in interpret mode — CPU validation
  "fused_packed"    the fused kernel with bit-packed verdict bitsets —
                    the per-graph accumulator is ceil(G/32) uint32 words
                    in VMEM and support counting is AND+popcount
                    (DESIGN.md §12); bit-identical to "fused"
  "fused_packed_interpret"  the packed kernel in interpret mode
  "pallas"          legacy two-launch Pallas pipeline (join kernel, (C,G)
                    HBM intermediates, then reduce kernel) — kept as the
                    on-device oracle/fallback for the fused path
  "interpret"       the two-launch pipeline in interpret mode

The wrappers own the padding contract: G is padded to the graph tile and
C to the candidate tile with masked-off rows, so kernel callers never see
alignment requirements.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from .bitset import n_words, tail_mask
from .embedding_join import DEFAULT_TILE_G, embedding_join_pallas
from .fused_level import (DEFAULT_TILE_C, fused_level_packed_pallas,
                          fused_level_pallas, graph_tile)
from .ref import embedding_join_ref, support_count_ref
from .support_count import support_count_pallas

Backend = Literal["ref", "pallas", "interpret", "fused", "fused_interpret",
                  "fused_packed", "fused_packed_interpret"]

__all__ = ["level_supports", "fused_level_supports",
           "fused_level_supports_packed", "device_local_supports",
           "default_backend", "is_fused_backend", "is_packed_backend"]


def default_backend() -> Backend:
    return "fused" if jax.default_backend() == "tpu" else "ref"


def is_fused_backend(backend: Backend | None) -> bool:
    return (backend or default_backend()) in (
        "fused", "fused_interpret", "fused_packed", "fused_packed_interpret")


def is_packed_backend(backend: Backend | None) -> bool:
    return (backend or default_backend()) in (
        "fused_packed", "fused_packed_interpret")


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def fused_level_supports(
    sched_meta: jnp.ndarray,   # (Cs, 6) int32 — schedule_candidates output
    tiles: jnp.ndarray,        # (NT, 2) int32 block descriptors
    pol: jnp.ndarray,          # (PP, P, G, M, K) int32
    pmask: jnp.ndarray,        # (PP, P, G, M) bool/int8
    src: jnp.ndarray,          # (PP, T, G, F) int32
    dst: jnp.ndarray,
    emask: jnp.ndarray,
    *,
    tile_g: int = DEFAULT_TILE_G,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(partition, scheduled-candidate) (support, embed_count) in ONE
    kernel launch covering every device-local partition.

    Outputs are in scheduled order — gather with ``schedule.inv`` for
    canonical order.  The graph axis needs no padding: the kernel masks
    the lanes of an overhanging last graph tile.
    """
    return fused_level_pallas(sched_meta, tiles, pol, pmask, src, dst,
                              emask, tile_g=tile_g, interpret=interpret)


def fused_level_supports_packed(
    sched_meta: jnp.ndarray,   # (Cs, 6) int32 — schedule_candidates output
    tiles: jnp.ndarray,        # (NT, 2) int32 block descriptors
    pol: jnp.ndarray,          # (PP, P, G, M, K) int32
    pmask: jnp.ndarray,        # (PP, P, G, M) bool/int8
    src: jnp.ndarray,          # (PP, T, G, F) int32
    dst: jnp.ndarray,
    emask: jnp.ndarray,
    *,
    tile_g: int = DEFAULT_TILE_G,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Packed twin of :func:`fused_level_supports` (DESIGN.md §12).

    Builds the valid-graph bit mask: each graph tile packs to whole
    uint32 words, and ``gmask`` zeroes the bits past G (padded graphs
    and overhanging lanes also carry no match — the lane-AND is the
    second line of defence that makes the bitset contract local).
    Returns ``(sup, emb)`` in scheduled order, as the dense kernel.
    """
    G = pol.shape[2]
    tg = graph_tile(tile_g, G)
    words = -(-G // tg) * n_words(tg)
    gmask = jnp.asarray(tail_mask(G, words=words))
    return fused_level_packed_pallas(sched_meta, tiles, gmask, pol, pmask,
                                     src, dst, emask, tile_g=tile_g,
                                     interpret=interpret)


def device_local_supports(
    meta: jnp.ndarray,     # (C, 5) int32 — replicated candidate metadata
    pol: jnp.ndarray,      # (PP, P, G, M, K) — device-local partitions
    pmask: jnp.ndarray,    # (PP, P, G, M)
    src: jnp.ndarray,      # (PP, T, G, F)
    dst: jnp.ndarray,
    emask: jnp.ndarray,
    *,
    backend: Backend | None = None,
    packed: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Map phase on one device: the per-candidate join vmapped over the
    device-local partition stack.  Returns the summed (C,) local support
    and embed count plus the per-partition (PP, C) embed counts (the
    straggler-rebalance cost signal).  Non-fused backends only — the
    fused kernel covers the partition axis in its own grid
    (``fused_level_supports``).

    ``packed=True`` routes the "ref" backend through the bitset-shaped
    oracle (``embedding.support_bits_ref``: per-graph verdicts pack to
    uint32 words, support = AND+popcount) — bit-identical by
    construction, so the packed pipeline stays exercised on CPU where
    the default backend is "ref".  The two-launch Pallas backends stay
    dense (they are the oracle for the fused path)."""
    if packed and (backend or default_backend()) == "ref":
        from ..core.embedding import support_bits_ref

        sup_pp, emb_pp = jax.vmap(
            lambda a, b, c, d, e: support_bits_ref(
                meta, a, b, c, d, e)[:2]
        )(pol, pmask, src, dst, emask)
        return sup_pp.sum(0), emb_pp.sum(0), emb_pp
    sup_pp, emb_pp = jax.vmap(
        lambda a, b, c, d, e: level_supports(
            meta, a, b, c, d, e, backend=backend)
    )(pol, pmask, src, dst, emask)
    return sup_pp.sum(0), emb_pp.sum(0), emb_pp


def level_supports(
    meta: jnp.ndarray,     # (C, 5) int32
    pol: jnp.ndarray,      # (P, G, M, K) int32
    pmask: jnp.ndarray,    # (P, G, M) bool/int8
    src: jnp.ndarray,      # (T, G, F) int32
    dst: jnp.ndarray,
    emask: jnp.ndarray,
    *,
    backend: Backend | None = None,
    tile_g: int = DEFAULT_TILE_G,
    tile_c: int = DEFAULT_TILE_C,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-candidate (local_support, embed_count) for one level.

    This is the whole map-phase compute of a MIRAGE iteration on one
    partition: join + reduce, fused across all candidates.  The fused
    backends build the parent-grouped schedule host-side, so ``meta``
    must be concrete (not a tracer) for them — the distributed driver
    (`core/mapreduce.py`) schedules once per level and calls
    ``fused_level_supports`` directly instead.
    """
    backend = backend or default_backend()
    C = meta.shape[0]
    G = pol.shape[1]

    if backend == "ref":
        matched, count = embedding_join_ref(meta, pol, pmask, src, dst, emask)
        return support_count_ref(matched, count)

    if is_fused_backend(backend):
        from ..core.candgen import schedule_candidates
        sched = schedule_candidates(np.asarray(meta), tile_c)
        interpret = backend.endswith("interpret")
        if is_packed_backend(backend):
            sup, emb = fused_level_supports_packed(
                jnp.asarray(sched.meta), jnp.asarray(sched.tiles),
                pol[None], pmask[None], src[None], dst[None], emask[None],
                tile_g=tile_g, interpret=interpret)
        else:
            sup, emb = fused_level_supports(
                jnp.asarray(sched.meta), jnp.asarray(sched.tiles),
                pol[None], pmask[None], src[None], dst[None], emask[None],
                tile_g=tile_g, interpret=interpret)
        inv = jnp.asarray(sched.inv)
        return jnp.take(sup[0], inv), jnp.take(emb[0], inv)

    interpret = backend == "interpret"
    # pad graphs axis; padded graphs carry zero masks -> no contribution
    tg = min(tile_g, _round_up(G, 8))
    polp = _pad_to(pol, 1, tg, value=-1)
    pmaskp = _pad_to(pmask.astype(jnp.int8), 1, tg)
    srcp = _pad_to(src, 1, tg, value=-1)
    dstp = _pad_to(dst, 1, tg, value=-1)
    emaskp = _pad_to(emask.astype(jnp.int8), 1, tg)

    matched, count = embedding_join_pallas(
        meta, polp, pmaskp, srcp, dstp, emaskp,
        tile_g=tg, interpret=interpret)

    tc = min(tile_c, C) or 1
    matchedp = _pad_to(matched, 0, tc)
    countp = _pad_to(count, 0, tc)
    sup, emb = support_count_pallas(matchedp, countp, tile_c=tc,
                                    tile_g=tg, interpret=interpret)
    return sup[:C], emb[:C]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
