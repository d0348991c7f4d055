"""Pallas TPU kernel: single-launch fused map phase (join + support).

One ``pallas_call`` covers the whole map-phase compute of a MIRAGE level
on one device — join *and* per-candidate reduction — replacing the seed
two-launch pipeline (``embedding_join`` then ``support_count``) that
round-tripped two full ``(C, G)`` int32 tensors through HBM between
launches.  See DESIGN.md §5-6 for the traffic argument.

Grid: ``(PP, NT, G/TG)`` with the graph axis innermost.  ``PP`` is the
device-local partition count, ``NT`` the candidate-*tile* count.  Each
grid step loads one graph tile of one partition and joins it against a
block of ``TC = tile_c`` candidates; the per-candidate output block is
revisited across the G sweep and accumulated in place (the canonical
Pallas revisited-output reduction), so per-graph intermediates never
leave VMEM.

Feeding contract (``core/candgen.schedule_candidates``): candidates are
parent-grouped — every TC-row block shares one ``(parent, triple)`` pair,
recorded in the scalar-prefetched block-descriptor table ``tiles``.  The
data-dependent BlockSpec index maps stream the block's shared parent-OL
and edge-OL tiles from HBM **once per block** instead of once per
candidate (the seed kernel's grid was per-candidate).  Padded rows carry
``valid=0`` in meta column 5 and contribute zero.

TPU layout.  Mosaic tiles the last two dims of every block by (8, 128),
so the kernel puts the graph axis on the 128 lanes: it reads the stores
graph-minor — ``pol`` as ``(PP, P, M·K, G)``, ``pmask`` ``(PP, P, M, G)``
and the edge store ``(PP, T, F, G)`` — and the join is dense ``(M, TG)``
VPU work, with no lane padding of the small K and F axes.  The HBM store
layout does not change: XLA's default TPU layout for these arrays
already keeps G minor-most, so the transposes in ``_call`` are layout
bitcasts, not copies.  A stride-K sublane read splits the ``(M·K, TG)``
OL slab into one ``(M, TG)`` plane per vertex slot.  G needs no padding:
the last graph tile may overhang the array and its lanes past G are
masked in the kernel.  The schedule tables are flattened to 1-D for
SMEM, and the outputs are ``(TCp, 128)`` blocks (candidate rows,
``TCp = TC`` rounded up to 8) that the wrapper slices back to the
``(PP, Cs)`` contract.

Shapes (one device):
  sched_meta (Cs, 6) int32  [parent, stub, to, fwd, triple, valid]
  tiles      (NT, 2) int32  [parent, triple] per candidate block
  pol        (PP, P, G, M, K) int32   stacked parent OLs, PAD = -1
  pmask      (PP, P, G, M)    int8    embedding validity
  src/dst    (PP, T, G, F)    int32   edge-OL endpoints
  emask      (PP, T, G, F)    int8

Outputs (scheduled candidate order — gather with ``schedule.inv`` to
restore canonical order):
  sup (PP, Cs) int32 — per-partition local support
  emb (PP, Cs) int32 — per-partition embedding count (cost signal)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitset import WORD, popcount

__all__ = ["fused_level_pallas", "fused_level_packed_pallas",
           "graph_tile", "DEFAULT_TILE_C", "LANES"]

DEFAULT_TILE_C = 8
LANES = 128
_META_W = 6


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_join(meta_ref, ct, g, tile_c, n_graphs, pol_ref, pmask_ref, src_ref,
               dst_ref, emask_ref, pk_ref, em_ref, stub_ref, to_ref):
    """Join graph tile ``g`` against the ``tile_c`` candidates of tile
    ``ct``.  Returns per candidate ``(hit, emb, valid)``: ``hit`` (1, TG)
    int32 is 1 where the graph holds >= 1 match, ``emb`` (1, TG) int32
    the match count per graph, ``valid`` the meta valid flag.  Shared by
    the dense and packed kernels so the join semantics cannot diverge
    between the two backends."""
    mk, tg = pol_ref.shape[-2:]
    m = pmask_ref.shape[-2]
    k = mk // m
    f_dim = src_ref.shape[-2]

    # vertex slot kk of embedding row r sits at slab row r*K + kk: a
    # stride-K sublane read gathers the (M, TG) plane of slot kk
    for kk in range(k):
        pk_ref[kk] = pol_ref[0, 0, pl.ds(kk, m, stride=k), :]
    em_ref[...] = emask_ref[0, 0].astype(jnp.int32)            # (F, TG)
    # lanes past the last graph (an overhanging final tile) hold
    # whatever VMEM held: mask them out of every match
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tg), 1)
    live = g * tg + lane < n_graphs
    pm = (pmask_ref[0, 0].astype(jnp.int32) != 0) & live       # (M, TG)

    rows = [(ct * tile_c + i) * _META_W for i in range(tile_c)]
    for i, row in enumerate(rows):
        stub = meta_ref[row + 1]
        to = meta_ref[row + 2]
        sv = jnp.zeros((m, tg), jnp.int32)
        tv = jnp.zeros((m, tg), jnp.int32)
        for kk in range(k):                  # one-hot select, as the ref
            sv = sv + jnp.where(stub == kk, pk_ref[kk], 0)
            tv = tv + jnp.where(to == kk, pk_ref[kk], 0)
        stub_ref[i] = sv
        to_ref[i] = tv

    def body(f, acc):
        s = src_ref[0, 0, pl.ds(f, 1), :]                      # (1, TG)
        d = dst_ref[0, 0, pl.ds(f, 1), :]
        base = pm & (em_ref[pl.ds(f, 1), :] != 0)             # (M, TG)
        # forward-edge membership test (new endpoint must not be a
        # parent vertex) depends only on (pol, dst) — computed once per
        # occurrence and shared by all tile_c candidates.  Bucket-padded
        # K slots hold PAD (-1) and never match a real endpoint.
        member = pk_ref[0] == d
        for kk in range(1, k):
            member = member | (pk_ref[kk] == d)
        fresh = (~member).astype(jnp.int32)   # int32: Mosaic selects no i1
        out = []
        for i, row in enumerate(rows):
            fwd = meta_ref[row + 3] != 0
            ext = jnp.where(fwd, fresh, (to_ref[i] == d).astype(jnp.int32))
            ok = (base & (stub_ref[i] == s)).astype(jnp.int32) * ext
            out.append(jnp.maximum(acc[2 * i],
                                   jnp.max(ok, axis=0, keepdims=True)))
            out.append(acc[2 * i + 1] + jnp.sum(ok, axis=0, keepdims=True))
        return tuple(out)

    zero = jnp.zeros((1, tg), jnp.int32)
    acc = jax.lax.fori_loop(0, f_dim, body, (zero,) * (2 * tile_c))
    return [(acc[2 * i], acc[2 * i + 1], meta_ref[row + 5])
            for i, row in enumerate(rows)]


def _tile_valid(meta_ref, ct, tile_c):
    # Shape bucketing pads the schedule with whole valid=0 tiles
    # (descriptor (0, 0)); their output blocks stay at the init zeros,
    # so the entire join is skipped, not just masked — the bucket tail
    # costs HBM streaming of one (already-resident) tile index, no VPU.
    v = meta_ref[ct * tile_c * _META_W + 5]
    for i in range(1, tile_c):   # static unroll — TC is a compile constant
        v = v | meta_ref[(ct * tile_c + i) * _META_W + 5]
    return v


def _rows(values, tcp, width):
    """Stack per-candidate (1, W) rows into a (TCp, W) block, row i =
    candidate i, zero rows past tile_c."""
    rid = jax.lax.broadcasted_iota(jnp.int32, (tcp, width), 0)
    out = jnp.zeros((tcp, width), values[0].dtype)
    for i, v in enumerate(values):
        out = jnp.where(rid == i, v, out)
    return out


def _lanes(values):
    """Per-candidate (1, 1) scalars into one (1, 128) row, lane i =
    candidate i."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    out = jnp.zeros((1, LANES), jnp.int32)
    for i, v in enumerate(values):
        out = jnp.where(lane == i, v, out)
    return out


def _init_block(sup_ref, emb_ref, ct, g):
    """Zero an output block before its first use.  A block holds 8
    consecutive candidate tiles (row ct % 8, lane i = candidate i of the
    tile); the grid visits them back to back, so the block is zeroed at
    its first tile's first graph tile."""
    @pl.when((g == 0) & (ct % 8 == 0))
    def _init():
        sup_ref[...] = jnp.zeros_like(sup_ref)
        emb_ref[...] = jnp.zeros_like(emb_ref)


def _add_row(sup_ref, emb_ref, ct, sups, embs):
    """Accumulate one step's per-candidate counts into row ct % 8."""
    row = pl.ds(ct % 8, 1)
    sup_ref[0, 0, row, :] += _lanes(sups)
    emb_ref[0, 0, row, :] += _lanes(embs)


def _fused_kernel(meta_ref, tiles_ref, pol_ref, pmask_ref, src_ref, dst_ref,
                  emask_ref, sup_ref, emb_ref, *scratch, tile_c, n_graphs):
    ct = pl.program_id(1)
    g = pl.program_id(2)
    _init_block(sup_ref, emb_ref, ct, g)

    @pl.when(_tile_valid(meta_ref, ct, tile_c) != 0)
    def _compute():
        sups, embs = [], []
        for hit, emb, valid in _tile_join(meta_ref, ct, g, tile_c, n_graphs,
                                          pol_ref, pmask_ref, src_ref,
                                          dst_ref, emask_ref, *scratch):
            sups.append(jnp.sum(hit, axis=1, keepdims=True) * valid)
            embs.append(jnp.sum(emb, axis=1, keepdims=True) * valid)
        _add_row(sup_ref, emb_ref, ct, sups, embs)


def _pack_words(bits, n_words):
    """(TCp, TG) 0/1 int32 -> (TCp, n_words) uint32, LSB-first (the
    ``bitset`` layout).  The lane-to-word reduction is one matmul per
    16-bit half: every product is 0 or a power of two below 2^16, all
    exact in bf16, and f32 sums them exactly."""
    tg = bits.shape[-1]
    gi = jax.lax.broadcasted_iota(jnp.int32, (tg, n_words), 0)
    wi = jax.lax.broadcasted_iota(jnp.int32, (tg, n_words), 1)
    bit = gi % WORD
    mine = gi // WORD == wi
    b = bits.astype(jnp.float32).astype(jnp.bfloat16)

    def half(lo):
        sel = mine & ((bit < 16) if lo else (bit >= 16))
        w = jnp.where(sel, jnp.left_shift(1, bit % 16), 0)
        w = w.astype(jnp.float32).astype(jnp.bfloat16)
        return jnp.dot(b, w, preferred_element_type=jnp.float32
                       ).astype(jnp.int32)

    return (half(True) | (half(False) << 16)).astype(jnp.uint32)


def _fused_packed_kernel(meta_ref, tiles_ref, gmask_ref, pol_ref, pmask_ref,
                         src_ref, dst_ref, emask_ref, sup_ref, emb_ref,
                         *scratch, tile_c, n_graphs):
    """Packed twin of ``_fused_kernel`` (DESIGN.md §12).

    Each candidate's per-graph any-match lanes pack to uint32 words in
    VMEM, lane-AND with the valid-graph mask ``gmask`` (ragged G%32
    tail + partition padding), and local support is their popcount per
    ``tile_c`` block.  The words never leave VMEM: a ``(tile_c,
    TG/32)`` block would be lane-padded 32x in HBM.
    """
    ct = pl.program_id(1)
    g = pl.program_id(2)
    _init_block(sup_ref, emb_ref, ct, g)

    @pl.when(_tile_valid(meta_ref, ct, tile_c) != 0)
    def _compute():
        hits, embs = [], []
        for hit, emb, valid in _tile_join(meta_ref, ct, g, tile_c, n_graphs,
                                          pol_ref, pmask_ref, src_ref,
                                          dst_ref, emask_ref, *scratch):
            hits.append(hit * (valid != 0).astype(jnp.int32))
            embs.append(jnp.sum(emb, axis=1, keepdims=True) * valid)
        tcp = _round_up(tile_c, 8)
        words = _pack_words(_rows(hits, tcp, hits[0].shape[-1]),
                            gmask_ref.shape[-1])
        words = words & gmask_ref[0]                           # lane-AND
        counts = popcount(words)                               # popcount
        sups = [jnp.sum(counts[i:i + 1], axis=1, keepdims=True)
                for i in range(tile_c)]
        _add_row(sup_ref, emb_ref, ct, sups, embs)


def graph_tile(tile_g: int, n_graphs: int) -> int:
    """The kernel's graph tile: ``tile_g`` rounded up to the 128 lanes,
    or the whole graph axis when that is smaller (a block equal to the
    array dim is always legal)."""
    tg = _round_up(tile_g, LANES)
    return n_graphs if n_graphs <= tg else tg


def _call(kernel, sched_meta, tiles, pol, pmask, src, dst, emask, *,
          tile_g, interpret, gmask=None):
    """Shared pallas_call plumbing: graph-minor operand views, grid,
    block specs, VMEM scratch, and the output slicing back to
    ``(PP, Cs)`` scheduled order."""
    Cs = sched_meta.shape[0]
    NT = tiles.shape[0]
    tile_c = Cs // NT
    if Cs != NT * tile_c:
        raise ValueError(f"Cs={Cs} not a multiple of NT={NT}")
    if tile_c > LANES:
        raise ValueError(f"tile_c={tile_c} exceeds {LANES} lanes")
    PP, P, G, M, K = pol.shape
    _, T, _, F = src.shape
    tg = graph_tile(tile_g, G)
    n_g = pl.cdiv(G, tg)
    n_blk = pl.cdiv(NT, 8)

    # graph-minor views; on TPU these match the stores' default HBM
    # layout, so XLA lowers them to bitcasts
    pol_t = jnp.transpose(pol, (0, 1, 3, 4, 2)).reshape(PP, P, M * K, G)
    pmask_t = jnp.swapaxes(pmask.astype(jnp.int8), 2, 3)
    src_t, dst_t = jnp.swapaxes(src, 2, 3), jnp.swapaxes(dst, 2, 3)
    emask_t = jnp.swapaxes(emask.astype(jnp.int8), 2, 3)

    def par(pp, ct, g, meta, tl):
        return (pp, tl[2 * ct], 0, g)

    def tri(pp, ct, g, meta, tl):
        return (pp, tl[2 * ct + 1], 0, g)

    in_specs = [pl.BlockSpec((1, 1, M * K, tg), par),
                pl.BlockSpec((1, 1, M, tg), par),
                pl.BlockSpec((1, 1, F, tg), tri),
                pl.BlockSpec((1, 1, F, tg), tri),
                pl.BlockSpec((1, 1, F, tg), tri)]
    acc_spec = pl.BlockSpec((1, 1, 8, LANES),
                            lambda pp, ct, g, meta, tl: (pp, ct // 8, 0, 0))
    out_specs = [acc_spec, acc_spec]
    out_shape = [jax.ShapeDtypeStruct((PP, n_blk, 8, LANES), jnp.int32)] * 2
    args = [pol_t, pmask_t, src_t, dst_t, emask_t]
    params = dict(tile_c=tile_c, n_graphs=G)
    if gmask is not None:
        tgw = -(-tg // WORD)
        if gmask.shape != (n_g * tgw,):
            raise ValueError(
                f"gmask shape {gmask.shape} != ({n_g * tgw},) words")
        in_specs.insert(0, pl.BlockSpec(
            (1, 1, tgw), lambda pp, ct, g, meta, tl: (g, 0, 0)))
        args.insert(0, gmask.reshape(n_g, 1, tgw))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(PP, NT, n_g),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((K, M, tg), jnp.int32),          # per-slot planes
            pltpu.VMEM((F, tg), jnp.int32),             # emask as int32
            pltpu.VMEM((tile_c, M, tg), jnp.int32),     # stub values
            pltpu.VMEM((tile_c, M, tg), jnp.int32),     # to values
        ],
    )
    outs = pl.pallas_call(
        functools.partial(kernel, **params),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            # the output block is revisited across consecutive tiles
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(sched_meta.reshape(-1), tiles.reshape(-1), *args)
    sup, emb = (o[..., :tile_c].reshape(PP, n_blk * 8, tile_c)[:, :NT]
                .reshape(PP, Cs) for o in outs)
    return sup, emb


@functools.partial(jax.jit, static_argnames=("tile_g", "interpret"))
def fused_level_pallas(
    sched_meta: jnp.ndarray,   # (Cs, 6) int32, Cs = NT * tile_c
    tiles: jnp.ndarray,        # (NT, 2) int32
    pol: jnp.ndarray,          # (PP, P, G, M, K) int32
    pmask: jnp.ndarray,        # (PP, P, G, M) int8/bool
    src: jnp.ndarray,          # (PP, T, G, F) int32
    dst: jnp.ndarray,          # (PP, T, G, F) int32
    emask: jnp.ndarray,        # (PP, T, G, F) int8/bool
    *,
    tile_g: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-launch level supports over ``graph_tile(tile_g, G)``-wide
    graph tiles (any G: the last tile may overhang); ``tile_c`` (at most
    128) is implied by the schedule (Cs / NT)."""
    return _call(_fused_kernel, sched_meta, tiles, pol, pmask, src, dst,
                 emask, tile_g=tile_g, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile_g", "interpret"))
def fused_level_packed_pallas(
    sched_meta: jnp.ndarray,   # (Cs, 6) int32, Cs = NT * tile_c
    tiles: jnp.ndarray,        # (NT, 2) int32
    gmask: jnp.ndarray,        # (n_g·ceil(TG/32),) uint32 valid-graph bits
    pol: jnp.ndarray,          # (PP, P, G, M, K) int32
    pmask: jnp.ndarray,        # (PP, P, G, M) int8/bool
    src: jnp.ndarray,          # (PP, T, G, F) int32
    dst: jnp.ndarray,          # (PP, T, G, F) int32
    emask: jnp.ndarray,        # (PP, T, G, F) int8/bool
    *,
    tile_g: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Packed single-launch level supports (DESIGN.md §12).

    Same grid, feeding contract and outputs as
    :func:`fused_level_pallas`; each graph tile packs to
    ``ceil(TG/32)`` uint32 words in VMEM, and ``gmask`` holds those
    words' valid-graph bits for every tile.  ``sup`` is the popcount of
    the masked words, summed over graph tiles.
    """
    return _call(_fused_packed_kernel, sched_meta, tiles, pol, pmask, src,
                 dst, emask, tile_g=tile_g, interpret=interpret,
                 gmask=gmask)
