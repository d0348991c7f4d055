"""Map / shuffle / reduce phases of MIRAGE as shard_map SPMD programs.

One MIRAGE iteration (paper Figs. 7-9) becomes, on a TPU mesh:

  map     — per-device, per-local-partition: fused embedding-join kernel
            over all candidates (kernels/ops.level_supports), summed over
            the device's partitions.  Zero communication: computation
            lives where the data lives.
  shuffle — the dense-key exchange that replaces Hadoop's sort/shuffle:
            the candidate axis is the key space (all devices enumerate the
            identical canonical candidate list), so aggregation is ONE
            collective over a dense int vector:
              * ``psum``            — baseline (paper-faithful reduce)
              * ``reduce_scatter``  — optimized: psum_scatter the support
                vector so each device owns C/W keys (exactly Hadoop's
                "reducer owns a key range"), threshold locally, and
                all_gather the 1-byte verdicts + supports.
  reduce  — threshold + the survivors' child-OL materialization, again
            data-local per partition (pass 2; survivors only).

Why this is the right TPU translation (DESIGN.md §2): a string-keyed
shuffle is a sparse all-to-all — poison on ICI; a dense psum/
reduce-scatter over an agreed key ordering is line-rate.  Agreement costs
nothing because candidate enumeration is deterministic given F_k, which
is globally known at the end of iteration k-1 (the same invariant that
lets Hadoop-MIRAGE read F_k from HDFS).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.bitset import pack_bits, unpack_bits
from ..kernels.ops import (Backend, default_backend, device_local_supports,
                           fused_level_supports, fused_level_supports_packed,
                           is_fused_backend, is_packed_backend)
from ..runtime import jax_compat
from .candgen import schedule_candidates
from .embedding import (materialize_prefix, spill_child_counts,
                        spill_support_rows, spill_write)

__all__ = ["MiningMesh", "map_reduce_supports", "map_materialize",
           "reduce_supports", "worker_imbalance", "spill_supports",
           "spill_counts", "spill_materialize"]


@dataclasses.dataclass(frozen=True)
class MiningMesh:
    """A (possibly multi-axis) mesh with all axes used as workers.

    The paper's "worker" view is 1-D; on a pod the physical mesh is 2-D/3-D
    (("pod",)"data","model").  Mining flattens every axis into the worker
    pool — collectives take the axis-name tuple directly.
    """

    mesh: Mesh

    @property
    def axes(self) -> tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    @property
    def n_workers(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.axes]))

    def spec_parts(self) -> P:
        """Partition-major arrays: shard dim 0 over every mesh axis."""
        return P(self.axes)

    def replicated(self) -> P:
        return P()

    @staticmethod
    def single_device() -> "MiningMesh":
        return MiningMesh(jax_compat.make_mesh((1,), ("w",)))


def worker_imbalance(cost, n_workers: int):
    """max/mean per-worker cost under the blocked partition→worker
    assignment, as a traced jnp scalar (1.0 when the mesh is idle).
    Shared by the level program's rebalance trigger and the device
    loop's per-level stats row so the two report identical signals."""
    per_worker = cost.astype(jnp.float32).reshape(n_workers, -1).sum(-1)
    mean = per_worker.mean()
    return jnp.where(mean > 0, per_worker.max() / mean, jnp.float32(1.0))


def reduce_supports(local_sup, axes, minsup: int, reduce: str, *,
                    gather_gsup: bool = False, packed: bool = False):
    """The shuffle: dense-key aggregation of (C,) local supports.

    With ``gather_gsup`` the support counts are all-gathered alongside
    the verdicts in the reduce_scatter variant — the single-sync level
    program needs the full vector on every device to pack the wire;
    the legacy two-program driver leaves them sharded (the host
    reassembles lazily when reading the output array).

    With ``packed`` the reduce_scatter verdict exchange ships bit-packed
    lanes (DESIGN.md §12): each worker packs its C/W verdict shard into
    ``ceil(C/W/32)`` uint32 words, the all-gather moves words instead of
    int8 lanes (8x smaller payload), and each shard unpacks ragged
    (masking pad bits past its C/W tail) before concatenation — the
    returned verdict vector is bit-identical to the dense exchange.
    """
    if reduce == "psum":
        gsup = jax.lax.psum(local_sup, axes)                      # (C,)
        verdict = (gsup >= minsup).astype(jnp.int8)
    elif reduce == "reduce_scatter":
        # each worker owns a contiguous key shard (C/W keys) —
        # Hadoop's "reducer owns a key range", as one collective.
        # Only the 1-byte verdicts are all-gathered (plus the supports
        # when the caller asks); wire per key:
        # (4+1)·(W-1)/W bytes vs psum's 8·(W-1)/W.
        gsup = jax.lax.psum_scatter(
            local_sup, axes, scatter_dimension=0, tiled=True)      # (C/W,)
        if packed:
            cs = gsup.shape[0]
            words = pack_bits(gsup >= minsup)              # (ceil(cs/32),)
            gathered = jax.lax.all_gather(
                words, axes, axis=0, tiled=True)           # (W·ww,)
            shards = gathered.reshape(-1, words.shape[0])  # (W, ww)
            verdict = unpack_bits(shards, cs).reshape(-1).astype(jnp.int8)
        else:
            v_shard = (gsup >= minsup).astype(jnp.int8)
            verdict = jax.lax.all_gather(v_shard, axes, axis=0, tiled=True)
        if gather_gsup:
            gsup = jax.lax.all_gather(gsup, axes, axis=0, tiled=True)
    else:
        raise ValueError(f"unknown reduce {reduce!r}")
    return gsup, verdict


@functools.lru_cache(maxsize=64)
def _support_program(mmesh: MiningMesh, minsup: int,
                     backend: Optional[Backend], reduce: str):
    """Build (once per static config) the jitted SPMD support round —
    per-level shape changes then hit jit's own cache, not a rebuild."""
    axes = mmesh.axes
    parts = mmesh.spec_parts()
    rep = mmesh.replicated()

    def program(meta, pol, pmask, src, dst, emask):
        local_sup, _local_emb, emb_pp = device_local_supports(
            meta, pol, pmask, src, dst, emask, backend=backend)
        gsup, verdict = reduce_supports(local_sup, axes, minsup, reduce)
        return gsup, verdict, emb_pp

    sup_spec = rep if reduce == "psum" else parts
    # check_vma=False: the varying-axis checker cannot see that a tiled
    # all_gather output is device-invariant; semantics are unchanged.
    return jax.jit(jax_compat.shard_map(
        program, mesh=mmesh.mesh,
        in_specs=(rep, parts, parts, parts, parts, parts),
        out_specs=(sup_spec, rep, parts), check_vma=False))


@functools.lru_cache(maxsize=64)
def _support_program_fused(mmesh: MiningMesh, minsup: int,
                           backend: Backend, reduce: str):
    """Fused map phase: ONE kernel launch per device covers every local
    partition and every candidate tile (no per-partition vmap, no (C, G)
    HBM intermediates).  Inputs are in scheduled (parent-grouped) order;
    the inverse permutation is applied on-device before the collective so
    the shuffle and the caller both see canonical candidate order."""
    axes = mmesh.axes
    parts = mmesh.spec_parts()
    rep = mmesh.replicated()
    interpret = backend.endswith("interpret")
    packed = is_packed_backend(backend)

    def program(sched_meta, tiles, inv, pol, pmask, src, dst, emask):
        if packed:
            sup_pp, emb_pp_s = fused_level_supports_packed(
                sched_meta, tiles, pol, pmask, src, dst, emask,
                interpret=interpret)                # (PP, Cs) scheduled
        else:
            sup_pp, emb_pp_s = fused_level_supports(
                sched_meta, tiles, pol, pmask, src, dst, emask,
                interpret=interpret)                # (PP, Cs) scheduled
        local_sup = jnp.take(sup_pp.sum(0), inv)    # (C,) canonical
        emb_pp = jnp.take(emb_pp_s, inv, axis=1)    # (PP, C) canonical
        gsup, verdict = reduce_supports(local_sup, axes, minsup, reduce)
        return gsup, verdict, emb_pp

    sup_spec = rep if reduce == "psum" else parts
    return jax.jit(jax_compat.shard_map(
        program, mesh=mmesh.mesh,
        in_specs=(rep, rep, rep, parts, parts, parts, parts, parts),
        out_specs=(sup_spec, rep, parts), check_vma=False))


def map_reduce_supports(
    mmesh: MiningMesh,
    meta: np.ndarray,         # (C, 5) host metadata, replicated on device
    pol: jnp.ndarray,         # (NP, P, G, M, K) sharded dim0
    pmask: jnp.ndarray,       # (NP, P, G, M)
    src: jnp.ndarray,         # (NP, T, G, F)
    dst: jnp.ndarray,
    emask: jnp.ndarray,
    *,
    minsup: int,
    backend: Optional[Backend] = None,
    reduce: str = "psum",
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One full map+shuffle+reduce support round.

    Returns (global_support (C,), frequent_verdict (C,), per-partition
    embed counts (NP, C)) as host numpy, in canonical candidate order
    regardless of backend.  The reduce_scatter variant needs the
    candidate axis divisible by the worker count (``psum_scatter`` with
    ``tiled=True`` splits it evenly); when C is not, the metadata is
    transparently padded (the same rows ``mining.py`` pads with) and
    every output is sliced back to C — per-candidate supports are
    independent, so padding rows cannot leak.  The fused backends build the
    parent-grouped tile schedule here, host-side, so ``meta`` must be
    concrete (numpy or committed device array).
    """
    backend = backend or default_backend()
    meta = np.asarray(meta)
    C = meta.shape[0]
    W = mmesh.n_workers
    if reduce == "reduce_scatter" and C % W:
        pad = W - C % W
        meta = np.concatenate(
            [meta, np.tile([[0, 0, 0, 1, 0]], (pad, 1))]).astype(meta.dtype)
    if is_fused_backend(backend):
        sched = schedule_candidates(meta)
        fn = _support_program_fused(mmesh, minsup, backend, reduce)
        gsup, verdict, emb_pp = fn(
            jnp.asarray(sched.meta), jnp.asarray(sched.tiles),
            jnp.asarray(sched.inv), pol, pmask, src, dst, emask)
    else:
        fn = _support_program(mmesh, minsup, backend, reduce)
        gsup, verdict, emb_pp = fn(jnp.asarray(meta), pol, pmask, src,
                                   dst, emask)
    return (np.asarray(gsup)[:C], np.asarray(verdict)[:C],
            np.asarray(emb_pp)[:, :C])


@functools.lru_cache(maxsize=64)
def _materialize_program(mmesh: MiningMesh, max_embeddings: int,
                         out_width: Optional[int]):
    axes = mmesh.axes
    parts = mmesh.spec_parts()
    rep = mmesh.replicated()

    @jax.named_scope("mirage/materialize")
    def program(meta, pol, pmask, src, dst, emask):
        n = meta.shape[0]
        width = out_width if out_width is not None else pol.shape[-1] + 1
        ol, mask, over = materialize_prefix(
            meta, n, pol, pmask, src, dst, emask, n_slots=n,
            max_embeddings=max_embeddings, out_width=width)
        return ol, mask, jax.lax.psum(over, axes)

    return jax.jit(jax_compat.shard_map(
        program, mesh=mmesh.mesh,
        in_specs=(rep, parts, parts, parts, parts, parts),
        out_specs=(parts, parts, rep), check_vma=False))


def map_materialize(
    mmesh: MiningMesh,
    keep_meta: jnp.ndarray,   # (C', 5) replicated — surviving candidates
    pol: jnp.ndarray,         # (NP, P, G, M, K)
    pmask: jnp.ndarray,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    emask: jnp.ndarray,
    *,
    max_embeddings: int,
    out_width: Optional[int] = None,
) -> tuple[jnp.ndarray, jnp.ndarray, int]:
    """Pass 2: build next level's OL store for survivors (data-local; the
    only collective is the overflow-telemetry psum).  ``out_width``
    forwards the bucketed child vertex-slot width (None = exact K+1)."""
    fn = _materialize_program(mmesh, max_embeddings, out_width)
    ol, mask, overflow = fn(keep_meta, pol, pmask, src, dst, emask)
    return ol, mask, int(np.asarray(overflow))


# ---------------------------------------------------------------------------
# The spill store's level (embedding_store="spill", DESIGN.md §15): a
# support round, then a count pass and a write pass of materialization.
# Three programs, two small host syncs — the survivors, and the child
# spill table's size — where the dense store's level is one program.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _spill_support_program(mmesh: MiningMesh, backend: Backend,
                           packed: bool):
    """Global supports over the dense store AND the spill table: the
    dense kernel's local count plus, per candidate, the graphs in which
    a spilled pair of its parent matches (the two hold disjoint pairs),
    psummed over the workers."""
    axes = mmesh.axes
    parts = mmesh.spec_parts()
    rep = mmesh.replicated()
    fused = is_fused_backend(backend)
    interpret = backend.endswith("interpret")

    def program(meta, by_parent, cpos, *args):
        *sched, pol, pmask, src, dst, emask, rows, key, nbr, code = args
        with jax.named_scope("mirage/support_kernel"):
            if fused:
                kernel = (fused_level_supports_packed if packed
                          else fused_level_supports)
                sched_meta, tiles, inv = sched
                sup_pp, _ = kernel(sched_meta, tiles, pol, pmask, src, dst,
                                   emask, interpret=interpret)
                local = jnp.take(sup_pp.sum(0), inv)
            else:
                local, _, _ = device_local_supports(
                    meta, pol, pmask, src, dst, emask, backend=backend,
                    packed=packed)
        with jax.named_scope("mirage/spill_support"):
            extra = jax.lax.map(
                lambda xs: spill_support_rows(
                    meta, by_parent, cpos, *xs, n_graphs=pol.shape[2],
                    n_triples=src.shape[1]),
                (rows, key, nbr, code)).sum(0)
        with jax.named_scope("mirage/reduce"):
            return jax.lax.psum(local + extra, axes)

    n_sched = 3 if fused else 0
    return jax.jit(jax_compat.shard_map(
        program, mesh=mmesh.mesh,
        in_specs=(rep,) * (3 + n_sched) + (parts,) * 9,
        out_specs=rep, check_vma=False))


def spill_supports(mmesh: MiningMesh, meta_p: np.ndarray,
                   sched_args: tuple, by_parent: np.ndarray,
                   cpos: np.ndarray, pol, pmask, src, dst, emask, spill,
                   adjacency, *, backend: Backend, packed: bool):
    """Dispatch the spill store's support round; returns the (Cp,)
    global supports as a device array (not yet fetched).  ``sched_args``
    are the fused schedule's arrays (``level_step.schedule_args``), or
    the metadata itself for the other backends."""
    fused = is_fused_backend(backend)
    fn = _spill_support_program(mmesh, backend, packed)
    return fn(jnp.asarray(meta_p), jnp.asarray(by_parent),
              jnp.asarray(cpos), *(sched_args if fused else ()),
              pol, pmask, src, dst, emask, *spill, *adjacency)


@functools.lru_cache(maxsize=64)
def _spill_count_program(mmesh: MiningMesh, max_embeddings: int,
                         n_triples: int):
    parts = mmesh.spec_parts()
    rep = mmesh.replicated()

    @jax.named_scope("mirage/spill_materialize")
    def program(cmeta, n_fill, pol, pmask, rows, key, nbr, code):
        return jax.lax.map(
            lambda xs: spill_child_counts(
                cmeta, n_fill, *xs, n_triples=n_triples,
                max_embeddings=max_embeddings),
            (pol, pmask, rows, key, nbr, code))

    return jax.jit(jax_compat.shard_map(
        program, mesh=mmesh.mesh, in_specs=(rep, rep) + (parts,) * 6,
        out_specs=(parts,) * 6, check_vma=False))


def spill_counts(mmesh: MiningMesh, cmeta: np.ndarray, n_fill: int, pol,
                 pmask, spill, adjacency, *, max_embeddings: int,
                 n_triples: int):
    """The count pass of a spill level's materialization for the
    survivors ``cmeta`` (S, 5), the first ``n_fill`` real.  Returns the
    device-resident counts for :func:`spill_materialize` and, fetched,
    each partition's child spill rows and pairs."""
    fn = _spill_count_program(mmesh, max_embeddings, n_triples)
    out = fn(jnp.asarray(cmeta), jnp.int32(n_fill), pol, pmask, *spill,
             *adjacency)
    return out[:4], np.asarray(out[4]), np.asarray(out[5])


@functools.lru_cache(maxsize=64)
def _spill_write_program(mmesh: MiningMesh, max_embeddings: int,
                         out_width: int, out_rows: int):
    parts = mmesh.spec_parts()
    rep = mmesh.replicated()

    def program(cmeta, n_fill, cdx, csx, bounds, spilled, pol, pmask, src,
                dst, emask, rows, key, nbr, code):
        with jax.named_scope("mirage/materialize"):
            # a child pair headed for the spill keeps no dense rows
            ol, mask, _ = materialize_prefix(
                cmeta, n_fill, pol, pmask, src, dst, emask,
                n_slots=cmeta.shape[0], max_embeddings=max_embeddings,
                out_width=out_width)
            mask = mask & ~spilled[..., None]
            ol = jnp.where(mask[..., None], ol, -1)
        with jax.named_scope("mirage/spill_materialize"):
            srows, skey = jax.lax.map(
                lambda xs: spill_write(cmeta, *xs, out_rows=out_rows,
                                       out_width=out_width),
                (cdx, csx, bounds, spilled, pol, rows, key, nbr, code))
        return ol, mask, srows, skey

    return jax.jit(jax_compat.shard_map(
        program, mesh=mmesh.mesh, in_specs=(rep, rep) + (parts,) * 13,
        out_specs=(parts,) * 4, check_vma=False))


def spill_materialize(mmesh: MiningMesh, cmeta: np.ndarray, n_fill: int,
                      counts, pol, pmask, src, dst, emask, spill,
                      adjacency, *, max_embeddings: int, out_width: int,
                      out_rows: int):
    """The write pass: the survivors' dense child store (NP, S, G, M, W)
    and mask, and their child spill table (NP, out_rows, W) and keys,
    all device-resident."""
    fn = _spill_write_program(mmesh, max_embeddings, out_width, out_rows)
    return fn(jnp.asarray(cmeta), jnp.int32(n_fill), *counts, pol, pmask,
              src, dst, emask, *spill, *adjacency)
