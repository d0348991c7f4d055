"""Single-sync device-resident level program (DESIGN.md §8).

The PR-1 driver still crossed the host↔device boundary several times per
mining level: fetch the support vector, build a Python ``keep`` list,
re-upload the survivor metadata, loop the escalation valve from host
control flow, and detour through the host to compute the LPT straggler
repack from the embed-count signal.  Each crossing is a device sync — the
iterative-MapReduce overhead the paper identifies (§IV-B) surviving in
miniature as dispatch latency.

This module fuses the whole per-level dataflow into ONE jitted program:

  1. pass-1 support counting   (fused single-launch kernel, or the
                                vmapped ref/pallas backends, per device)
  2. dense-collective threshold (psum | reduce_scatter — the shuffle)
  3. survivor compaction        (verdict-masked prefix-sum rank, one
                                scatter; survivor metadata gathered to
                                the front, padded to a static cap S)
  4. pass-2 materialization     (child OLs for the S compact slots,
                                data-local per partition)
  5. straggler repack           (per-partition embed-cost → on-device
                                LPT permutation + trigger decision; the
                                permutation rides home in the wire and,
                                when it fired, ``permute_stores`` gathers
                                the OL + edge-OL stores into the new
                                layout in a separate cached device
                                program — no host detour, and the rare
                                all-to-all doesn't tax every level's
                                compile)

The host receives exactly ONE device→host transfer per level: the packed
int32 *wire*.  The wire comes in two layouts (DESIGN.md §11):

**Dense** (``psum``, or ``sharded_wire=False``) — one replicated vector:

  [0:Cp]      global support per (padded) candidate
  [Cp+0]      true survivor count (may exceed the cap S — driver retries)
  [Cp+1]      overflow (matches dropped by the M cap, survivors only)
  [Cp+2]      rebalanced flag (0/1)
  [Cp+3]      imbalance, 16.16 fixed point
  [Cp+4]      audit word — device-side invariant check bit flags
              (DESIGN.md §14; 0 = every check passed)
  [Cp+5:-1]   the (NP,) partition permutation that was applied
  [-1]        checksum word over everything before it (DESIGN.md §10)

**Sharded** (``reduce_scatter``; the default single-sync layout) — the
wire itself is sharded over the W workers.  The support vector is never
all-gathered on device: the ``psum_scatter`` output stays put and each
worker packs (and transfers to the host) only its own C/W key slice,
plus a replicated copy of the scalar words and permutation and its own
shard checksum:

  worker w's shard (length Cp/W + 5 + NP + 1):
    [0:Cp/W]  global support for keys [w·Cp/W, (w+1)·Cp/W)
    [...]     n_keep | overflow | rebalanced | imbalance | audit | perm
              | checksum

The host reassembles the canonical (Cp,) support vector by concatenating
the verified shards (blocked dim-0 sharding ⇒ device order is key
order) and reads the scalar words from shard 0.

**Packed** (DESIGN.md §12; orthogonal to dense/sharded, default for
single-sync): either layout's gsup slice ships two uint16 supports per
int32 word — the checksum covers the packed words, and
``reassemble_wire`` expands the slice back to int32 only after
verification, so ``unpack_wire`` sees an identical body.  Upstream of
the wire, ``packed`` also selects the bitset kernel (verdict bitsets in
VMEM, AND+popcount support counting) and bit-packed verdict lanes in
the reduce_scatter shuffle.  Per level this removes
the (W-1)/W·Cp·4B support all-gather from the collective phase (fig19's
~40% wire cut) AND shrinks each worker's device→host transfer from the
full wire to its 1/W slice — the per-iteration host traffic DIMSpan
(arXiv 1703.01910) identifies as the distributed-FSM killer.

From either layout the host derives everything else (frequent verdicts,
survivor ids, escalation and rebalance bookkeeping).  Checksums are
computed on device and re-computed host-side per shard before any field
is decoded: a corrupted transfer triggers a bounded re-fetch from the
(pristine) device buffer, then a ``WireIntegrityError`` — never
silently wrong supports.

``dispatch_level`` / :class:`PendingLevel` split the level into an
asynchronous dispatch and the blocking wire sync, so the driver can run
the next level's host candidate generation in the shadow of the
in-flight device program (the overlap state machine, DESIGN.md §11).

Exceptional paths — the escalation valve (overflow > 0) and a survivor-
cap miss (n_keep > S) — fall back to the cheap materialize-only program
from the *preserved* inputs (the wire's pass-1 supports stay valid); they
cost extra syncs only when they fire.  Because such a retry consumes the
parent OL store again, its buffers are donated only when no retry is
possible: escalation disabled or M already at its ceiling, and S
covering the full real candidate set (S >= C rules a cap miss out).

Shape bucketing (``core/buckets.py``, DESIGN.md §9): the program is
cached per STATIC config only — the true candidate count ``c_real``
rides in as a traced scalar, so consecutive levels whose bucketed
shapes (Cp, S, M, K, schedule rows) coincide reuse one compiled
program instead of paying a fresh XLA compile per level.  The driver
passes ``child_width`` (the bucketed child vertex-slot width; None
reproduces the exact K+1 growth) and ``sched_floor`` (the fused
schedule's row-bucket floor).  When bucketed shapes repeat, the donated
parent store has exactly the child store's shape and XLA aliases the
buffers — the donation arena — rather than merely freeing them at
program exit; ``permute_stores`` aliases unconditionally (its outputs
always match its inputs).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from ..kernels.ops import (Backend, device_local_supports,
                           fused_level_supports,
                           fused_level_supports_packed, is_fused_backend)
from ..runtime import faults, jax_compat, tracing
from .embedding import materialize_prefix
from .mapreduce import MiningMesh, reduce_supports, worker_imbalance

__all__ = ["LevelWire", "LevelOutputs", "PendingLevel", "dispatch_level",
           "run_level", "unpack_wire", "reassemble_wire", "wire_words",
           "wire_cost_model", "lpt_permutation", "wire_checksum",
           "fetch_wire", "schedule_args", "AUDIT_MONOTONIC", "AUDIT_COMPACT",
           "AUDIT_RANGE", "AUDIT_NKEEP"]

_IMBAL_FX = 1 << 16

# wire scalar words per shard: n_keep | overflow | rebalanced |
# imbalance | audit (DESIGN.md §14)
_N_SCALARS = 5

# audit-word bit flags (device-side invariant checks, 0 = clean)
AUDIT_MONOTONIC = 1     # child support exceeds its parent's support
AUDIT_COMPACT = 2       # a valid compact slot holds a non-survivor
AUDIT_RANGE = 4         # support negative or above the DB graph count
AUDIT_NKEEP = 8         # survivor count exceeds the real candidate count

# Fibonacci / murmur-style 32-bit odd mixing constants.  The checksum is
# a position-salted multiplicative sum: word i contributes
# (w_i ^ i*PHI32) * MIX, all in wrapping uint32, so both a flipped bit
# anywhere and two swapped words change the sum.  The final >> 1 makes
# the value fit int32 exactly, letting it ride the int32 wire itself.
_CSUM_SALT = 0x9E3779B1
_CSUM_MIX = 0x85EBCA77

_WIRE_FETCH_ATTEMPTS = 3


def wire_checksum(wire):
    """Checksum word for a packed int32 wire (all words but the last).

    Pure wrapping-uint32 arithmetic so the device (jnp, inside the level
    program) and the host (np, before decoding) compute bit-identical
    values."""
    xp = jnp if isinstance(wire, jax.Array) else np
    u = wire.astype(xp.uint32)
    idx = xp.arange(u.shape[0], dtype=xp.uint32)
    mixed = (u ^ (idx * xp.uint32(_CSUM_SALT))) * xp.uint32(_CSUM_MIX)
    return (mixed.sum(dtype=xp.uint32) >> xp.uint32(1)).astype(xp.int32)


def wire_words(cp: int, n_partitions: int, n_shards: int = 1,
               packed: bool = False) -> int:
    """Total int32 words of the packed wire: ``n_shards`` shards of
    [gsup slice | 5 scalars | perm | checksum].  ``n_shards=1`` is the
    dense layout.  With ``packed`` (DESIGN.md §12) each shard's gsup
    slice ships two uint16 supports per int32 word — ``ceil(cs/2)``
    words for a ``cs``-support slice."""
    if cp % n_shards:
        raise ValueError(f"Cp={cp} not divisible into {n_shards} shards")
    cs = cp // n_shards
    gw = -(-cs // 2) if packed else cs
    return n_shards * (gw + _N_SCALARS + n_partitions + 1)


def reassemble_wire(host: np.ndarray, n_partitions: int,
                    n_shards: int = 1, *, packed: bool = False,
                    cp: Optional[int] = None) -> Optional[np.ndarray]:
    """Verify a fetched wire's per-shard checksums and reassemble the
    dense body ``[gsup (Cp) | scalars | perm]`` (checksums stripped).

    Returns None when any shard fails its checksum — the caller
    re-fetches.  With ``n_shards=1`` this is exactly the dense-layout
    verify+strip.  Scalar words and the permutation are replicated
    device-side; shard 0's (checksum-verified) copy is authoritative.

    With ``packed`` each shard's gsup slice carries two uint16 supports
    per int32 word (``cp`` — the padded candidate total — is then
    required to locate the field boundaries).  The checksum is verified
    over the PACKED words exactly as the device computed it, and only
    then is the slice expanded back to int32 supports, so the returned
    body is layout-independent and ``unpack_wire`` never changes."""
    shards = host.reshape(n_shards, -1)
    for s in shards:
        if int(wire_checksum(s[:-1])) != int(s[-1]):
            return None
    if not packed:
        cs = shards.shape[1] - (_N_SCALARS + n_partitions + 1)
        return np.concatenate([shards[:, :cs].reshape(-1), shards[0, cs:-1]])
    if cp is None:
        raise ValueError("packed wire reassembly needs cp")
    cs = cp // n_shards                                # supports per shard
    gw = -(-cs // 2)                                   # packed words
    u = shards[:, :gw].astype(np.uint32)
    lo = (u & np.uint32(0xFFFF)).astype(np.int32)
    hi = (u >> np.uint32(16)).astype(np.int32)
    gsup = np.stack([lo, hi], axis=-1).reshape(n_shards, -1)[:, :cs]
    return np.concatenate([gsup.reshape(-1), shards[0, gw:-1]])


def wire_cost_model(cp: int, n_partitions: int, n_workers: int, *,
                    reduce: str, sharded: Optional[bool] = None,
                    packed: bool = False) -> dict:
    """Modeled per-worker wire bytes for one level (the deterministic
    proxy the scaling CI gate checks — CPU wall time is noisy, bytes
    are not).

    ``host_bytes``       device→host transfer this worker performs for
                         the level wire (int32 words × 4);
    ``collective_bytes`` inter-device bytes this worker moves in the
                         shuffle collectives (ring factors, as in
                         ``benchmarks/bench_reducers``).

    Layouts: ``psum`` — dense wire + 2(W-1)/W·Cp·4B all-reduce;
    dense ``reduce_scatter`` (``sharded=False``) — psum_scatter (4B) +
    verdict all-gather (1B) + support all-gather (4B), dense wire;
    sharded ``reduce_scatter`` (default) — the support all-gather
    disappears (each worker keeps its C/W slice; only the 1-byte
    verdicts and the tiny (NP,) cost vector are gathered) and the host
    transfer shrinks to the worker's own shard.

    ``packed`` (DESIGN.md §12) shrinks the reduce_scatter verdict
    all-gather to bit lanes (``ceil(cp/32)`` uint32 words instead of
    ``cp`` int8 lanes) and the wire's gsup slice to two uint16 supports
    per int32 word."""
    W = n_workers
    if sharded is None:
        sharded = reduce == "reduce_scatter"
    ring = (W - 1) / W
    tail = _N_SCALARS + n_partitions + 1          # scalars + perm + csum
    vbytes = (-(-cp // 32) * 4) if packed else cp * 1   # verdict gather

    def gw(n):                                    # gsup words on the wire
        return -(-n // 2) if packed else n

    if reduce == "psum":
        coll = 2 * ring * cp * 4
        host = (gw(cp) + tail) * 4
    elif not sharded:
        coll = ring * (cp * 4 + vbytes + cp * 4)
        host = (gw(cp) + tail) * 4
    else:
        coll = ring * (cp * 4 + vbytes + n_partitions * 4)
        host = (gw(cp // W) + tail) * 4
    return {"host_bytes": host, "collective_bytes": coll,
            "total_bytes": host + coll}


@dataclasses.dataclass
class LevelWire:
    """Host view of the single per-level transfer."""

    gsup: np.ndarray        # (C,) int32 — global supports, canonical order
    n_keep: int             # true survivor count (may exceed the cap)
    overflow: int           # matches dropped by the M cap (survivors only)
    rebalanced: bool
    imbalance: float
    perm: np.ndarray        # (NP,) applied partition permutation
    audit: int = 0          # device audit bit flags (0 = clean, §14)


@dataclasses.dataclass
class LevelOutputs:
    """Device-resident results of one level program invocation.  The
    edge store passes through untouched; when the wire reports a
    rebalance the driver repacks everything via ``permute_stores``."""

    wire: LevelWire
    pol: jnp.ndarray        # (NP, S, G, M, K+1) — compact survivor OLs
    pmask: jnp.ndarray      # (NP, S, G, M)
    src: jnp.ndarray        # (NP, T, G, F) — edge store (as passed in)
    dst: jnp.ndarray
    emask: jnp.ndarray
    # host seconds from the dispatch's first host work to the decoded
    # wire (the schedule span's start to the wire_decode span's end)
    seconds: float = 0.0


def lpt_permutation(cost: jnp.ndarray, n_workers: int) -> jnp.ndarray:
    """Device LPT repack: heaviest partition first onto the lightest
    worker bucket with room; emits the permutation laying buckets
    contiguously (matching the blocked dim-0 sharding).  The device twin
    of ``mining._lpt_order`` — NP is tiny, so the sequential fori_loop
    is noise next to the level compute it rides along with."""
    npn = cost.shape[0]
    per = npn // n_workers
    order = jnp.argsort(-cost)

    def body(i, state):
        load, cnt, pos = state
        item = order[i]
        bucket_key = jnp.where(cnt < per, load, jnp.inf)
        b = jnp.argmin(bucket_key)
        pos = pos.at[b * per + cnt[b]].set(item.astype(jnp.int32))
        load = load.at[b].add(cost[item])
        cnt = cnt.at[b].add(1)
        return load, cnt, pos

    _, _, pos = jax.lax.fori_loop(
        0, npn, body,
        (jnp.zeros((n_workers,), cost.dtype),
         jnp.zeros((n_workers,), jnp.int32),
         jnp.zeros((npn,), jnp.int32)))
    return pos


@functools.lru_cache(maxsize=256)
def _level_program(mmesh: MiningMesh, minsup: int,
                   backend: Backend, reduce: str, max_embeddings: int,
                   survivor_cap: int, rebalance: bool, threshold: float,
                   donate: bool, child_width: Optional[int],
                   sharded: bool, packed: bool = False,
                   n_graphs: int = -1):
    """Build (and cache per static config) the jitted level program.

    The true candidate count is a TRACED argument (``c_real``), not part
    of the cache key: only bucketed quantities (shapes, the survivor
    cap, M, the child vertex width) select a program, so levels with
    coinciding buckets share one compile (DESIGN.md §9).

    With ``sharded`` the wire is packed per device INSIDE the shard_map
    (each worker's shard carries its C/W support slice; DESIGN.md §11),
    which requires the ``reduce_scatter`` shuffle — the support vector
    is then never all-gathered on device.  The rebalance decision moves
    inside too, fed by an all-gather of the tiny (NP,) cost vector.

    With ``packed`` (DESIGN.md §12) the boolean-per-graph support signal
    travels bit-packed end to end: the fused kernel accumulates verdict
    bitsets in VMEM (AND+popcount support counting), the reduce_scatter
    verdict gather ships uint32 bit lanes, and the wire's gsup slice
    carries two uint16 supports per int32 word (the driver guarantees
    supports < 2^16 by gating on the DB's graph count).  Every output is
    bit-identical to the dense program."""
    axes = mmesh.axes
    W = mmesh.n_workers
    parts = mmesh.spec_parts()
    rep = mmesh.replicated()
    fused = is_fused_backend(backend)
    interpret = backend.endswith("interpret")
    S = survivor_cap
    with_rebalance = rebalance and W > 1
    if sharded and reduce != "reduce_scatter":
        raise ValueError(
            f"the sharded wire needs reduce='reduce_scatter' (each worker "
            f"owns a support slice), got reduce={reduce!r}")

    @jax.named_scope("mirage/wire_pack")
    def _pack_wire(gsup, n_keep, overflow, do_reb, imbal, audit, perm):
        gsup = gsup.astype(jnp.int32)
        if packed:
            # two uint16 supports per int32 word (lossless: the driver
            # only enables packing when every support fits 16 bits);
            # the checksum below covers the PACKED words — the host
            # verifies before expanding (reassemble_wire).
            u = gsup.astype(jnp.uint32)
            if u.shape[0] % 2:
                u = jnp.concatenate([u, jnp.zeros((1,), jnp.uint32)])
            w = u[0::2] | (u[1::2] << jnp.uint32(16))
            gsup = jax.lax.bitcast_convert_type(w, jnp.int32)
        body = jnp.concatenate([
            gsup,
            jnp.stack([n_keep, overflow, do_reb.astype(jnp.int32),
                       (imbal * _IMBAL_FX).astype(jnp.int32),
                       audit.astype(jnp.int32)]),
            perm,
        ])
        return jnp.concatenate([body, wire_checksum(body)[None]])

    @jax.named_scope("mirage/wire_pack")
    def _rebalance(cost):
        NP = cost.shape[0]
        imbal = worker_imbalance(cost, W)
        if with_rebalance:
            do_reb = imbal > threshold
            perm = jnp.where(
                do_reb, lpt_permutation(cost.astype(jnp.float32), W),
                jnp.arange(NP, dtype=jnp.int32))
        else:
            do_reb = jnp.zeros((), bool)
            perm = jnp.arange(NP, dtype=jnp.int32)
        return do_reb, imbal, perm

    def core(c_real, psup, *args):
        with jax.named_scope("mirage/support_kernel"):
            if fused:
                sched_meta, tiles, inv, pol, pmask, src, dst, emask = args
                if packed:
                    # verdict accumulator = ceil(G/32) uint32 words in
                    # VMEM; local support counting is AND+popcount per
                    # tile_c block
                    sup_pp, emb_s = fused_level_supports_packed(
                        sched_meta, tiles, pol, pmask, src, dst, emask,
                        interpret=interpret)
                else:
                    sup_pp, emb_s = fused_level_supports(
                        sched_meta, tiles, pol, pmask, src, dst, emask,
                        interpret=interpret)
                local_sup = jnp.take(sup_pp.sum(0), inv)    # (Cp,) canonical
                emb_pp = jnp.take(emb_s, inv, axis=1)       # (PP, Cp)
                meta_can = jnp.take(sched_meta[:, :5], inv, axis=0)
            else:
                meta, pol, pmask, src, dst, emask = args
                local_sup, _, emb_pp = device_local_supports(
                    meta, pol, pmask, src, dst, emask, backend=backend,
                    packed=packed)
                meta_can = meta

        # sharded: gsup stays the psum_scatter output — this worker's
        # (Cp/W,) key slice, never all-gathered; only the 1-byte
        # verdicts travel the ring (the fig19 wire cut made total) —
        # bit lanes instead when packed.
        with jax.named_scope("mirage/reduce"):
            gsup, verdict = reduce_supports(local_sup, axes, minsup, reduce,
                                            gather_gsup=not sharded,
                                            packed=packed)

        # verdict-masked prefix-sum compaction: survivor i's compact slot
        # is its rank among survivors; one scatter inverts rank -> id.
        # Ranks past the cap S (and non-survivors) scatter out of bounds.
        with jax.named_scope("mirage/compact"):
            Cp = verdict.shape[0]
            real = jnp.arange(Cp) < c_real
            keep = (verdict != 0) & real
            rank = jnp.cumsum(keep.astype(jnp.int32)) - 1
            n_keep = rank[-1] + 1
            dest = jnp.where(keep, rank, S)
            surv = (jnp.zeros((S,), jnp.int32)
                    .at[dest].set(jnp.arange(Cp, dtype=jnp.int32),
                                  mode="drop"))
            cmeta = jnp.take(meta_can, surv, axis=0)        # (S, 5)
            valid_s = jnp.arange(S) < n_keep                # (S,)

        # continuous invariant audit (DESIGN.md §14): bit flags over the
        # level's own outputs, folded into the checksummed wire.  psup
        # is PARENT-indexed (one int32 per parent-store slot, -1 =
        # unknown / padding); each candidate gathers its parent's
        # support through the replicated meta parent column, so the
        # upload is O(parents), not O(candidates).  In sharded mode
        # gsup is this worker's key slice, so the slice-local violation
        # counts are psummed; the compaction and survivor-count checks
        # run on replicated values.
        with jax.named_scope("mirage/audit"):
            par = meta_can[:, 0]
            psc = jnp.where(
                (par >= 0) & (par < psup.shape[0]),
                jnp.take(psup, jnp.clip(par, 0, psup.shape[0] - 1)), -1)
            if sharded:
                w_idx = jax.lax.axis_index(axes)
                cs_a = gsup.shape[0]
                psl = jax.lax.dynamic_slice(psc, (w_idx * cs_a,), (cs_a,))
                real_a = (w_idx * cs_a + jnp.arange(cs_a)) < c_real
            else:
                psl, real_a = psc, real
            gs_a = gsup.astype(jnp.int32)
            mono_bad = ((gs_a > psl) & real_a & (psl >= 0)).sum()
            rng_bad = (((gs_a < 0) | (gs_a > n_graphs)) & real_a).sum() \
                if n_graphs >= 0 else jnp.zeros((), jnp.int32)
            if sharded:
                mono_bad = jax.lax.psum(mono_bad, axes)
                rng_bad = jax.lax.psum(rng_bad, axes)
            comp_bad = (valid_s & ~jnp.take(keep, surv)).sum()
            audit = (jnp.where(mono_bad > 0, AUDIT_MONOTONIC, 0)
                     | jnp.where(comp_bad > 0, AUDIT_COMPACT, 0)
                     | jnp.where(rng_bad > 0, AUDIT_RANGE, 0)
                     | jnp.where(n_keep > c_real, AUDIT_NKEEP, 0)
                     ).astype(jnp.int32)

        # pass 2 over the valid compact slots only: cap padding keeps
        # the constant fill and costs nothing
        with jax.named_scope("mirage/materialize"):
            Wk = (child_width if child_width is not None
                  else pol.shape[-1] + 1)
            ol, mask, over = materialize_prefix(
                cmeta, jnp.minimum(n_keep, S), pol, pmask, src, dst, emask,
                n_slots=S, max_embeddings=max_embeddings, out_width=Wk)
            overflow = jax.lax.psum(over, axes)
        with jax.named_scope("mirage/wire_pack"):
            cost_pp = (emb_pp * real[None, :].astype(emb_pp.dtype)).sum(1)
        if not sharded:
            return gsup, n_keep, overflow, audit, ol, mask, cost_pp
        # sharded wire: the LPT/rebalance decision moves inside the
        # shard_map (fed by an all-gather of the TINY (NP,) cost
        # vector), and each worker packs its own shard — support slice,
        # replicated scalars + perm, per-shard checksum.  The level's
        # device→host transfer is then 1/W-sized per worker.
        with jax.named_scope("mirage/wire_pack"):
            cost = jax.lax.all_gather(cost_pp, axes, axis=0, tiled=True)
        do_reb, imbal, perm = _rebalance(cost)
        shard = _pack_wire(gsup, n_keep, overflow, do_reb, imbal, audit,
                           perm)
        return shard, ol, mask

    n_meta = 3 if fused else 1
    out_specs = ((parts, parts, parts) if sharded
                 else (rep, rep, rep, rep, parts, parts, parts))
    smapped = jax_compat.shard_map(
        core, mesh=mmesh.mesh,
        in_specs=(rep,) * (2 + n_meta) + (parts,) * 5,
        out_specs=out_specs, check_vma=False)

    if sharded:
        program = smapped
    else:
        def program(*args):
            (gsup, n_keep, overflow, audit, ol, mask,
             cost) = smapped(*args)
            do_reb, imbal, perm = _rebalance(cost)
            wire = _pack_wire(gsup, n_keep, overflow, do_reb, imbal,
                              audit, perm)
            return wire, ol, mask

    donate_argnums = ()
    if donate:
        # the parent OL store (after c_real + psup + the meta args).
        # With bucketed shapes the child store matches it exactly, so
        # this is a true arena alias, not just an early free.
        donate_argnums = (2 + n_meta, 3 + n_meta)
    return jax.jit(program, donate_argnums=donate_argnums)


@functools.lru_cache(maxsize=64)
def _permute_program(mmesh: MiningMesh):
    """Partition gather applying a wire-reported LPT permutation to the
    whole device-resident store (OL + edge arrays) — dispatched only
    when a rebalance actually fired, so the (rare) all-to-all neither
    taxes every level's compile nor syncs the host.  Inputs are donated:
    the repack replaces the store wholesale."""
    shard = NamedSharding(mmesh.mesh, mmesh.spec_parts())

    @jax.named_scope("mirage/permute")
    def permute(perm, *arrays):
        return tuple(jax.lax.with_sharding_constraint(
            jnp.take(a, perm, axis=0), shard) for a in arrays)

    return jax.jit(permute, donate_argnums=tuple(range(1, 6)))


def permute_stores(mmesh: MiningMesh, perm: np.ndarray, *arrays):
    """Apply the level's LPT permutation to (pol, pmask, src, dst,
    emask) on device.  No host transfer — ``perm`` came home in the
    wire."""
    with tracing.Span("permute"):
        return _permute_program(mmesh)(jnp.asarray(perm, jnp.int32),
                                       *arrays)


def _fetch_wire(wire_d, level: Optional[int], n_partitions: int,
                n_shards: int = 1, packed: bool = False,
                cp: Optional[int] = None) -> np.ndarray:
    """The ONE device→host transfer of a clean level, integrity-checked.

    ``np.array`` (a copy, so jax's cached host value stays pristine even
    when the chaos hook corrupts our view) fetches the packed wire —
    with the sharded layout each worker contributes only its own slice
    to that one gather.  Every shard's trailing checksum word is
    re-computed host-side before any field is decoded.  A mismatch — a
    flipped bit on the host link — triggers a bounded re-fetch from the
    device buffer; persistent mismatch raises
    :class:`~repro.runtime.faults.WireIntegrityError` for the supervisor
    rather than ever decoding corrupt supports."""
    for _ in range(_WIRE_FETCH_ATTEMPTS):
        tracing.count("wire_fetches")
        host = faults.corrupt_wire(np.array(wire_d), level)
        body = reassemble_wire(host, n_partitions, n_shards,
                               packed=packed, cp=cp)
        if body is not None:
            return body
    raise faults.WireIntegrityError(
        f"level wire failed checksum {_WIRE_FETCH_ATTEMPTS}x"
        + (f" at level {level}" if level is not None else ""))


def fetch_wire(wire_d, level: Optional[int] = None) -> np.ndarray:
    """Fetch + verify a DENSE single-shard wire (trailing §10 checksum
    word), with the same bounded re-fetch and chaos hook as the level
    wire.  Used by the device-loop pipeline for its one run wire."""
    return _fetch_wire(wire_d, level, 0, 1, False, None)


def unpack_wire(wire: np.ndarray, C: int, Cp: int, n_partitions: int
                ) -> LevelWire:
    """Decode the (checksum-stripped) wire body by explicit offsets —
    robust to any trailing padding."""
    return LevelWire(
        gsup=wire[:C],
        n_keep=int(wire[Cp]),
        overflow=int(wire[Cp + 1]),
        rebalanced=bool(wire[Cp + 2]),
        imbalance=float(wire[Cp + 3]) / _IMBAL_FX,
        perm=wire[Cp + 5: Cp + 5 + n_partitions],
        audit=int(wire[Cp + 4]),
    )


@dataclasses.dataclass
class PendingLevel:
    """An in-flight level program: dispatched, not yet synced.

    Holds the device-resident futures (JAX dispatches asynchronously, so
    construction returns before the program finishes) plus everything
    the host needs to decode the wire later.  ``finish()`` performs the
    level's single blocking device→host transfer — the driver calls it
    only after it has done the NEXT level's host candidate generation in
    the shadow of this program (DESIGN.md §11)."""

    wire_d: jax.Array          # packed wire (dense or sharded layout)
    pol: jnp.ndarray           # (NP, S, G, M, K+1) — child OLs (future)
    pmask: jnp.ndarray
    src: jnp.ndarray
    dst: jnp.ndarray
    emask: jnp.ndarray
    C_real: int
    Cp: int
    n_partitions: int
    n_shards: int              # 1 = dense wire; W = sharded
    level: Optional[int]
    packed: bool = False       # gsup slices ship 2x uint16 per word
    start_ns: int = 0          # the dispatch's schedule span opened

    def finish(self) -> LevelOutputs:
        """Block on the wire (the one host sync), verify + decode it.
        The wait for the device and the transfer are separate spans, so
        a stall in the transfer is not put down to the device."""
        with tracing.Span("wire_wait"):
            self.wire_d.block_until_ready()
        with tracing.Span("wire_decode",
                          counts={"attempts": "wire_fetches"}) as dec:
            wire = unpack_wire(
                _fetch_wire(self.wire_d, self.level, self.n_partitions,
                            self.n_shards, self.packed, self.Cp),
                self.C_real, self.Cp, self.n_partitions)
        return LevelOutputs(wire, self.pol, self.pmask, self.src,
                            self.dst, self.emask,
                            (dec.end_ns - self.start_ns) / 1e9)


def dispatch_level(
    mmesh: MiningMesh,
    meta_p: np.ndarray,       # (Cp, 5) padded candidate metadata (host)
    C_real: int,              # unpadded candidate count
    pol: jnp.ndarray,         # (NP, P, G, M, K) sharded dim0
    pmask: jnp.ndarray,
    src: jnp.ndarray,         # (NP, T, G, F)
    dst: jnp.ndarray,
    emask: jnp.ndarray,
    *,
    minsup: int,
    backend: Backend,
    reduce: str,
    max_embeddings: int,
    survivor_cap: int,
    rebalance: bool,
    threshold: float,
    donate: bool,
    child_width: Optional[int] = None,
    sched_floor: Optional[int] = None,
    level: Optional[int] = None,
    sharded: bool = False,
    packed: bool = False,
    tile_c: Optional[int] = None,
    psup: Optional[np.ndarray] = None,
    n_graphs: int = -1,
) -> PendingLevel:
    """Dispatch one level program WITHOUT the host sync.

    The fused backends build the parent-grouped tile schedule host-side
    (same contract as ``map_reduce_supports``), so ``meta_p`` must be
    concrete.  Returns a :class:`PendingLevel`; the caller blocks via
    ``finish()`` when it needs the wire, and owns retry policy
    (escalation / cap miss).

    ``child_width`` is the (bucketed) child vertex-slot width, default
    exact K+1; ``sched_floor`` buckets the fused schedule's row count
    so consecutive levels present one static schedule shape.
    ``sharded`` selects the sharded wire layout (requires
    ``reduce='reduce_scatter'`` and Cp divisible by the worker count).
    ``packed`` selects the bit-packed support path (DESIGN.md §12) —
    the caller guarantees supports fit uint16 (total graph count
    < 2^16).  ``tile_c`` pins the fused schedule's candidate-tile width
    for the run (None = the adaptive per-call choice); the driver pins
    it from the level-2 grouping so the kernel grid — and therefore the
    compiled program — stays constant across levels.

    ``psup`` feeds the device-side invariant audit (DESIGN.md §14): the
    PARENT-indexed support vector, one int32 per slot of the parent
    store's pattern axis in canonical order (-1 = unknown, which skips
    the monotonicity check for candidates of that parent).  It is
    padded to the store's parent axis, so the upload is O(parents) —
    each candidate gathers its parent's support on device through the
    meta parent column.  ``n_graphs`` (the DB graph count) arms the
    support-range check; -1 disables it.  The audit word rides home in
    the wire; a zero word certifies the level passed every check.
    """
    Cp = meta_p.shape[0]
    n_partitions = pol.shape[0]
    W = mmesh.n_workers
    if sharded and Cp % W:
        raise ValueError(
            f"sharded wire needs the padded candidate count divisible by "
            f"the worker count, got Cp={Cp}, W={W} (buckets.candidates / "
            f"round_up_multiple(C, W) guarantee this in the pipeline)")
    # chaos hook: a scheduled in-kernel fault fires here, standing in for
    # an XLA/Mosaic dispatch abort (the supervisor's degradation ladder
    # answers it by swapping backends)
    faults.maybe_raise("kernel", level)
    with tracing.Span("schedule") as sched_span:
        fn = _level_program(mmesh, minsup, backend, reduce,
                            max_embeddings, survivor_cap, rebalance,
                            threshold, donate, child_width, sharded, packed,
                            n_graphs)
        c_real = jnp.asarray(C_real, jnp.int32)
        # pad to the parent store's pattern axis: the psup length then
        # moves with the same bucket family as pol, costing no extra
        # compiles
        P_axis = pol.shape[1]
        psup_p = np.full((P_axis,), -1, np.int32)
        if psup is not None:
            n_par = min(len(psup), P_axis)
            psup_p[:n_par] = np.asarray(psup, np.int32)[:n_par]
        psup_d = jnp.asarray(psup_p)
        meta_args = schedule_args(meta_p, C_real, backend, tile_c,
                                  sched_floor, sched_span)
    with tracing.Span("dispatch", counts={"compiles": "compiles"}):
        wire_d, new_pol, new_pmask = fn(c_real, psup_d, *meta_args,
                                        pol, pmask, src, dst, emask)
    return PendingLevel(wire_d, new_pol, new_pmask, src, dst, emask,
                        C_real, Cp, n_partitions,
                        W if sharded else 1, level, packed,
                        sched_span.start_ns)


def schedule_args(meta_p: np.ndarray, C_real: int, backend: Backend,
                  tile_c: Optional[int], sched_floor: Optional[int],
                  span: tracing.Span) -> tuple:
    """The candidate arguments of a level's support stage: the padded
    canonical ``meta_p`` itself, or for the fused backends the
    parent-grouped tile schedule ``(meta, tiles, inv)`` of its first
    ``C_real`` rows (set as the ``rows``/``tile_c`` args of ``span``)."""
    if not is_fused_backend(backend):
        return (jnp.asarray(meta_p),)
    from ..kernels.fused_level import DEFAULT_TILE_C
    from .buckets import bucket_size
    from .candgen import pad_schedule, schedule_candidates
    Cp = meta_p.shape[0]
    tc = tile_c if tile_c is not None else DEFAULT_TILE_C
    # only the real rows are scheduled (padded candidates would
    # fragment the parent grouping); the row axis is then bucketed
    # with whole invalid tiles and inv parked on one of them.  The
    # bucketed schedule PINS tile_c: the adaptive halving picks a
    # different width per level (a different kernel grid — a
    # recompile); partial-tile waste is bounded by the row bucket and
    # fully-invalid tiles are skipped inside the kernel.  The mining
    # loop's run-level pin (``tile_c``) replaces the hardwired 8 with
    # the level-2 grouping's adaptive choice.
    if sched_floor is not None:
        sched = schedule_candidates(np.asarray(meta_p)[:C_real], tc,
                                    max_inflation=float("inf"))
        rows = bucket_size(sched.meta.shape[0], sched_floor)
    else:
        sched = schedule_candidates(np.asarray(meta_p)[:C_real], tc)
        rows = sched.meta.shape[0]
    sched = pad_schedule(sched, rows_to=rows, inv_to=Cp)
    span.set(rows=rows, tile_c=sched.tile_c)
    return (jnp.asarray(sched.meta), jnp.asarray(sched.tiles),
            jnp.asarray(sched.inv))


def run_level(*args, **kwargs) -> LevelOutputs:
    """Dispatch one level program and perform the single host sync.

    ``dispatch_level(...).finish()`` — the non-overlapped form; same
    signature as :func:`dispatch_level`."""
    return dispatch_level(*args, **kwargs).finish()
