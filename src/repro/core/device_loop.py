"""Whole-run device-resident mining loop (pipeline="device_loop",
DESIGN.md §13).

The single-sync pipeline (PR 3) already collapsed each mining level to
one jitted program and ONE device→host transfer — but the *run* still
crossed the boundary once per level: fetch the wire, host-generate the
next level's candidates, re-upload their metadata, dispatch again.  On
a real pod every crossing is a dispatch-latency bubble; on the paper's
ledger it is the per-iteration job-startup overhead of iterative
MapReduce (§IV-B), shrunk but not gone.

This module removes the loop itself from the host.  One jitted
shard_map program executes the ENTIRE run as a ``lax.while_loop``:

  body (one level, all on device):
    1. candidate generation — ``candgen.device_candidates``: rightmost-
       path extension slots over array-shaped DFS codes + the bounded-
       state ``min_dfs_canonical_array`` canonicality machine, prefix-sum
       compacted into a fixed candidate budget CB in EXACTLY the host
       generator's order;
    2. schedule — ``candgen.device_schedule`` recasts the parent-grouped
       tile schedule as pure jnp under static (rows, tile_c), feeding
       the fused Pallas kernel inside the loop body (non-fused backends
       take the vmapped ``device_local_supports`` path);
    3. map + shuffle — the same ``reduce_supports`` collective as the
       level program (psum | reduce_scatter, bit-packed verdict lanes
       under ``packed``), with the support vector all-gathered so every
       device can fill the run outputs;
    4. reduce — verdict-masked prefix-sum compaction of survivors into
       the SPP parent slots, ``materialize_prefix`` over the survivors;
    5. bookkeeping — per-level stats row (candidates, survivors,
       overflow, imbalance, bail flags), survivor supports and codes
       written at the level's slot of the run outputs.

  cond: ``(k < k_stop) & (n_par > 0) & ok`` — mining stops at max_size,
  at the first empty frequent set, or when any exactness valve trips
  (candidate/state/schedule budget overflow); ``ok=False`` makes the
  driver fall back to the per-level single-sync pipeline, keeping the
  conformance contract bit-exact.

Every iteration has IDENTICAL shapes (the while_loop carry): the run
compiles ONE program (asserted ≤3 in tests/test_compile_cache.py) and
the host receives ONE transfer — the run wire:

  [ out_stats (NL·6) | out_sups (NL·SPP) | out_codes (NL·SPP·L·5)
    | k_final | n_par | ok | total_overflow | checksum ]

verified with the §10 position-salted checksum and decoded into the
same levels/supports/stats the per-level pipeline produces.

Checkpoint cadence (``device_loop_ckpt_every``): the SAME compiled
program is re-invoked on its own device-resident carry with a nearer
``k_stop`` — a chunk; at each chunk boundary the host fetches the wire
plus the OL store and writes the usual canonical checkpoint.  The
transfer count per run is exactly ``1`` without checkpointing and
``3 · n_chunks`` with it (wire + pol + pmask per boundary), gated by
``benchmarks/check_residency.py``.

The escalation valve hoists to run granularity: the loop mines at one
uniform embedding cap M (the carry shape); if the run finishes with
``total_overflow > 0`` the driver doubles M and reruns the whole
program — earlier levels had no overflow at the smaller M, so their
stores are bit-identical at the larger one and the rerun converges to
the exact (escalated) host semantics.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from ..kernels.ops import (Backend, device_local_supports,
                           fused_level_supports,
                           fused_level_supports_packed, is_fused_backend)
from ..runtime import jax_compat
from .candgen import device_candidates, device_schedule
from .embedding import materialize_prefix
from .level_step import _IMBAL_FX, wire_checksum
from .mapreduce import MiningMesh, reduce_supports, worker_imbalance

__all__ = ["DeviceLoopFallback", "RunWire", "run_wire_words",
           "decode_run_wire", "run_program"]

#: per-level stats words in the run wire:
#: [n_candidates, n_keep, overflow, imbalance·2^16, bail flags, reserved]
NSTAT = 6

#: bail-flag bits (stats word 4): any nonzero flag stops the loop and
#: sends the driver to the single-sync fallback
FLAG_RAW_OVF = 1        # structural slots overflowed the raw budget
FLAG_CANON_OVF = 2      # canonical candidates overflowed CB
FLAG_STATE_OVF = 4      # canonicality machine overflowed max_states
FLAG_SCHED_OVF = 8      # tile-padded schedule overflowed the row budget


class DeviceLoopFallback(RuntimeError):
    """The device loop bailed (budget/state/schedule overflow, or
    overflow at the M ceiling) — the driver replays the run through the
    per-level single-sync pipeline, which has no static budgets."""


@dataclasses.dataclass
class RunWire:
    """Host view of the run's single transfer."""

    stats: np.ndarray      # (NL, NSTAT) int32 per-level stats rows
    sups: np.ndarray       # (NL, SPP) int32 survivor supports, slot order
    codes: np.ndarray      # (NL, SPP, L, 5) int32 survivor DFS codes
    k_final: int           # parent size the loop stopped at
    n_par: int             # surviving parent count at the stop
    ok: bool               # False = a bail flag tripped mid-run
    total_overflow: int    # M-cap overflow summed over the run


def run_wire_words(n_levels: int, spp: int, max_edges: int) -> int:
    """Total int32 words of the run wire (incl. trailer + checksum)."""
    return (n_levels * NSTAT + n_levels * spp
            + n_levels * spp * max_edges * 5 + 4 + 1)


def decode_run_wire(body: np.ndarray, n_levels: int, spp: int,
                    max_edges: int) -> RunWire:
    """Decode a (checksum-stripped) run-wire body by explicit offsets."""
    o = 0
    stats = body[o:o + n_levels * NSTAT].reshape(n_levels, NSTAT)
    o += n_levels * NSTAT
    sups = body[o:o + n_levels * spp].reshape(n_levels, spp)
    o += n_levels * spp
    codes = body[o:o + n_levels * spp * max_edges * 5].reshape(
        n_levels, spp, max_edges, 5)
    o += n_levels * spp * max_edges * 5
    k_final, n_par, ok, tovf = (int(x) for x in body[o:o + 4])
    return RunWire(stats, sups, codes, k_final, n_par, bool(ok), tovf)


@functools.lru_cache(maxsize=32)
def _run_program(mmesh: MiningMesh, minsup: int, backend: Backend,
                 reduce: str, packed: bool, max_edges: int,
                 n_vertex_slots: int, c_budget: int, raw_budget: int,
                 max_states: int, n_levels: int, tile_c: int,
                 sched_rows: int, n_triples: int, unroll: int):
    """Build (once per static config) the jitted whole-run program.

    ``k_stop`` and the loop carry are TRACED — chunked re-invocation for
    checkpointing reuses this one compile.  ``unroll > 0`` replaces the
    while_loop with that many cond-gated body applications (the
    stepping-stone variant differential tests pin against the loop).
    All shapes are static: CB (``c_budget``) is the canonical candidate
    budget, CBR the structural raw budget, SPP the parent/survivor slot
    count (the codes/OL-store pattern axis), NL the level-slot count,
    and the fused schedule lives in ``sched_rows`` rows of ``tile_c``.
    """
    axes = mmesh.axes
    W = mmesh.n_workers
    parts = mmesh.spec_parts()
    rep = mmesh.replicated()
    fused = is_fused_backend(backend)
    interpret = backend.endswith("interpret")
    NV = n_vertex_slots
    CB = c_budget
    NL = n_levels

    def graph_minor(pol, pmask):
        # pin the carried store graph-minor (the layout the compiled
        # kernel reads); left free, XLA lays the loop carry out with K
        # minor-most, padding it 16x and copying it every iteration
        if not fused or interpret:
            return pol, pmask
        return (with_layout_constraint(pol, Layout((0, 1, 3, 4, 2))),
                with_layout_constraint(pmask, Layout((0, 1, 3, 2))))

    def core(k_stop, k0, n_par0, codes0, triples, pol, pmask, src, dst,
             emask, out_codes0, out_sups0, out_stats0, ok0, tovf0):
        SPP = codes0.shape[0]
        M, K = pol.shape[3:]

        def body(carry):
            (k, n_par, codes, pol, pmask,
             out_codes, out_sups, out_stats, ok, tovf) = carry

            # 1. right-most-extension candidates, host order (candgen.py)
            meta, child, n_cand, cg_flags = device_candidates(
                codes, n_par, triples, n_vertex_slots=NV,
                raw_budget=raw_budget, budget=CB, max_states=max_states)

            # 2+3. map phase + shuffle — same kernels/collective as the
            # per-level program, with the schedule built on device
            if fused:
                sched, tiles, inv, sc_ovf = device_schedule(
                    meta, n_cand, tile_c=tile_c, n_triples=n_triples,
                    rows=sched_rows)
                if packed:
                    sup_pp, emb_s = fused_level_supports_packed(
                        sched, tiles, pol, pmask, src, dst, emask,
                        interpret=interpret)
                else:
                    sup_pp, emb_s = fused_level_supports(
                        sched, tiles, pol, pmask, src, dst, emask,
                        interpret=interpret)
                local_sup = jnp.take(sup_pp.sum(0), inv)     # (CB,) canonical
                emb_pp = jnp.take(emb_s, inv, axis=1)        # (PP, CB)
            else:
                local_sup, _, emb_pp = device_local_supports(
                    meta, pol, pmask, src, dst, emask, backend=backend,
                    packed=packed)
                sc_ovf = jnp.zeros((), bool)
            # the run outputs need the full support vector on every
            # device, so the sharded-gsup wire optimization does not
            # apply here — there is only ONE transfer per run anyway
            gsup, verdict = reduce_supports(local_sup, axes, minsup,
                                            reduce, gather_gsup=True,
                                            packed=packed)

            # 4. survivor compaction into the SPP parent slots (the
            # level program's prefix-sum idiom; SPP >= CB >= n_keep, so
            # the compaction can never miss)
            real = jnp.arange(CB) < n_cand
            keep = (verdict != 0) & real
            rank = jnp.cumsum(keep.astype(jnp.int32)) - 1
            n_keep = rank[-1] + 1
            dest = jnp.where(keep, rank, SPP)
            surv = (jnp.zeros((SPP,), jnp.int32)
                    .at[dest].set(jnp.arange(CB, dtype=jnp.int32),
                                  mode="drop"))
            valid_s = jnp.arange(SPP) < n_keep
            cmeta = jnp.take(meta, surv, axis=0)             # (SPP, 5)

            new_pol, new_pmask, over = materialize_prefix(
                cmeta, jnp.minimum(n_keep, SPP), pol, pmask, src, dst,
                emask, n_slots=SPP, max_embeddings=M, out_width=K)
            new_pol, new_pmask = graph_minor(new_pol, new_pmask)
            overflow = jax.lax.psum(over, axes)

            # 5. run-output bookkeeping at this level's slot
            cost_pp = (emb_pp * real[None, :].astype(emb_pp.dtype)).sum(1)
            cost = jax.lax.all_gather(cost_pp, axes, axis=0, tiled=True)
            imbal = worker_imbalance(cost, W)
            flags = (cg_flags[0].astype(jnp.int32) * FLAG_RAW_OVF
                     | cg_flags[1].astype(jnp.int32) * FLAG_CANON_OVF
                     | cg_flags[2].astype(jnp.int32) * FLAG_STATE_OVF
                     | sc_ovf.astype(jnp.int32) * FLAG_SCHED_OVF)
            slot = k - 1
            out_stats = out_stats.at[slot].set(jnp.stack(
                [n_cand, n_keep, overflow,
                 (imbal * _IMBAL_FX).astype(jnp.int32), flags,
                 jnp.zeros((), jnp.int32)]))
            out_sups = out_sups.at[slot].set(
                jnp.where(valid_s, jnp.take(gsup, surv), 0)
                .astype(jnp.int32))
            new_codes = jnp.where(valid_s[:, None, None],
                                  jnp.take(child, surv, axis=0), -1)
            out_codes = out_codes.at[slot].set(new_codes)
            return (k + 1, n_keep, new_codes, new_pol, new_pmask,
                    out_codes, out_sups, out_stats,
                    ok & (flags == 0), tovf + overflow)

        def cond(carry):
            k, n_par = carry[0], carry[1]
            ok = carry[8]
            return (k < k_stop) & (n_par > 0) & ok

        pol, pmask = graph_minor(pol, pmask)
        carry = (k0, n_par0, codes0, pol, pmask,
                 out_codes0, out_sups0, out_stats0, ok0, tovf0)
        if unroll > 0:
            for _ in range(unroll):
                carry = jax.lax.cond(cond(carry), body, lambda c: c, carry)
        else:
            carry = jax.lax.while_loop(cond, body, carry)
        (k, n_par, codes, pol, pmask,
         out_codes, out_sups, out_stats, ok, tovf) = carry

        wire_body = jnp.concatenate([
            out_stats.reshape(-1), out_sups.reshape(-1),
            out_codes.reshape(-1),
            jnp.stack([k, n_par, ok.astype(jnp.int32), tovf])])
        wire = jnp.concatenate([wire_body, wire_checksum(wire_body)[None]])
        return (wire, k, n_par, codes, pol, pmask,
                out_codes, out_sups, out_stats, ok, tovf)

    smapped = jax_compat.shard_map(
        core, mesh=mmesh.mesh,
        in_specs=(rep, rep, rep, rep, rep, parts, parts, parts, parts,
                  parts, rep, rep, rep, rep, rep),
        out_specs=(rep, rep, rep, rep, parts, parts, rep, rep, rep, rep,
                   rep),
        check_vma=False)
    return jax.jit(smapped)


def run_program(*args, **kwargs):
    """Public (monkeypatch-stable) accessor for the cached run program —
    the compile-count tracer in tests wraps ``_run_program`` exactly the
    way it wraps ``level_step._level_program``."""
    return _run_program(*args, **kwargs)
