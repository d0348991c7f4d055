"""MIRAGE iterative mining driver (paper §IV-B/C, Figs. 9-10).

Phases:
  1. data partition  — filter infrequent edges, split into NP partitions
                       (NP ≫ workers, paper Fig. 20), pad uniformly;
  2. preparation     — per-partition static structures (edge-OL,
                       edge-extension map is implied by the triple table)
                       + the level-1 pattern OLs;
  3. mining          — host enumerates canonical candidates from F_k
                       (tiny metadata); the devices run the whole level
                       as ONE program (`core/level_step.py`): fused join
                       (map), dense collective (shuffle+reduce), on-device
                       survivor compaction, child-OL materialization and
                       straggler repack — the host syncs exactly once per
                       level, on the packed wire vector.  Repeat until no
                       frequent patterns.

Three pipelines (MirageConfig.pipeline):
  "single_sync" — the device-resident level program above (default);
  "device_loop" — the ENTIRE run as one jitted lax.while_loop program
                  (core/device_loop.py, DESIGN.md §13): on-device
                  candidate generation + schedule + level compute, one
                  device→host transfer per run; bails to single_sync
                  when a static budget overflows;
  "legacy"      — the PR-1 two-program driver (separate support and
                  materialize dispatches, host keep-list, host-side
                  escalation loop and LPT detour), kept as the
                  differential oracle and benchmark baseline.

Fault tolerance: every level boundary checkpoints the complete mining
state (codes + OL store + cursor) atomically — the HDFS write of the
paper made explicit.  ``Mirage.fit(..., resume=True)`` replays at most
one level after any failure, and may resume onto a *different* mesh
(elastic: state is saved unsharded, resharded on load).

Straggler mitigation: the join kernel's embed-count output is an exact
per-partition cost signal for the *next* level; when predicted imbalance
exceeds a threshold the partition→device assignment is re-packed (LPT)
and the OL store re-laid-out (one all-to-all-equivalent gather).  Under
the single-sync pipeline both the decision and the gather run on device;
the applied permutation rides home in the wire so checkpoints stay in
canonical partition order.  This is deterministic load balancing,
replacing Hadoop's speculative execution.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.fused_level import LANES
from ..kernels.ops import Backend, default_backend, is_fused_backend
from ..runtime import checkpoint as ckpt
from ..runtime import faults, tracing
from ..runtime.sharding import partition_sharding
from ..runtime.watchdog import Watchdog
from . import device_loop as dloop
from .auditor import Auditor
from .buckets import BucketSpec, bucket_size, round_up_multiple
from .candgen import (Candidate, EdgeAlphabet, candidates_from_arrays,
                      device_candgen_jit, filter_speculative,
                      generate_candidates, schedule_candidates)
from .dfscode import Code, array_to_code, code_to_array
from .embedding import (build_adjacency, build_edge_ol, candidate_meta,
                        empty_spill, level1_ol)
from .graphdb import Graph
from .level_step import (_IMBAL_FX, dispatch_level, fetch_wire,
                         permute_stores, schedule_args)
from .mapreduce import (MiningMesh, map_materialize, map_reduce_supports,
                        spill_counts, spill_materialize, spill_supports)
from .partition import make_partitions

__all__ = ["MirageConfig", "LevelStats", "DistMiningResult",
           "PartialResult", "Mirage", "DonationPolicy",
           "DonationRetryRebuild", "decode_saved_levels"]

PIPELINES = ("single_sync", "device_loop", "legacy")
CANDGENS = ("host", "device")
STORES = ("dense", "spill")

# the spill store's table rows R (1024·4^i, never shrinking within a fit:
# chunks past the last used row cost nothing, a new R costs a compile)
# and its candidates per parent (bucketed from 8)
_SPILL_R_FLOOR = 1024
_SPILL_KC_FLOOR = 8

# child stores a level may hold at once, in units of the cap's store:
# the program's own (S slots at M) plus a retry's beside it (S slots at
# up to 2·M) — see Mirage._fit_cap
_CAP_STORES = 3


class DonationRetryRebuild(RuntimeError):
    """An armed-donation level needed its retry path, but donation
    already consumed the parent buffers — the driver must rebuild them
    from the latest checkpoint and replay the level."""

    def __init__(self, level: int):
        self.level = level
        super().__init__(
            f"level {level}: donated arena hit a retry — rebuilding "
            f"parents from checkpoint")


class DonationPolicy:
    """Donation re-arming state machine (DESIGN.md §10, closing the
    PR-3 ROADMAP note).

    A level that might retry (survivor-cap miss, escalation valve) must
    normally keep its parent buffers alive — donation off, arena lost.
    This policy re-arms donation after ``k`` consecutive clean levels
    *provided* a checkpoint exists to rebuild the parents from: the
    retry stays possible, it just changes shape — a gambled retry costs
    one checkpoint load + level replay instead of a kept parent copy
    every level.  A retry or a rebuild resets the streak."""

    def __init__(self, k: int, can_rebuild: bool = False):
        self.k = k
        self.can_rebuild = can_rebuild
        self.clean_streak = 0
        self.rebuilds = 0

    @property
    def armed(self) -> bool:
        """May the driver donate even though this level could retry?"""
        return (self.k > 0 and self.can_rebuild
                and self.clean_streak >= self.k)

    def record(self, retried: bool) -> None:
        """Account one completed level."""
        self.clean_streak = 0 if retried else self.clean_streak + 1

    def record_rebuild(self) -> None:
        """The gamble lost: parents were rebuilt from checkpoint."""
        self.rebuilds += 1
        self.clean_streak = 0


@dataclasses.dataclass
class MirageConfig:
    minsup: float | int                 # fraction of |G| or absolute count
    n_partitions: int = 8
    scheme: int | str = 2               # partition scheme (1|2|"density")
    max_size: Optional[int] = None      # max pattern edges (None = to fixpoint)
    max_embeddings: int = 32            # M cap (exactness valve escalates)
    max_embeddings_limit: int = 512     # escalation ceiling
    # the embedding store (DESIGN.md §15): "dense" holds M embeddings a
    # (pattern, graph) pair and escalates M on overflow up to the
    # ceiling, past which it cuts embeddings off and reports them in
    # total_overflow; "spill" (single_sync only) keeps M fixed and holds
    # every pair past it, whole, in a row table that follows the real
    # embeddings — exact at any size, total_overflow always 0
    embedding_store: str = "dense"
    max_occ: Optional[int] = None       # F pad (None = derive from data)
    backend: Optional[Backend] = None   # kernels backend (None = auto)
    # shuffle collective; None resolves per pipeline in __post_init__:
    # "reduce_scatter" for single_sync (fig19: faster AND lighter on the
    # wire), "psum" for legacy (the paper-faithful differential oracle)
    reduce: Optional[str] = None        # "psum" | "reduce_scatter" | None
    # sharded wire layout (DESIGN.md §11): each worker transfers only its
    # C/W support slice.  None = auto (on whenever the reduce_scatter
    # shuffle runs under single_sync — the slice already lives there)
    sharded_wire: Optional[bool] = None
    # bit-packed support path (DESIGN.md §12): verdict bitsets in VMEM
    # with AND+popcount support counting, bit-lane verdict gathers, and
    # a 2x-uint16 gsup wire slice.  None = auto (on for single_sync);
    # the legacy pipeline stays dense — it is the differential oracle.
    # Regardless of the flag, packing engages only when every support
    # fits uint16 (total graph count < 2^16)
    packed_support: Optional[bool] = None
    # double-buffer host candidate generation for level k+1 in the
    # shadow of level k's in-flight device program (DESIGN.md §11)
    overlap_candgen: bool = True
    # speculation cost gate: the speculative candgen runs over the FULL
    # candidate superset, |C_k|/|F_k| times the survivor-only work — at
    # sparse survival that dwarfs the device time it hides behind.  The
    # driver estimates its cost from a running per-parent candgen rate
    # and skips the speculation for any level where the estimate
    # exceeds the hiding window max(previous level's device seconds,
    # this floor)
    overlap_spec_window: float = 0.05
    checkpoint_dir: Optional[str] = None
    escalate_on_overflow: bool = True
    rebalance_threshold: float = 1.25   # max/mean partition cost trigger
    rebalance: bool = True
    pipeline: str = "single_sync"   # "single_sync"|"device_loop"|"legacy"
    # candidate generation: "host" (the python generator) or "device"
    # (candgen.device_candidates dispatched per level — the benchable
    # stepping stone toward device_loop, which always generates on
    # device INSIDE its while_loop).  Device candgen statically disables
    # the speculative-overlap machinery; a per-level budget/state
    # overflow falls back to the host generator for that level only.
    candgen: str = "host"
    # ---- device_loop static budgets (DESIGN.md §13) ------------------
    # canonical candidate budget CB per loop iteration (None = auto:
    # 4x the host-generated start-level candidate count, bucketed —
    # candgen typically peaks one or two levels past the start); the raw
    # structural-slot budget before canonicality filtering (None = auto:
    # 4x CB); the canonicality machine's bounded state count.  Any
    # overflow trips a bail flag and the run falls back to single_sync.
    device_c_budget: Optional[int] = None
    device_raw_budget: Optional[int] = None
    device_max_states: int = 64
    # checkpoint cadence: re-invoke the (single) compiled run program
    # every k levels, fetching wire + OL store at each boundary for the
    # canonical checkpoint (None = no mid-run checkpoints — exactly one
    # device→host transfer for the whole run)
    device_loop_ckpt_every: Optional[int] = None
    # > 0: replace the while_loop with this many cond-gated body
    # applications per program invocation (the unrolled stepping stone)
    device_loop_unroll: int = 0
    donate: bool = True                 # donate OL buffers when retry-free
    # re-arm donation after this many consecutive clean levels even when
    # a retry is possible, rebuilding parents from checkpoint if the
    # gamble loses (0 disables; needs checkpoint_dir to ever engage)
    donation_rearm_levels: int = 3
    predict_survivors: bool = True      # shrink the survivor cap from history
    survivor_slack: float = 2.0         # cap = slack * predicted survivors
    # ---- shape bucketing (single_sync pipeline; DESIGN.md §9) --------
    # round the per-level shapes (Cp, S, P, M, K, fused-schedule rows)
    # up to the geometric family floor·2^i so consecutive levels hit the
    # jit cache instead of recompiling, and the donated parent/child
    # stores alias as one arena.  Padded slots are masked end-to-end.
    bucket_shapes: bool = True
    bucket_c_floor: int = 64            # candidate axis Cp (+ sched rows)
    bucket_s_floor: int = 32            # survivor cap S / parent axis P
    bucket_k_floor: int = 8             # OL vertex-slot axis K
    # ---- continuous invariant auditor + deadlines (DESIGN.md §14) ----
    # device audit word folded into the wire (monotonicity, compaction,
    # range, survivor-count) + sampled host spot checks each level
    # (downward closure, DFS-code canonicality); violations raise
    # AuditError, a state-class fault the supervisor heals by replay
    audit: bool = True
    audit_samples: int = 2              # host spot checks per level
    # watchdog phase-deadline policy: deadline = max(floor, slack·EWMA)
    # of recent level wall-times; floor=0 with no EWMA sample = unarmed
    # (the first level usually contains compilation)
    level_deadline_floor: float = 0.0
    level_deadline_slack: float = 8.0

    def __post_init__(self):
        if self.pipeline not in PIPELINES:
            raise ValueError(f"pipeline={self.pipeline!r} must be one of "
                             f"{PIPELINES}")
        if self.candgen not in CANDGENS:
            raise ValueError(f"candgen={self.candgen!r} must be one of "
                             f"{CANDGENS}")
        if self.embedding_store not in STORES:
            raise ValueError(f"embedding_store={self.embedding_store!r} "
                             f"must be one of {STORES}")
        if self.embedding_store == "spill":
            if self.pipeline != "single_sync":
                raise ValueError(
                    f"embedding_store='spill' runs on pipeline="
                    f"'single_sync' only, got {self.pipeline!r}")
            if self.checkpoint_dir:
                raise ValueError(
                    "embedding_store='spill' takes no checkpoint_dir — a "
                    "checkpoint holds the dense store only, and the spill "
                    "table would not survive a resume")
        if self.n_partitions < 1:
            raise ValueError(
                f"n_partitions={self.n_partitions} must be >= 1")
        if self.reduce is None:
            self.reduce = ("psum" if self.pipeline == "legacy"
                           else "reduce_scatter")
        if self.reduce not in ("psum", "reduce_scatter"):
            raise ValueError(f"reduce={self.reduce!r} must be 'psum' or "
                             f"'reduce_scatter'")
        if self.packed_support and self.pipeline == "legacy":
            raise ValueError(
                "packed_support=True is unavailable on pipeline='legacy' — "
                "the legacy pipeline stays dense as the differential oracle")
        if self.pipeline == "device_loop":
            if self.max_size is None:
                raise ValueError(
                    "pipeline='device_loop' needs a finite max_size — the "
                    "while_loop carry (codes, OL store, run outputs) is "
                    "shaped by the run's maximum pattern size")
            if not self.bucket_shapes:
                raise ValueError(
                    "pipeline='device_loop' requires bucket_shapes=True — "
                    "its static budgets are sized in the bucket families")
            if not self.escalate_on_overflow:
                raise ValueError(
                    "pipeline='device_loop' requires escalate_on_overflow "
                    "— the loop mines at one uniform M and reruns doubled "
                    "on overflow, matching only the exact (escalated) "
                    "host semantics")
        if self.level_deadline_slack < 1.0:
            raise ValueError(
                f"level_deadline_slack={self.level_deadline_slack} must "
                f"be >= 1 — a sub-unit slack trips on every level")
        if self.pipeline == "device_loop" or self.candgen == "device":
            # device candgen makes host speculation structurally
            # impossible mid-loop — disable it statically (satellite:
            # the cost gate is bypassed, no PendingLevel speculation)
            self.overlap_candgen = False


@dataclasses.dataclass
class LevelStats:
    level: int
    n_candidates: int
    n_frequent: int
    overflow: int
    seconds: float
    map_seconds: float
    rebalanced: bool
    imbalance: float                    # max/mean partition embed-count
    escalations: int = 0                # M-cap doublings the valve performed
    # host candgen seconds for the NEXT level, spent in the shadow of
    # this level's in-flight device program (0.0 when not overlapped)
    candgen_seconds: float = 0.0
    survivor_cap: int = 0               # S the level program compacted into
    retried: bool = False               # level took a materialize-only retry
    # rows and (pattern, graph) pairs the spill store's table holds after
    # the level (0 under the dense store)
    spill_rows: int = 0
    spill_pairs: int = 0


@dataclasses.dataclass
class DistMiningResult:
    levels: list[list[Code]]
    supports: dict[Code, int]
    stats: list[LevelStats]
    alphabet: EdgeAlphabet
    minsup: int
    total_overflow: int

    @property
    def frequent(self) -> dict[Code, int]:
        return self.supports

    def counts(self) -> list[int]:
        return [len(l) for l in self.levels]


@dataclasses.dataclass
class PartialResult:
    """A verified *prefix* of the full answer (anytime contract, §14).

    MIRAGE's level-synchronous loop makes every completed level a
    complete, valid answer to "all frequent subgraphs up to size k" —
    so when the supervisor's retry budget, degradation ladder, or run
    deadline is exhausted, it cuts here: the frequent set through the
    newest intact *audited* checkpoint, re-verified by
    :func:`~repro.core.auditor.audit_frequent_set` before it is
    trusted.  ``complete`` is always False (the marker callers branch
    on); ``audited`` is False only for the trivially valid empty prefix
    (no surviving checkpoint)."""

    levels: list[list[Code]]
    supports: dict[Code, int]
    minsup: Optional[int]
    last_level: int                     # deepest audited complete level
    reason: str                         # "deadline" | "budget-exhausted"
    audited: bool
    complete: bool = False
    events: list[dict] = dataclasses.field(default_factory=list)

    @property
    def frequent(self) -> dict[Code, int]:
        return self.supports

    def counts(self) -> list[int]:
        return [len(l) for l in self.levels]


def decode_saved_levels(state: dict) -> tuple[list[list[Code]],
                                              dict[Code, int]]:
    """Decode a checkpoint's (levels, supports) arrays back into codes —
    shared by resume and the supervisor's partial-result cut."""
    levels = [[array_to_code(a) for a in lvl] for lvl in state["levels"]]
    supports = {array_to_code(a): int(s) for a, s in
                zip(state["support_codes"], state["support_vals"])}
    return levels, supports


@dataclasses.dataclass
class _LevelOutcome:
    """What one mined level hands back to the driver loop, identical for
    both pipelines."""

    gsup: np.ndarray            # (C,) global supports, canonical order
    keep: np.ndarray            # survivor candidate indices
    pol: jnp.ndarray            # next-level OL store (compact survivors)
    pmask: jnp.ndarray
    src: jnp.ndarray            # edge store (repacked iff rebalanced)
    dst: jnp.ndarray
    emask: jnp.ndarray
    overflow: int
    max_embeddings: int         # M after any escalation
    rebalanced: bool
    imbalance: float
    perm: Optional[np.ndarray]  # applied partition permutation (or None)
    map_seconds: float
    escalations: int
    retried: bool = False       # level took a materialize-only retry
    survivor_cap: int = 0       # S the level program was dispatched with
    # candidates for the NEXT level, speculatively generated from ALL of
    # this level's candidates while the device program was in flight;
    # the driver narrows them to the surviving parents (None = not
    # speculated — regenerate from F_{k+1} as usual)
    spec_cands: Optional[list[Candidate]] = None
    candgen_seconds: float = 0.0
    # device audit word from the wire (0 = every invariant held; the
    # legacy pipeline computes no word and always reports 0)
    audit: int = 0
    donated: bool = False       # the parent store was donated
    # the speculation gate's decision: "taken", "skipped" (the estimate
    # outran its window) or "none" (no speculation attempted)
    spec: str = "none"
    # the spill store's next-level table (rows, keys) and its size
    # (None / 0 under the dense store)
    spill: Optional[tuple] = None
    spill_rows: int = 0
    spill_pairs: int = 0


class Mirage:
    """The distributed miner.  ``mesh=None`` uses a single-device mesh
    (tests/CPU); production passes ``MiningMesh(make_production_mesh())``.
    """

    def __init__(self, config: MirageConfig,
                 mesh: Optional[MiningMesh] = None):
        self.cfg = config
        self.mesh = mesh or MiningMesh.single_device()
        # introspection for the last device-loop run (tests + residency
        # gate): {"completed": bool, "fallback": Optional[str], ...};
        # None until a device_loop fit has executed
        self.last_device_loop: Optional[dict] = None
        # per-run invariant auditor (§14); rebuilt by each fit() once
        # minsup and the DB graph count are known
        self.auditor: Optional[Auditor] = None
        self._watchdog: Optional[Watchdog] = None
        self._ckpt_meta: dict = {}
        self._graphs_axis: Optional[int] = None   # store G, set by fit()
        if config.n_partitions % self.mesh.n_workers:
            raise ValueError(
                f"n_partitions={config.n_partitions} must be a multiple of "
                f"the worker count {self.mesh.n_workers}")

    # ------------------------------------------------------------------
    def _effective_partitions(self, n_graphs: int) -> int:
        """Clamp n_partitions to the database size (a partition with no
        graphs would silently pad) while staying a multiple of the
        worker count."""
        cfg, W = self.cfg, self.mesh.n_workers
        if n_graphs == 0 or cfg.n_partitions <= n_graphs:
            return cfg.n_partitions
        clamped = max(W, n_graphs - n_graphs % W)
        if clamped > n_graphs:
            raise ValueError(
                f"database has {n_graphs} graphs but the mesh has {W} "
                f"workers — need at least one graph per worker")
        return clamped

    # ------------------------------------------------------------------
    def fit(self, graphs: Sequence[Graph], *, resume: bool = False,
            watchdog: Optional[Watchdog] = None,
            deadline_s: Optional[float] = None) -> DistMiningResult:
        """Mine ``graphs``; the run is the ``mirage:fit`` span, its
        phases the spans of ``runtime/tracing.py``."""
        cfg = self.cfg
        with tracing.Span("fit", counts=tracing.FIT_COUNTS,
                          n_graphs=len(graphs), minsup=cfg.minsup,
                          pipeline=cfg.pipeline) as run:
            result = self._fit(graphs, resume=resume, watchdog=watchdog,
                               deadline_s=deadline_s)
            run.set(levels=len(result.levels))
            return result

    # the paper's verb; the supervisor wraps this entrypoint
    mine = fit

    def _fit(self, graphs: Sequence[Graph], *, resume: bool,
             watchdog: Optional[Watchdog],
             deadline_s: Optional[float]) -> DistMiningResult:
        cfg = self.cfg

        # peek the checkpoint first: the partition count is baked into
        # the saved OL store, and the clamp below depends on the mesh —
        # a resume must reproduce the WRITER's partitioning, not
        # re-derive one from the (possibly different) current mesh
        resume_state = resume_meta = None
        if resume and cfg.checkpoint_dir and ckpt.latest_step(cfg.checkpoint_dir):
            try:
                resume_state, resume_meta = ckpt.load_step(cfg.checkpoint_dir)
            except FileNotFoundError:
                # every on-disk step failed integrity verification and
                # was reaped — a fresh start is the only sound option
                resume_state = resume_meta = None

        # ---- phase 1: partition (host) --------------------------------
        if resume_state is not None:
            n_parts = int(resume_state["pol"].shape[0])
            if n_parts % self.mesh.n_workers:
                raise ValueError(
                    f"checkpoint holds {n_parts} partitions, not a "
                    f"multiple of the current worker count "
                    f"{self.mesh.n_workers} — resume on a compatible mesh")
        else:
            n_parts = self._effective_partitions(len(graphs))
        with tracing.Span("partition", n_parts=n_parts):
            part = make_partitions(graphs, cfg.minsup, n_parts,
                                   scheme=cfg.scheme)
        alphabet, minsup = part.alphabet, part.minsup
        triples = sorted({t for c in alphabet.canonical()
                          for t in (c, (c[2], c[1], c[0]))})
        if not triples:
            return DistMiningResult([], {}, [], alphabet, minsup, 0)

        # ---- §14 run plumbing: auditor + deadline watchdog -------------
        n_graphs = part.n_graphs
        self.auditor = (Auditor(minsup=minsup, n_graphs=n_graphs,
                                samples=cfg.audit_samples)
                        if cfg.audit else None)
        wd = watchdog
        if wd is None and deadline_s is not None:
            wd = Watchdog(deadline_s,
                          phase_floor=cfg.level_deadline_floor,
                          phase_slack=cfg.level_deadline_slack)
        self._watchdog = wd
        if wd is not None:
            wd.start()
        # checkpoint metadata the supervisor's partial-result cut reads:
        # a step is a candidate cut point only when it was written by an
        # auditing run (and its prefix re-verifies on load)
        self._ckpt_meta = {"audited": bool(cfg.audit),
                           "minsup": int(minsup),
                           "n_graphs": int(n_graphs)}

        # ---- phase 2: preparation (host, once) -------------------------
        G = max((len(p) for p in part.partitions), default=1)
        backend = cfg.backend or default_backend()
        spilling = cfg.embedding_store == "spill"
        if is_fused_backend(backend) and not backend.endswith("interpret"):
            # a lane-aligned graph axis keeps G minor-most in XLA's TPU
            # layout of every store, so the kernel's graph-minor views
            # stay bitcasts (padded graphs carry all-False masks)
            G = round_up_multiple(G, LANES)
        self._graphs_axis = G
        with tracing.Span("edge_ol_build") as sp:
            eols = [build_edge_ol(p, triples, pad_graphs=G,
                                  max_occ=cfg.max_occ)
                    for p in part.partitions]
            F = max(e.src.shape[-1] for e in eols)
            src = np.stack([_pad_f(e.src, F, -1) for e in eols])   # (NP,T,G,F)
            dst = np.stack([_pad_f(e.dst, F, -1) for e in eols])
            emask = np.stack([_pad_f(e.mask, F, False) for e in eols])
            # the spill join's view of the same occurrences
            adjacency = (build_adjacency(src, dst, emask) if spilling
                         else ())
            sp.set(F=F)
        eol0 = eols[0]   # triple_index identical across partitions

        codes = [((0, 1, a, e, b),) for (a, e, b) in alphabet.canonical()]
        # level-1 embeddings/graph are bounded by F (the edge-OL width), so
        # M1 = F is exact by construction — no silent truncation at level 1.
        bk = self._buckets()
        M1 = max(cfg.max_embeddings, F)
        if bk is not None:
            M1 = bk.embeddings(M1, cfg.max_embeddings)
        with tracing.Span("level1", codes=len(codes)):
            lvl1 = [level1_ol(codes, e, max_embeddings=M1) for e in eols]
            pol = np.stack([np.asarray(l.ol) for l in lvl1])   # (NP,P,G,M,2)
            pmask = np.stack([np.asarray(l.mask) for l in lvl1])
            if bk is not None:
                # bucket the level-1 store into the same (P, K) family
                # the child stores live in, so the level-2 program is
                # often THE program every later level reuses
                pol, pmask = _pad_store(
                    pol, pmask, p_to=bucket_size(len(codes), bk.s_floor),
                    k_to=bk.vertex_slots(2))

            supports: dict[Code, int] = {}
            for c in codes:
                ti = eol0.triple_index[c[0][2:]]
                supports[c] = int(emask[:, ti].any(axis=-1).sum())
        levels: list[list[Code]] = [list(codes)]
        stats: list[LevelStats] = []
        total_overflow = 0
        start_level = 1
        M = cfg.max_embeddings

        # ---- resume (elastic: mesh may differ from writer's) ----------
        if resume_state is not None:
            state = resume_state
            levels, supports = decode_saved_levels(state)
            pol, pmask = state["pol"], state["pmask"]
            start_level = int(resume_meta["step"])
            M = int(state["max_embeddings"])
            total_overflow = int(state["total_overflow"])
            # checkpoints store the CANONICAL (unpadded) survivor store;
            # re-bucket it into the CURRENT config's family — the writer
            # may have used different floors (or none)
            pol, pmask = self._repad_saved(pol, pmask)

        extra = ()
        if spilling:
            extra = (*empty_spill(n_parts, pol.shape[-1], _SPILL_R_FLOOR),
                     *adjacency)
        (pol, pmask, src_d, dst_d, emask_d, *extra) = self._device_put(
            pol, pmask, src, dst, emask, *extra)
        spill, adjacency = tuple(extra[:2]), tuple(extra[2:])

        # cumulative partition permutation from straggler rebalancing;
        # checkpoints always store the OL store in CANONICAL order so a
        # resumed run (which rebuilds edge-OLs canonically) stays aligned
        order = np.arange(n_parts)
        # per-level (n_parents, n_candidates, n_keep) history drives the
        # next level's compaction cap from the measured per-parent fanout
        # (single-sync pipeline); empty = no history yet
        history: list[tuple[int, int, int]] = []
        # bit-packed support path: the 2x-uint16 wire slice needs every
        # global support to fit uint16 — supports are bounded by |G|
        packed = self._packed_support(part.n_graphs)
        # fused tile_c, pinned ONCE per run from the level-2 candidate
        # grouping: per-level adaptive widths would reshape the tile
        # schedule (and recompile the level program) every level
        tile_pin: Optional[int] = None
        # donation re-arming: a resumed run already has a rebuildable
        # checkpoint; a fresh run earns one at its first _save
        policy = DonationPolicy(
            cfg.donation_rearm_levels,
            can_rebuild=bool(cfg.checkpoint_dir) and resume_state is not None)

        # ---- device-resident whole-run loop (DESIGN.md §13) ------------
        if cfg.pipeline == "device_loop" and start_level < cfg.max_size:
            try:
                with tracing.Span("device_loop") as run:
                    return self._mine_device_loop(
                        alphabet, minsup, triples, eol0, levels, supports,
                        pol, pmask, src_d, dst_d, emask_d, packed=packed,
                        start_k=start_level, total_overflow=total_overflow,
                        order=order, run=run)
            except dloop.DeviceLoopFallback as bail:
                # a static budget tripped (or the M valve hit its
                # ceiling): replay the run through the per-level
                # pipeline below — it has no static budgets and mines
                # the identical frequent set (§10 ladder, rung 2)
                self.last_device_loop = {"completed": False,
                                         "fallback": str(bail),
                                         "chunks": 0, "escalations": 0}

        # ---- phase 3: iterative mining ---------------------------------
        k = start_level
        # overlapped candgen (DESIGN.md §11): each single-sync level
        # speculatively generates the NEXT level's candidates while its
        # device program is in flight; the loop head narrows them to the
        # survivors and only regenerates when no speculation ran.  A
        # replayed level keeps its ``cands``.
        cands: Optional[list[Candidate]] = None
        spec: Optional[tuple[list[Candidate], np.ndarray]] = None
        # speculation cost gate inputs (see overlap_spec_window): EWMA
        # per-parent candgen rate, sampled from EVERY generation (fresh
        # and speculative), and the last level's device-only seconds
        cand_rate: Optional[float] = None
        prev_dev = 0.0
        while cfg.max_size is None or k < cfg.max_size:
            with tracing.Span("level", counts={"compiles": "compiles"},
                              level=k + 1) as lvl:
                if wd is not None:
                    # cooperative run-deadline check at the loop head — the
                    # only place a DeadlineExceeded can safely unwind from
                    wd.check_run(level=k + 1)
                if cands is None:
                    with tracing.Span("candgen",
                                      counts=tracing.CANON_COUNTS,
                                      parents=len(levels[-1])) as cg:
                        fresh = False
                        if spec is not None:
                            # provably equal to generate_candidates(F_{k+1}),
                            # see filter_speculative
                            cands = filter_speculative(*spec)
                        elif cfg.candgen == "device":
                            # the stepping-stone device candgen: one jitted
                            # device_candidates dispatch instead of the host
                            # generator (None = per-level budget overflow →
                            # fall back to the host generator for this level)
                            cands = self._device_candgen(levels[-1], triples)
                        if cands is None:
                            cands = generate_candidates(levels[-1], alphabet)
                            fresh = True
                        cg.set(candidates=len(cands))
                    if fresh and levels[-1]:
                        r = cg.seconds / len(levels[-1])
                        cand_rate = (r if cand_rate is None
                                     else 0.5 * (cand_rate + r))
                if not cands:
                    break
                # chaos hook: a scheduled worker death at this level
                faults.maybe_raise("level_start", k + 1)
                n_parents = len(levels[-1])
                with tracing.Span("candidate_meta"):
                    meta = candidate_meta(cands, eol0)
                    C = meta.shape[0]
                    W = self.mesh.n_workers
                    Cp = (bk.candidates(C, W) if bk is not None
                          else round_up_multiple(C, W))
                    meta_p = np.concatenate(
                        [meta, np.tile([[0, 0, 0, 1, 0]], (Cp - C, 1))]
                    ).astype(np.int32)

                    # parent supports for the device audit word (§14): one
                    # int32 per parent pattern, indexed on device through the
                    # meta parent column (-1 = unknown, e.g. a resumed run
                    # whose map predates the parent) — monotonicity
                    # gsup <= psup[parent] is anti-monotone pruning's invariant
                    psup = None
                    if cfg.audit and cfg.pipeline != "legacy":
                        psup = np.array([supports.get(p, -1)
                                         for p in levels[-1]], np.int32)
                lvl.set(candidates=C, Cp=Cp)
                if wd is not None:
                    # arm the phase deadline around the device dispatch —
                    # the stretch a hang would otherwise block unobserved
                    wd.arm(level=k + 1)

                if (tile_pin is None and bk is not None
                        and cfg.pipeline != "legacy"
                        and is_fused_backend(cfg.backend)):
                    # level 2 is the widest, most parent-diverse grouping
                    # the run will see — its adaptive choice generalizes;
                    # later levels reuse it so the schedule shapes (and
                    # the compiled level program) stay fixed
                    with tracing.Span("schedule") as sp:
                        tile_pin = schedule_candidates(meta).tile_c
                        sp.set(tile_c=tile_pin)
                if cfg.pipeline == "legacy":
                    out = self._level_legacy(
                        meta_p, meta, C, pol, pmask, src_d, dst_d, emask_d,
                        minsup, M, n_parts, level=k + 1)
                elif spilling:
                    out = self._level_spill(
                        meta_p, meta, C, pol, pmask, src_d, dst_d, emask_d,
                        spill, adjacency, minsup,
                        (bk.vertex_slots(k + 2, int(pol.shape[-1]))
                         if bk is not None else None),
                        packed=packed, tile_c=tile_pin)
                    spill = out.spill
                else:
                    # child patterns (size k+1) have at most k+2 vertices;
                    # the bucketed width reuses the parent store's while the
                    # child still fits, so the arena shape repeats
                    child_width = (bk.vertex_slots(k + 2, int(pol.shape[-1]))
                                   if bk is not None else None)
                    try:
                        out = self._level_single_sync(
                            meta_p, meta, C, pol, pmask, src_d, dst_d, emask_d,
                            minsup, M, history, child_width,
                            level=k + 1, policy=policy,
                            packed=packed, tile_c=tile_pin,
                            cands=cands, alphabet=alphabet,
                            cand_rate=cand_rate,
                            spec_window=max(prev_dev,
                                            cfg.overlap_spec_window),
                            psup=psup, n_graphs=n_graphs)
                    except DonationRetryRebuild:
                        # the armed-donation gamble lost: the arena consumed
                        # the parents, so restore them from the latest intact
                        # checkpoint (canonical store re-padded + cumulative
                        # rebalance permutation re-applied) and replay
                        if wd is not None:
                            wd.disarm()
                        pol, pmask = self._rebuild_parents(order)
                        policy.record_rebuild()
                        continue
                    policy.record(out.retried)
                lvl.set(S=out.survivor_cap, M=out.max_embeddings,
                        donated=int(out.donated), retried=int(out.retried),
                        escalations=out.escalations, spec=out.spec,
                        spill_rows=out.spill_rows,
                        spill_pairs=out.spill_pairs)
                tracing.count("spill_rows", out.spill_rows)
                tracing.count("spill_pairs", out.spill_pairs)
                if wd is not None:
                    # feed the level's wall-time into the EWMA the next
                    # phase deadline is derived from
                    wd.disarm(observe_s=lvl.elapsed())
                if self.auditor is not None:
                    with tracing.Span("audit"):
                        self.auditor.check_wire(k + 1, out.audit)
                        if len(out.keep):
                            self.auditor.check_level(
                                k + 1, cands=cands, keep=out.keep,
                                gsup=out.gsup, parents=levels[-1],
                                supports=supports)
                prev_dev = max(out.map_seconds - out.candgen_seconds, 0.0)
                if out.spec_cands is not None and cands:
                    r = out.candgen_seconds / len(cands)
                    cand_rate = (r if cand_rate is None
                                 else 0.5 * (cand_rate + r))
                M = out.max_embeddings
                total_overflow += out.overflow

                if len(out.keep) == 0:
                    stats.append(LevelStats(
                        k + 1, C, 0, out.overflow, lvl.elapsed(),
                        out.map_seconds, False, out.imbalance,
                        out.escalations, out.candgen_seconds,
                        survivor_cap=out.survivor_cap, retried=out.retried))
                    break

                pol, pmask = out.pol, out.pmask
                src_d, dst_d, emask_d = out.src, out.dst, out.emask
                levels.append([cands[i].code for i in out.keep])
                for i in out.keep:
                    supports[cands[i].code] = int(out.gsup[i])
                if out.perm is not None:
                    order = order[out.perm]
                history.append((n_parents, C, len(out.keep)))

                stats.append(LevelStats(k + 1, C, len(out.keep), out.overflow,
                                        lvl.elapsed(),
                                        out.map_seconds, out.rebalanced,
                                        out.imbalance, out.escalations,
                                        out.candgen_seconds,
                                        survivor_cap=out.survivor_cap,
                                        retried=out.retried,
                                        spill_rows=out.spill_rows,
                                        spill_pairs=out.spill_pairs))

                if cfg.checkpoint_dir:
                    self._save(cfg.checkpoint_dir, k + 1, levels, supports,
                               pol, pmask, M, total_overflow, order)
                    policy.can_rebuild = True
                # this level's speculative superset (generated from ALL
                # candidates) is narrowed to the surviving parents at the
                # next loop head
                cands = None
                spec = ((out.spec_cands, out.keep)
                        if out.spec_cands is not None else None)
                k += 1

        return DistMiningResult(levels, supports, stats, alphabet, minsup,
                                total_overflow)

    # ------------------------------------------------------------------
    def _repad_saved(self, pol, pmask):
        """Re-bucket a checkpoint's canonical (padding-stripped) survivor
        store into the CURRENT config's shape family — shared by resume
        and mid-run parent rebuild — and pad its graph axis to this
        run's (a writer on another backend may have aligned it
        differently)."""
        bk = self._buckets()
        g_to = self._graphs_axis
        if bk is None:
            return _pad_store(pol, pmask, g_to=g_to)
        return _pad_store(
            pol, pmask, g_to=g_to,
            p_to=bucket_size(pol.shape[1], bk.s_floor),
            m_to=bk.embeddings(pol.shape[3], self.cfg.max_embeddings),
            k_to=bk.vertex_slots(pol.shape[-1]))

    def _rebuild_parents(self, order: np.ndarray):
        """Restore the parent OL store of the level being replayed from
        the latest intact checkpoint: canonical store → current bucket
        family → the live partition order (checkpoints are canonical;
        ``order`` is the cumulative rebalance permutation, unchanged
        since that save because rebalances apply only to levels that
        completed)."""
        state, _ = ckpt.load_step(self.cfg.checkpoint_dir)
        pol, pmask = self._repad_saved(state["pol"], state["pmask"])
        pol, pmask = pol[order], pmask[order]
        sharding = partition_sharding(self.mesh.mesh)
        return (jax.device_put(jnp.asarray(pol), sharding),
                jax.device_put(jnp.asarray(pmask), sharding))

    # ------------------------------------------------------------------
    def kernel_path(self, n_graphs: int) -> tuple[str, bool]:
        """``(backend, packed)``: the support kernel a fit over
        ``n_graphs`` graphs runs — the resolved backend name and whether
        it takes the bit-packed path."""
        return (self.cfg.backend or default_backend(),
                self._packed_support(n_graphs))

    # ------------------------------------------------------------------
    def _sharded_wire(self) -> bool:
        """Resolve the sharded-wire tri-state: explicit config wins;
        auto means on whenever the reduce_scatter shuffle runs under the
        single-sync pipeline (the support slice already lives sharded on
        each worker — gathering it just to re-slice host-side is the
        waste the layout removes).  The device-loop pipeline never
        shards: its wire is the ONE replicated run wire (a fallback run
        through ``_level_single_sync`` then uses the dense layout)."""
        cfg = self.cfg
        if cfg.pipeline != "single_sync":
            return False
        if cfg.sharded_wire is not None:
            return cfg.sharded_wire
        return cfg.reduce == "reduce_scatter"

    # ------------------------------------------------------------------
    def _packed_support(self, n_graphs: int) -> bool:
        """Resolve the packed-support tri-state: explicit config wins
        (True was validated against the legacy pipeline at construction);
        auto means default-ON for the single-sync pipeline.  Either way
        packing additionally requires every global support to fit uint16
        (the wire ships 2 supports per uint32 word) — supports are
        bounded by the database's graph count, checked here."""
        cfg = self.cfg
        if cfg.pipeline not in ("single_sync", "device_loop"):
            return False
        on = (cfg.packed_support if cfg.packed_support is not None
              else True)
        return bool(on) and n_graphs < (1 << 16)

    # ------------------------------------------------------------------
    def _buckets(self) -> Optional[BucketSpec]:
        """The run's shape-bucket family, or None when bucketing is off.
        The legacy pipeline never buckets — it is the PR-1 differential
        oracle and must stay bit-identical to it."""
        cfg = self.cfg
        if (not cfg.bucket_shapes
                or cfg.pipeline not in ("single_sync", "device_loop")):
            return None
        return BucketSpec(cfg.bucket_c_floor, cfg.bucket_s_floor,
                          cfg.bucket_k_floor)

    # ------------------------------------------------------------------
    def _survivor_cap(self, C: int, Cp: int,
                      history: list[tuple[int, int, int]]) -> int:
        """Static survivor cap for the level program's compaction stage.

        Cap padding slots are cond-gated on device (they execute a
        constant fill, not a materialization), so the cap only governs
        the child store's HBM footprint; a miss costs one
        materialize-only retry dispatch (the pass-1 supports stay
        valid).  Policy: predict the next survivor count from the
        previous level's measured per-parent fanout —
        ``keep_prev / parents_prev`` survivors per parent times the
        ``keep_prev`` parents this level mines from, scaled by the
        configured slack — or a quarter of the candidate space when
        there is no history yet.  (The earlier survival-RATIO predictor
        multiplied by the CURRENT candidate count C, which balloons with
        the parent set and over-padded the arena by the fanout squared
        on expanding runs.)

        Under shape bucketing the prediction is rounded to the S-bucket
        family and clamped at the (bucketed) Cp ceiling: a cap miss
        then retries into the NEXT family member, and near-boundary
        predictions cannot thrash between adjacent raw values — both
        would recompile the level program every flip."""
        bk = self._buckets()
        if not self.cfg.predict_survivors:
            # no prediction = no cap miss allowed: S must cover every
            # real candidate.  Bucketed, the smallest S-family member
            # >= C keeps the arena in the same shape family as the
            # parent axis instead of jumping to the C family.
            return Cp if bk is None else bk.survivors(C, Cp)
        if not history:
            s = min(Cp, max(32, -(-Cp // 4)))
        else:
            parents_prev, _cands_prev, keep_prev = history[-1]
            fanout = keep_prev / max(parents_prev, 1)
            pred = self.cfg.survivor_slack * fanout * max(keep_prev, 1)
            # n_keep <= C always, so C is a sound extra clamp
            s = min(Cp, C, max(1, int(np.ceil(pred)) + 16))
        if bk is not None:
            s = bk.survivors(s, Cp)
        return s

    # ------------------------------------------------------------------
    def _fit_cap(self, S: int, pol, M: int,
                 child_width: Optional[int]) -> int:
        """Clamp the survivor cap so the child stores it sizes fit the
        memory the mesh's devices have free.

        The cap never decides correctness — a miss costs one
        materialize-only retry of the true survivors — but every cap
        slot holds a full (G, M, W) child OL per local partition, and
        an over-predicted cap (a wide candidate level with sparse
        survival) could ask for more HBM than a chip has.  The parents
        and edge OLs are already counted in ``bytes_in_use``; beside
        them the level holds its own child store and, on an escalation
        retry, a second one at up to twice M — ``_CAP_STORES`` stores of
        the cap's size in all.  Backends that report no memory limit
        (the CPU) keep S.  Under bucketing the clamp rounds down into
        the S family; a fit below the family floor is kept as is."""
        free = _free_device_bytes(self.mesh.mesh.devices.flat)
        if free is None:
            return S
        NP, _, G, _, K = pol.shape
        width = child_width if child_width is not None else K + 1
        slot = (NP // self.mesh.n_workers) * G * M * (4 * width + 1)
        fit = max(1, free // (_CAP_STORES * slot))
        bk = self._buckets()
        if bk is not None and fit >= bk.s_floor:
            fit = bk.s_floor << ((fit // bk.s_floor).bit_length() - 1)
        return min(S, fit)

    # ------------------------------------------------------------------
    def _device_candgen(self, parents: list[Code],
                        triples: list[tuple[int, int, int]]
                        ) -> Optional[list[Candidate]]:
        """Per-level device candidate generation (candgen="device"):
        one jitted ``device_candidates`` dispatch replaces the host
        generator, returning the SAME candidates in the SAME order
        (pinned by tests/test_device_loop.py).  Budgets default to the
        exact structural bound — overflow is then impossible unless the
        config pins them tighter; any tripped flag returns None and the
        caller regenerates on host for this level only."""
        cfg = self.cfg
        SP = len(parents)
        if SP == 0:
            return []
        Lk = len(parents[0]) + 1            # child edge count
        NV = Lk + 1                         # child vertex bound
        T = len(triples)
        raw_b = cfg.device_raw_budget or SP * (2 * NV - 1) * T
        budget = cfg.device_c_budget or raw_b
        fn = device_candgen_jit(Lk, NV, raw_b, budget,
                                cfg.device_max_states)
        codes = np.full((SP, Lk, 5), -1, np.int32)
        for i, c in enumerate(parents):
            codes[i] = code_to_array(c, Lk)
        meta, child, n_cand, flags = fn(
            jnp.asarray(codes), jnp.int32(SP),
            jnp.asarray(np.asarray(triples, np.int32)))
        if bool(np.asarray(flags).any()):
            return None
        return candidates_from_arrays(np.asarray(meta), np.asarray(child),
                                      int(n_cand), triples)

    # ------------------------------------------------------------------
    def _decode_device_run(self, rw: "dloop.RunWire", levels0, supports0,
                           start_k: int):
        """Decode a run wire into (levels, supports, stat rows) with the
        host loop's exact stopping semantics: an empty candidate set
        stops BEFORE its stats row (the host breaks at the loop head),
        an empty frequent set stops AFTER it."""
        levels = [list(l) for l in levels0]
        sups = dict(supports0)
        rows: list[tuple[int, int, int, int, float]] = []
        for s in range(start_k - 1, rw.k_final - 1):
            n_cand, n_keep, ovf, imb_fx = (int(x) for x in rw.stats[s, :4])
            if n_cand == 0:
                break
            rows.append((s + 2, n_cand, n_keep, ovf, imb_fx / _IMBAL_FX))
            if n_keep == 0:
                break
            lvl = [array_to_code(rw.codes[s, i]) for i in range(n_keep)]
            levels.append(lvl)
            for i, c in enumerate(lvl):
                sups[c] = int(rw.sups[s, i])
        return levels, sups, rows

    # ------------------------------------------------------------------
    def _mine_device_loop(self, alphabet, minsup, triples, eol0, levels0,
                          supports0, pol, pmask, src, dst, emask, *,
                          packed: bool, start_k: int, total_overflow: int,
                          order: np.ndarray,
                          run: tracing.Span) -> DistMiningResult:
        """The whole run as ONE jitted ``lax.while_loop`` program
        (core/device_loop.py, DESIGN.md §13).

        Candidate generation, schedule, support counting, survivor
        compaction and child materialization all stay on device for
        every level; the host sees exactly ONE run-wire transfer (plus
        wire+store fetches at the optional checkpoint-chunk boundaries).
        Static budgets are sized once from a single host candidate
        generation at the start level — the ONLY host candgen of a
        completed run (pinned by the satellite regression test); a
        budget overflow mid-run trips a bail flag and this method raises
        :class:`~.device_loop.DeviceLoopFallback` so the caller replays
        through the per-level pipeline.

        The exactness valve hoists to run granularity: the loop mines at
        one uniform embedding cap M (the carry shape); an overflowing
        run doubles M and reruns the whole program from the base store —
        pre-overflow levels are bit-identical at the larger M, so the
        rerun converges to the exact escalated host semantics."""
        cfg = self.cfg
        bk = self._buckets()
        W = self.mesh.n_workers
        backend = cfg.backend or default_backend()
        L = cfg.max_size
        NL = L - 1
        NV = bk.vertex_slots(L + 1)

        # ---- static budgets from one host generation ------------------
        base = generate_candidates(levels0[-1], alphabet)
        if not base:
            return DistMiningResult(levels0, supports0, [], alphabet,
                                    minsup, total_overflow)
        meta0 = candidate_meta(base, eol0)
        C0 = meta0.shape[0]
        CB = round_up_multiple(cfg.device_c_budget
                               or bk.candidates(4 * C0, W), W)
        CBR = cfg.device_raw_budget or 4 * CB
        SPP = max(bucket_size(len(levels0[-1]), bk.s_floor), CB)
        tile_c, ROWS = 1, CB
        if is_fused_backend(backend):
            sched0 = schedule_candidates(meta0)
            tile_c = sched0.tile_c
            ROWS = round_up_multiple(
                bucket_size(max(2 * sched0.meta.shape[0], CB), bk.c_floor),
                tile_c)

        prog = dloop._run_program(
            self.mesh, minsup, backend, cfg.reduce, packed, L, NV, CB,
            CBR, cfg.device_max_states, NL, tile_c, ROWS, len(triples),
            cfg.device_loop_unroll)

        # ---- device-resident carry ------------------------------------
        trip_a = jnp.asarray(np.asarray(triples, np.int32))
        codes_h = np.full((SPP, L, 5), -1, np.int32)
        for i, c in enumerate(levels0[-1]):
            codes_h[i] = code_to_array(c, L)
        n_par0 = len(levels0[-1])
        sharding = partition_sharding(self.mesh.mesh)
        pol0, pmask0 = _pad_store(pol, pmask, p_to=SPP, k_to=NV)
        pol0 = jax.device_put(jnp.asarray(pol0), sharding)
        pmask0 = jax.device_put(jnp.asarray(pmask0), sharding)
        M_run = int(pol0.shape[3])
        oc0 = jnp.asarray(np.full((NL, SPP, L, 5), -1, np.int32))
        os0 = jnp.asarray(np.zeros((NL, SPP), np.int32))
        ost0 = jnp.asarray(np.zeros((NL, dloop.NSTAT), np.int32))

        cadence = ckpt.ChunkCadence(start_k, L,
                                    cfg.device_loop_ckpt_every)
        escalations = chunks = 0
        pol_b, pmask_b = pol0, pmask0
        rw = carry = None
        wd = self._watchdog
        while True:                 # run-granular escalation valve
            carry = (jnp.int32(start_k), jnp.int32(n_par0),
                     jnp.asarray(codes_h), trip_a, pol_b, pmask_b,
                     src, dst, emask, oc0, os0, ost0,
                     jnp.asarray(True), jnp.int32(0))
            k_cur, escalate = start_k, False
            for k_stop in cadence.boundaries():
                if wd is not None:
                    # each ChunkCadence re-invocation doubles as a
                    # heartbeat: the run-deadline check fires here, and
                    # the phase deadline re-arms over the coming chunk
                    wd.check_run(level=k_stop)
                    wd.arm(level=k_stop)
                with tracing.Span("chunk", level=k_stop) as chunk:
                    for lv in range(k_cur + 1, k_stop + 1):
                        # chaos hooks, fired host-side per window level
                        # so fault schedules hit device-loop runs too
                        faults.maybe_raise("level_start", lv)
                        faults.maybe_raise("kernel", lv)
                    calls = (1 if cfg.device_loop_unroll <= 0 else
                             -(-(k_stop - k_cur) // cfg.device_loop_unroll))
                    for _ in range(calls):
                        out = prog(jnp.int32(k_stop), *carry)
                        carry = (out[1], out[2], out[3], trip_a, out[4],
                                 out[5], src, dst, emask, out[6], out[7],
                                 out[8], out[9], out[10])
                    chunks += 1
                    # chaos hook: a stalled chunk — the armed phase
                    # deadline (and the device_loop→single_sync rung)
                    # bounds it
                    faults.maybe_hang("chunk", k_stop, wd)
                    # the chunk boundary's (only) host contact
                    body = fetch_wire(out[0], level=k_stop)
                    rw = dloop.decode_run_wire(body, NL, SPP, L)
                k_cur = k_stop
                if wd is not None:
                    wd.disarm(observe_s=chunk.seconds)
                if not rw.ok:
                    break
                # overflow first: a run that reached its fixpoint
                # (n_par == 0) may still have capped embeddings on the way
                if (rw.total_overflow > 0
                        and M_run < cfg.max_embeddings_limit):
                    escalate = True
                    break
                if rw.n_par == 0:
                    break
                if cfg.checkpoint_dir and k_cur < L:
                    levels, sups, _ = self._decode_device_run(
                        rw, levels0, supports0, start_k)
                    if self.auditor is not None:
                        # a boundary save is a potential partial-result
                        # cut point: audit the whole decoded prefix
                        # BEFORE it reaches disk as "audited"
                        self.auditor.check_levels(levels, sups)
                    self._save(cfg.checkpoint_dir, k_cur, levels, sups,
                               np.asarray(carry[4]), np.asarray(carry[5]),
                               M_run,
                               total_overflow + rw.total_overflow, order)
            if not escalate:
                break
            M_run = min(M_run * 2, cfg.max_embeddings_limit)
            escalations += 1
            pol_b, pmask_b = _pad_store(pol0, pmask0, m_to=M_run)
            pol_b = jax.device_put(jnp.asarray(pol_b), sharding)
            pmask_b = jax.device_put(jnp.asarray(pmask_b), sharding)

        if not rw.ok:
            bad = int(np.bitwise_or.reduce(
                rw.stats[:, 4].astype(np.int64)))
            raise dloop.DeviceLoopFallback(
                f"device loop bailed at level {rw.k_final} "
                f"(flags=0b{bad:04b}: CB={CB} CBR={CBR} "
                f"states={cfg.device_max_states} rows={ROWS})")
        if rw.total_overflow > 0:
            raise dloop.DeviceLoopFallback(
                f"M-cap overflow {rw.total_overflow} persists at the "
                f"max_embeddings_limit={cfg.max_embeddings_limit} ceiling")

        levels, sups, rows = self._decode_device_run(
            rw, levels0, supports0, start_k)
        if self.auditor is not None:
            self.auditor.check_levels(levels, sups)
        tovf = total_overflow + rw.total_overflow
        per = run.elapsed() / max(len(rows), 1)
        stats = [LevelStats(lv, nc, nk, ov, per, per, False, imb,
                            escalations if i == 0 else 0,
                            survivor_cap=SPP)
                 for i, (lv, nc, nk, ov, imb) in enumerate(rows)]
        if cfg.checkpoint_dir and rw.n_par > 0:
            # the carry store row-aligns with levels[-1] only when the
            # run ended WITH survivors; a zero-survivor tail keeps the
            # last boundary save instead
            self._save(cfg.checkpoint_dir, len(levels), levels, sups,
                       np.asarray(carry[4]), np.asarray(carry[5]),
                       M_run, tovf, order)
        self.last_device_loop = {
            "completed": True, "fallback": None, "chunks": chunks,
            "escalations": escalations, "c_budget": CB,
            "raw_budget": CBR, "sched_rows": ROWS, "spp": SPP,
            "max_embeddings": M_run, "n_levels": NL, "tile_c": tile_c,
        }
        return DistMiningResult(levels, sups, stats, alphabet, minsup,
                                tovf)

    def _level_single_sync(self, meta_p, meta, C, pol, pmask, src, dst,
                           emask, minsup, M, history,
                           child_width: Optional[int] = None, *,
                           level: Optional[int] = None,
                           policy: Optional[DonationPolicy] = None,
                           cands: Optional[list[Candidate]] = None,
                           alphabet: Optional[EdgeAlphabet] = None,
                           cand_rate: Optional[float] = None,
                           spec_window: Optional[float] = None,
                           packed: bool = False,
                           tile_c: Optional[int] = None,
                           psup: Optional[np.ndarray] = None,
                           n_graphs: int = -1
                           ) -> _LevelOutcome:
        """One level through the device-resident program: a single
        dispatch and a single device→host sync on the wire vector.

        The dispatch is asynchronous (:class:`~.level_step.PendingLevel`):
        with ``overlap_candgen`` the host generates the NEXT level's
        candidates from this level's FULL candidate list (a superset of
        the frequent set — per-parent generation is independent, so the
        driver later narrows it exactly) while the device program runs,
        and blocks on the wire only afterwards.  The speculation only
        runs when its estimated cost (``cand_rate`` seconds/parent ×
        the superset size) fits the ``spec_window`` it would hide in —
        at sparse survival the superset is many times the frequent set
        and generating it would cost far more than it saves.

        Exceptional paths re-use the still-valid pass-1 supports and fall
        back to the cheap materialize-only program from the preserved
        inputs: a survivor-cap miss re-materializes the full survivor
        set, and the escalation valve re-materializes at a doubled M.
        Donation is engaged when no such retry is possible — or when the
        re-arming policy is armed (enough clean levels + a rebuildable
        checkpoint); an armed level that then DOES need its retry raises
        :class:`DonationRetryRebuild` instead, because donation already
        consumed the parents."""
        cfg = self.cfg
        bk = self._buckets()
        Cp = meta_p.shape[0]
        backend = cfg.backend or default_backend()
        S = self._survivor_cap(C, Cp, history)
        S = self._fit_cap(S, pol, M, child_width)
        # chaos hook: a cap-miss storm forces a pathological cap, driving
        # every hit level through the materialize-only retry path
        S = faults.override_cap(S, level)
        # a cap miss needs n_keep > S, and n_keep <= C always — S >= C
        # rules the retry out even when S sits below the padded Cp
        may_retry = (S < C or (cfg.escalate_on_overflow
                               and M < cfg.max_embeddings_limit))
        donated = cfg.donate and (not may_retry
                                  or (policy is not None and policy.armed))
        pending = dispatch_level(
            self.mesh, meta_p, C, pol, pmask, src, dst, emask,
            minsup=minsup, backend=backend, reduce=cfg.reduce,
            max_embeddings=M, survivor_cap=S,
            rebalance=cfg.rebalance, threshold=cfg.rebalance_threshold,
            donate=donated,
            child_width=child_width,
            sched_floor=bk.c_floor if bk is not None else None,
            level=level, sharded=self._sharded_wire(),
            packed=packed, tile_c=tile_c,
            psup=psup, n_graphs=n_graphs)
        # chaos hook: an injected stall while the program is in flight —
        # the watchdog's armed phase deadline is what bounds it
        faults.maybe_hang("dispatch", level, self._watchdog)
        # the overlap window: the device program is in flight, the host
        # is free — speculate the next level's candidates now
        spec_cands = None
        cand_secs = 0.0
        spec = "none"
        if cfg.overlap_candgen and cands is not None and alphabet is not None:
            window = (cfg.overlap_spec_window if spec_window is None
                      else spec_window)
            est = (cand_rate or 0.0) * len(cands)
            spec = "skipped"
            if est <= window:
                spec = "taken"
                with tracing.Span("candgen_spec",
                                  counts=tracing.CANON_COUNTS, est_s=est,
                                  window_s=window) as sp:
                    spec_cands = generate_candidates(
                        [c.code for c in cands], alphabet)
                cand_secs = sp.seconds
        out = pending.finish()
        w = out.wire

        keep = np.flatnonzero(w.gsup >= minsup)
        n = int(w.n_keep)
        overflow = w.overflow
        escalations = 0
        if bk is None:
            new_pol = out.pol[:, :max(n, 1)]
            new_pmask = out.pmask[:, :max(n, 1)]
        else:
            # keep the full S-bucket arena: slicing to the survivor
            # count would hand the next level a fresh shape (and a
            # fresh compile) every time n moves
            new_pol, new_pmask = out.pol, out.pmask

        escalatable = (cfg.escalate_on_overflow
                       and M < cfg.max_embeddings_limit)
        retried = bool(n > 0 and (n > S or (overflow > 0 and escalatable)))
        if retried:
            if donated:
                # armed-donation gamble lost: the parents are gone (the
                # arena aliased them) — the driver rebuilds from
                # checkpoint and replays this level
                raise DonationRetryRebuild(level if level is not None else -1)
            if overflow > 0 and escalatable:
                # the program just proved M too small (for a cap miss,
                # on a subset of survivors — still a proof): skip the
                # known-bad M before re-materializing
                M = min(M * 2, cfg.max_embeddings_limit)
                escalations += 1
            new_pol, new_pmask, overflow, M, esc = self._materialize_exact(
                jnp.asarray(meta[keep]), pol, pmask, src, dst, emask, M,
                out_width=child_width)
            escalations += esc
            if bk is not None:
                # re-bucket the retried store so the next level stays in
                # the family (the cap miss means n outgrew S's bucket)
                new_pol, new_pmask = _pad_store(
                    new_pol, new_pmask, p_to=bk.survivors(len(keep), Cp))

        if w.rebalanced and n > 0:
            # apply the wire-reported LPT permutation on device (no sync)
            new_pol, new_pmask, src, dst, emask = permute_stores(
                self.mesh, w.perm, new_pol, new_pmask, src, dst, emask)

        return _LevelOutcome(
            gsup=w.gsup, keep=keep, pol=new_pol, pmask=new_pmask,
            src=src, dst=dst, emask=emask,
            overflow=overflow, max_embeddings=M,
            rebalanced=w.rebalanced and n > 0, imbalance=w.imbalance,
            perm=w.perm if (w.rebalanced and n > 0) else None,
            map_seconds=out.seconds, escalations=escalations,
            retried=retried, survivor_cap=S, spec_cands=spec_cands,
            candgen_seconds=cand_secs, audit=int(w.audit),
            donated=donated, spec=spec)

    # ------------------------------------------------------------------
    def _level_spill(self, meta_p, meta, C, pol, pmask, src, dst, emask,
                     spill, adjacency, minsup,
                     child_width: Optional[int] = None, *,
                     packed: bool = False,
                     tile_c: Optional[int] = None) -> _LevelOutcome:
        """One level over the spill store (DESIGN.md §15): the support
        round over the dense store and the spill table, the survivors
        fetched, then the materialization's count pass (its only fetch is
        the child table's size, which sizes the write pass) and write
        pass.  M stays fixed: no escalation, no retry, and nothing is
        cut off.  The partition order never changes (no rebalance)."""
        cfg = self.cfg
        bk = self._buckets()
        backend = cfg.backend or default_backend()
        Cp = meta_p.shape[0]
        M = cfg.max_embeddings
        with tracing.Span("schedule") as sched_span:
            by_parent, cpos = _by_parent(meta, pol.shape[1], Cp)
            sched = schedule_args(meta_p, C, backend, tile_c,
                                  bk.c_floor if bk is not None else None,
                                  sched_span)
        with tracing.Span("dispatch", counts={"compiles": "compiles"}):
            gsup_d = spill_supports(
                self.mesh, meta_p, sched, by_parent, cpos, pol, pmask, src,
                dst, emask, spill, adjacency, backend=backend,
                packed=packed)
        with tracing.Span("wire_wait"):
            gsup_d.block_until_ready()
        with tracing.Span("wire_decode"):
            gsup = np.asarray(gsup_d)[:C]
        keep = np.flatnonzero(gsup >= minsup)
        out = _LevelOutcome(
            gsup=gsup, keep=keep, pol=pol, pmask=pmask, src=src, dst=dst,
            emask=emask, overflow=0, max_embeddings=M, rebalanced=False,
            imbalance=1.0, perm=None, map_seconds=0.0, escalations=0,
            spill=spill)
        if len(keep):
            n = len(keep)
            S = bk.survivors(n, Cp) if bk is not None else n
            cmeta = np.concatenate(
                [meta[keep], np.tile([[0, 0, 0, 1, 0]], (S - n, 1))]
            ).astype(np.int32)
            width = (child_width if child_width is not None
                     else int(pol.shape[-1]) + 1)
            with tracing.Span("spill_count") as sp:
                counts, rows, pairs = spill_counts(
                    self.mesh, cmeta, n, pol, pmask, spill, adjacency,
                    max_embeddings=M, n_triples=int(src.shape[1]))
                out.spill_rows = int(rows.sum())
                out.spill_pairs = int(pairs.sum())
                sp.set(rows=out.spill_rows, pairs=out.spill_pairs)
            with tracing.Span("spill_write"):
                (out.pol, out.pmask, *new_spill) = spill_materialize(
                    self.mesh, cmeta, n, counts, pol, pmask, src, dst,
                    emask, spill, adjacency, max_embeddings=M,
                    out_width=width,
                    out_rows=_spill_capacity(rows,
                                             int(spill[0].shape[1])))
            out.spill = tuple(new_spill)
            out.survivor_cap = S
        out.map_seconds = (time.time_ns() - sched_span.start_ns) / 1e9
        return out

    # ------------------------------------------------------------------
    def _level_legacy(self, meta_p, meta, C, pol, pmask, src, dst, emask,
                      minsup, M, n_parts, *,
                      level: Optional[int] = None) -> _LevelOutcome:
        """The PR-1 driver: separate support and materialize programs
        with host round-trips between them (keep list, escalation loop,
        LPT detour).  Kept as differential oracle + benchmark baseline."""
        cfg = self.cfg
        with tracing.Span("dispatch") as sp:
            gsup, verdict, emb_pp = map_reduce_supports(
                self.mesh, meta_p, pol, pmask, src, dst, emask,
                minsup=minsup, backend=cfg.backend, reduce=cfg.reduce)
            faults.maybe_hang("dispatch", level, self._watchdog)
        map_secs = sp.seconds

        keep = np.flatnonzero(verdict[:C] != 0)
        if len(keep) == 0:
            return _LevelOutcome(
                gsup=gsup[:C], keep=keep, pol=pol, pmask=pmask,
                src=src, dst=dst, emask=emask, overflow=0,
                max_embeddings=M, rebalanced=False, imbalance=1.0,
                perm=None, map_seconds=map_secs, escalations=0)

        keep_meta = jnp.asarray(meta[keep])
        pol, pmask, overflow, M, escalations = self._materialize_exact(
            keep_meta, pol, pmask, src, dst, emask, M)

        # ---- straggler rebalance (cost signal: embed counts) -----------
        cost = emb_pp.reshape(n_parts, -1).sum(-1).astype(np.float64)
        imbal = _imbalance(cost, self.mesh.n_workers)
        rebalanced = False
        perm = None
        if (cfg.rebalance and self.mesh.n_workers > 1
                and imbal > cfg.rebalance_threshold):
            perm = _lpt_order(cost, self.mesh.n_workers)
            take = lambda a: jnp.take(a, jnp.asarray(perm), axis=0)
            pol, pmask = take(pol), take(pmask)
            src, dst, emask = take(src), take(dst), take(emask)
            rebalanced = True
        return _LevelOutcome(
            gsup=gsup[:C], keep=keep, pol=pol, pmask=pmask,
            src=src, dst=dst, emask=emask, overflow=overflow,
            max_embeddings=M, rebalanced=rebalanced, imbalance=imbal,
            perm=perm, map_seconds=map_secs, escalations=escalations)

    # ------------------------------------------------------------------
    def _materialize_exact(self, keep_meta, pol, pmask, src, dst, emask, M,
                           out_width: Optional[int] = None):
        """Materialize survivors; escalate M until no overflow (exactness
        valve — keeps device supports == paper semantics)."""
        cfg = self.cfg
        escalations = 0
        with tracing.Span("retry_materialize") as sp:
            while True:
                new_pol, new_pmask, overflow = map_materialize(
                    self.mesh, keep_meta, pol, pmask, src, dst, emask,
                    max_embeddings=M, out_width=out_width)
                if (overflow == 0 or not cfg.escalate_on_overflow
                        or M >= cfg.max_embeddings_limit):
                    sp.set(escalations=escalations, M=M)
                    return new_pol, new_pmask, overflow, M, escalations
                M = min(M * 2, cfg.max_embeddings_limit)
                escalations += 1

    def _device_put(self, pol, pmask, src, dst, emask, *extra):
        sharding = partition_sharding(self.mesh.mesh)
        arrays = (pol, pmask, src, dst, emask, *extra)
        with tracing.Span("upload", bytes=sum(x.nbytes for x in arrays)):
            return tuple(jax.device_put(jnp.asarray(x), sharding)
                         for x in arrays)

    def _save(self, root, level, levels, supports, pol, pmask, M, overflow,
              order):
        with tracing.Span("checkpoint", level=level):
            # invert the cumulative rebalance permutation: checkpoints hold
            # the OL store in canonical partition order (resume rebuilds the
            # edge-OL store canonically and must stay row-aligned)
            inv = np.empty_like(order)
            inv[order] = np.arange(len(order))
            max_edges = max(len(c) for l in levels for c in l)
            pol_np, pmask_np = np.asarray(pol)[inv], np.asarray(pmask)[inv]
            # checkpoints hold the CANONICAL store: bucket padding is
            # stripped (pattern axis to the true survivor count, vertex axis
            # to the widest real pattern) so a resume under different bucket
            # floors — or none — re-pads into ITS family without inheriting
            # the writer's.  Unbucketed stores pass through unchanged.
            n_real = max(len(levels[-1]), 1)
            pol_np, pmask_np = pol_np[:, :n_real], pmask_np[:, :n_real]
            if self._buckets() is not None:
                kw = 1 + max(max(i, j) for c in levels[-1]
                             for (i, j, _a, _e, _b) in c)
                pol_np = pol_np[..., :kw]
            state = {
                "levels": [[code_to_array(c, max_edges) for c in l]
                           for l in levels],
                "support_codes": [code_to_array(c, max_edges)
                                  for c in supports],
                "support_vals": np.asarray(list(supports.values()), np.int64),
                "pol": pol_np,
                "pmask": pmask_np,
                "max_embeddings": M,
                "total_overflow": overflow,
            }
            # metadata the supervisor's partial-result cut branches on:
            # "audited" marks steps written by an auditing run (the only
            # levels a PartialResult may ever cut at), minsup + n_graphs
            # parameterize the load-time re-audit
            ckpt.save_step(root, level, state,
                           metadata={"kind": "mirage-mining",
                                     **self._ckpt_meta})


def _free_device_bytes(devices) -> Optional[int]:
    """Least free memory (``bytes_limit - bytes_in_use``) over
    ``devices``, or None where a backend reports no limit (the CPU)."""
    free = []
    for dev in devices:
        stats = dev.memory_stats() or {}
        if not stats.get("bytes_limit"):
            return None
        free.append(stats["bytes_limit"] - stats.get("bytes_in_use", 0))
    return min(free)


def _pad_store(pol, pmask, *, p_to: Optional[int] = None,
               m_to: Optional[int] = None, k_to: Optional[int] = None,
               g_to: Optional[int] = None):
    """Grow an OL store (NP, P, G, M, K)/(NP, P, G, M) into its bucket:
    PAD(-1) vertex entries, all-False masks.  Padded slots are inert —
    no candidate references a padded parent, masked embeddings never
    join, PAD vertex slots never match, padded graphs hold nothing.
    Works on numpy or device arrays (np.pad falls back to jnp dispatch
    via asarray semantics)."""
    xp = np if isinstance(pol, np.ndarray) else jnp

    def pad(a, axis, to):
        cur = a.shape[axis]
        if to is None or to <= cur:
            return a
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, to - cur)
        fill = -1 if a.dtype == xp.int32 else False
        return xp.pad(a, widths, constant_values=fill)

    pol = pad(pad(pad(pad(pol, 1, p_to), 2, g_to), 3, m_to), 4, k_to)
    pmask = pad(pad(pad(pmask, 1, p_to), 2, g_to), 3, m_to)
    return pol, pmask


def _spill_capacity(rows: np.ndarray, current: int) -> int:
    """Rows of the next spill table: the smallest 1024·4^i holding every
    partition's ``rows`` and twice their mean, never fewer than the
    ``current`` table's.  The mean is the same whatever the partitioning
    (every seed of a DB holds the same embeddings), so the sizes, and
    the programs compiled for them, rarely move with the graphs' order."""
    need = max(int(rows.max()), -(-2 * int(rows.sum()) // len(rows)))
    r = _SPILL_R_FLOOR
    while r < need:
        r *= 4
    return max(r, current)


def _by_parent(meta: np.ndarray, n_parents: int, Cp: int
               ) -> tuple[np.ndarray, np.ndarray]:
    """The candidates of each parent, for the spill join: ``by_parent``
    (n_parents, Kc) candidate indices, -1 padded, Kc bucketed; and
    ``cpos`` (Cp,) each candidate's flat index ``parent·Kc + j`` there,
    -1 past the C real ones."""
    parent = meta[:, 0].astype(np.int64)
    C = len(parent)
    order = np.argsort(parent, kind="stable")
    first = np.searchsorted(parent[order], parent[order], side="left")
    j = np.empty(C, np.int64)
    j[order] = np.arange(C) - first
    kc = bucket_size(int(j.max()) + 1 if C else 1, _SPILL_KC_FLOOR)
    by_parent = np.full((n_parents, kc), -1, np.int32)
    by_parent[parent, j] = np.arange(C)
    cpos = np.full(Cp, -1, np.int32)
    cpos[:C] = parent * kc + j
    return by_parent, cpos


def _pad_f(a: np.ndarray, F: int, fill) -> np.ndarray:
    pad = F - a.shape[-1]
    if pad == 0:
        return a
    widths = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
    return np.pad(a, widths, constant_values=fill)


def _imbalance(cost: np.ndarray, w: int) -> float:
    """max/mean of per-worker cost under the current blocked assignment."""
    per_worker = cost.reshape(w, -1).sum(-1)
    mean = per_worker.mean()
    return float(per_worker.max() / mean) if mean > 0 else 1.0


def _lpt_order(cost: np.ndarray, w: int) -> np.ndarray:
    """Re-pack partitions into w balanced blocks (LPT), then emit the
    permutation that lays blocks contiguously (matching the blocked
    dim-0 sharding)."""
    np_total = len(cost)
    per = np_total // w
    buckets: list[list[int]] = [[] for _ in range(w)]
    load = np.zeros(w)
    for i in np.argsort(-cost):
        # lightest bucket with room
        order = np.argsort(load)
        for b in order:
            if len(buckets[b]) < per:
                buckets[b].append(int(i))
                load[b] += cost[i]
                break
    return np.asarray([i for b in buckets for i in b], np.int32)
