"""Supervised recovery driver around :meth:`Mirage.mine` (DESIGN.md §10, §14).

MIRAGE inherits MapReduce's contract: iterations are restartable because
level state hits durable storage between them, so the *job* survives
what kills a *task*.  This module is that job-level supervisor for the
JAX runtime.  It classifies every failure the mining loop can surface —
injected or real — and applies one of five recoveries:

  worker_loss  → elastically shrink the worker pool (largest divisor of
                 n_partitions below the current W, floored at
                 ``min_workers``) and resume from the latest intact
                 checkpoint; PR 2's canonical unsharded checkpoints make
                 the re-layout free.  When no smaller mesh exists the
                 level is simply replayed on the same mesh.
  kernel       → retry; after ``degrade_after`` kernel faults descend
                 the degradation ladder ``fused → pallas → legacy``
                 (rung 1 swaps the fused single-launch kernel for the
                 two-launch pallas/interpret backend; rung 2 abandons
                 the single-sync program for the legacy host-driven
                 pipeline, which dispatches no fused kernel at all).
                 A ``pipeline="device_loop"`` run gets one extra rung
                 FIRST: abandon the whole-run loop for the per-level
                 single-sync program, which re-syncs (and re-checks)
                 every level instead of once per run.
  transient    → (wire checksum failures and other flaky-link signals)
                 retry with exponential backoff, same configuration.
  state        → (checkpoint integrity, audit failures) retry: the
                 store has already reaped the corrupt step, so the next
                 attempt resumes from the newest *intact* one — or
                 restarts clean.
  hang         → (watchdog-detected stalled phase, DESIGN.md §14) a
                 device_loop run descends its single_sync rung
                 immediately — the per-level program re-syncs every
                 level, bounding any future stall to one level; other
                 pipelines replay from the newest checkpoint.

Anything unclassified is **fatal** and re-raised untouched: a
supervisor that swallows real bugs would poison every chaos guarantee.

**Unified retry budget** (§14): every recovery class draws from ONE
jittered-exponential-backoff :class:`RetryBudget`, so a fault storm of
mixed kinds cannot loop forever.  Budget exhaustion — like a run
deadline (:class:`~repro.runtime.faults.DeadlineExceeded`, never
retried) — routes into the **anytime contract**: with
``on_exhausted="partial"`` the supervisor returns a
:class:`~repro.core.mining.PartialResult` cut at the newest intact
*audited* checkpoint (re-verified through
``auditor.audit_frequent_set`` before it is trusted) instead of
raising; ``"raise"`` (the default) preserves the strict behavior.

Every decision is recorded as a structured :class:`FaultEvent` and —
crash-safely — appended to ``fault_log_path`` as one JSON line per
event the moment it happens (a hard kill still leaves a usable log);
an end-of-run summary line closes the file.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Optional, Sequence, Union

import numpy as np

from ..runtime import checkpoint as ckpt
from ..runtime import faults, jax_compat
from ..runtime.watchdog import Watchdog
from .auditor import audit_frequent_set
from .graphdb import Graph
from .mapreduce import MiningMesh
from .mining import (DistMiningResult, Mirage, MirageConfig,
                     PartialResult, decode_saved_levels)

__all__ = ["SupervisorConfig", "FaultEvent", "MiningSupervisor",
           "RetryBudget", "classify", "elastic_shrink", "ladder_for"]

#: degradation-ladder rungs, most- to least-accelerated.  Each entry is
#: the config override applied at that rung; rung 0 is "as configured".
LADDER = ("as-configured", "pallas", "legacy")

#: the device-loop pipeline descends one extra rung first: give up the
#: whole-run while_loop for the per-level single-sync program (same
#: kernels, but a host sync — and a fresh chance — every level)
DEVICE_LOOP_LADDER = ("as-configured", "single_sync", "pallas", "legacy")


def ladder_for(cfg: MirageConfig) -> tuple[str, ...]:
    """The degradation ladder the ORIGINAL config starts from."""
    return (DEVICE_LOOP_LADDER if cfg.pipeline == "device_loop"
            else LADDER)


def classify(exc: BaseException) -> Optional[str]:
    """Map an exception to a recovery class, or None for fatal."""
    if isinstance(exc, faults.WorkerLost):
        return "worker_loss"
    if isinstance(exc, faults.KernelFault):
        return "kernel"
    if isinstance(exc, faults.HangTimeout):
        return "hang"
    if isinstance(exc, faults.WireIntegrityError):
        return "transient"
    if isinstance(exc, (faults.CheckpointIntegrityError,
                        faults.AuditError)):
        return "state"
    return None


def elastic_shrink(workers: int, n_partitions: int,
                   min_workers: int = 1) -> Optional[int]:
    """Largest viable worker count below ``workers``: the partition
    count must stay divisible (blocked dim-0 sharding), so this is the
    largest divisor of ``n_partitions`` in [min_workers, workers)."""
    for w in range(workers - 1, min_workers - 1, -1):
        if n_partitions % w == 0:
            return w
    return None


@dataclasses.dataclass
class RetryBudget:
    """One unified retry budget shared by every recovery class.

    ``spend(kind)`` charges one attempt and returns the jittered
    exponential backoff to sleep — or None when the budget is
    exhausted, which is exactly what routes the supervisor into the
    partial-result path.  Jitter is seeded (deterministic chaos runs):
    ``backoff = min(base·factor^(n-1), cap) · (1 + jitter·u)``,
    u ~ U[0, 1)."""

    max_attempts: int = 5
    base: float = 0.05
    factor: float = 2.0
    cap: float = 2.0
    jitter: float = 0.25
    seed: int = 0

    def __post_init__(self):
        self.attempt = 0
        self.by_kind: dict = {}
        self._rng = np.random.default_rng(self.seed)

    @property
    def exhausted(self) -> bool:
        return self.attempt >= self.max_attempts

    def spend(self, kind: str) -> Optional[float]:
        if self.exhausted:
            return None
        self.attempt += 1
        self.by_kind[kind] = self.by_kind.get(kind, 0) + 1
        backoff = min(self.base * self.factor ** (self.attempt - 1),
                      self.cap)
        if backoff > 0 and self.jitter > 0:
            backoff *= 1.0 + self.jitter * float(self._rng.random())
        return backoff


@dataclasses.dataclass
class SupervisorConfig:
    max_retries: int = 5                # unified retry budget
    backoff_base: float = 0.05          # seconds before attempt 2
    backoff_factor: float = 2.0
    backoff_max: float = 2.0
    backoff_jitter: float = 0.25        # jitter fraction on each backoff
    seed: int = 0                       # jitter rng seed (determinism)
    degrade_after: int = 2              # kernel faults per ladder rung
    min_workers: int = 1                # elastic-shrink floor
    deadline_s: Optional[float] = None  # whole-run wall-clock budget
    on_exhausted: str = "raise"         # "raise" | "partial" (DESIGN §14)
    sleep_fn: Callable[[float], None] = time.sleep
    fault_log_path: Optional[str] = None

    def __post_init__(self):
        if self.on_exhausted not in ("raise", "partial"):
            raise ValueError(
                f"on_exhausted must be 'raise' or 'partial', "
                f"got {self.on_exhausted!r}")


@dataclasses.dataclass
class FaultEvent:
    """One supervisor decision, structured for the fault log."""

    attempt: int
    kind: str                           # recovery class (or "fatal")
    error: str                          # repr of the triggering exception
    level: Optional[int]                # mining level, when known
    action: str                         # retry | shrink | degrade |
    detail: str                         #   partial | give_up
    backoff: float

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class MiningSupervisor:
    """Run :meth:`Mirage.mine` to completion through faults.

    ``mesh`` seeds the worker pool (default single-device);
    ``mesh_factory(n_workers)`` builds the shrunken mesh on worker loss
    — the default takes the first n of ``jax.devices()``.  Recovery is
    only cheap with ``config.checkpoint_dir`` set (resume replays at
    most one level); without it every retry restarts from scratch,
    which is still correct, just slower.  ``watchdog`` injects a
    pre-built :class:`Watchdog` (tests pin ``phase_default`` for
    deterministic hang detection); by default one is built from
    ``deadline_s`` + the config's phase-deadline knobs and spans every
    retry — the run deadline is wall-clock, not per-attempt.
    """

    def __init__(self, config: MirageConfig,
                 sup: Optional[SupervisorConfig] = None,
                 mesh: Optional[MiningMesh] = None,
                 mesh_factory: Optional[Callable[[int], MiningMesh]] = None,
                 watchdog: Optional[Watchdog] = None):
        self.config = config
        self.sup = sup or SupervisorConfig()
        self.mesh = mesh or MiningMesh.single_device()
        self.mesh_factory = mesh_factory or _default_mesh_factory
        self.events: list[FaultEvent] = []
        self.audit_report: list[dict] = []
        self.rung = 0
        self.watchdog = watchdog
        self.budget: Optional[RetryBudget] = None
        self.last_miner: Optional[Mirage] = None
        self._log_open = False

    # ------------------------------------------------------------------
    def mine(self, graphs: Sequence[Graph], *, resume: bool = False,
             deadline_s: Optional[float] = None
             ) -> Union[DistMiningResult, PartialResult]:
        sup = self.sup
        cfg = self.config
        mesh = self.mesh
        ladder = ladder_for(cfg)
        deadline = deadline_s if deadline_s is not None else sup.deadline_s
        wd = self.watchdog
        if wd is None:
            wd = Watchdog(run_deadline_s=deadline,
                          phase_floor=cfg.level_deadline_floor,
                          phase_slack=cfg.level_deadline_slack,
                          on_trip=self._log_line)
        elif wd.on_trip is None:
            wd.on_trip = self._log_line
        self.watchdog = wd
        wd.start()
        budget = self.budget = RetryBudget(
            max_attempts=sup.max_retries, base=sup.backoff_base,
            factor=sup.backoff_factor, cap=sup.backoff_max,
            jitter=sup.backoff_jitter, seed=sup.seed)
        kernel_faults = 0
        try:
            while True:
                miner = Mirage(cfg, mesh)
                self.last_miner = miner
                try:
                    result = miner.mine(
                        graphs, resume=resume or budget.attempt > 0,
                        watchdog=wd)
                    self._finish_log("complete")
                    return result
                except faults.DeadlineExceeded as exc:
                    # never retried: the clock cannot be argued with
                    partial = sup.on_exhausted == "partial"
                    self._record(budget.attempt, "deadline", exc,
                                 "partial" if partial else "give_up",
                                 "run deadline exceeded — cutting at the "
                                 "newest audited checkpoint"
                                 if partial else
                                 "run deadline exceeded", 0.0)
                    if partial:
                        return self._partial(cfg, "deadline")
                    self._finish_log("deadline")
                    raise
                except Exception as exc:                  # noqa: BLE001
                    kind = classify(exc)
                    if kind is None:
                        self._record(budget.attempt, "fatal", exc,
                                     "give_up",
                                     "unclassified failure — re-raised",
                                     0.0)
                        self._finish_log("fatal")
                        raise
                    backoff = budget.spend(kind)
                    if backoff is None:
                        partial = sup.on_exhausted == "partial"
                        self._record(
                            budget.attempt, kind, exc,
                            "partial" if partial else "give_up",
                            f"retry budget ({sup.max_retries}) "
                            f"exhausted", 0.0)
                        if partial:
                            return self._partial(cfg, "budget-exhausted")
                        self._finish_log("exhausted")
                        raise
                    action, detail = "retry", "same configuration"

                    if kind == "worker_loss":
                        w = elastic_shrink(mesh.n_workers,
                                           cfg.n_partitions,
                                           sup.min_workers)
                        if w is not None:
                            mesh = self.mesh_factory(w)
                            action = "shrink"
                            detail = (f"elastic shrink to {w} worker(s), "
                                      f"resume from checkpoint")
                        else:
                            detail = (f"no viable mesh below "
                                      f"{mesh.n_workers} worker(s) — "
                                      f"replay on the same mesh")
                    elif kind == "kernel":
                        kernel_faults += 1
                        if (kernel_faults % sup.degrade_after == 0
                                and self.rung < len(ladder) - 1):
                            self.rung += 1
                            cfg = _degrade(cfg, ladder[self.rung])
                            action = "degrade"
                            detail = (f"descend ladder to rung "
                                      f"{self.rung} "
                                      f"({ladder[self.rung]})")
                    elif kind == "hang":
                        waited = getattr(exc, "waited_s", 0.0)
                        if (cfg.pipeline == "device_loop"
                                and self.rung < len(ladder) - 1):
                            # a stalled chunk forfeits the whole-run
                            # loop: the single-sync rung re-syncs every
                            # level, bounding any future stall
                            self.rung = max(self.rung, 1)
                            cfg = _degrade(cfg, ladder[self.rung])
                            action = "degrade"
                            detail = (f"stalled device_loop chunk "
                                      f"(detected after {waited:.2f}s) — "
                                      f"descend to "
                                      f"{ladder[self.rung]}")
                        else:
                            detail = (f"stalled phase detected after "
                                      f"{waited:.2f}s — replay from "
                                      f"newest checkpoint")
                    elif kind == "state":
                        detail = ("corrupt or audit-failed state — "
                                  "resume from newest intact audited "
                                  "step (or restart clean)")

                    self._record(budget.attempt, kind, exc, action,
                                 detail, backoff)
                    rem = wd.run_remaining()
                    if rem is not None and rem <= 0:
                        continue          # let the deadline path fire
                    if backoff > 0:
                        if rem is not None:
                            backoff = min(backoff, max(rem, 0.0))
                        sup.sleep_fn(backoff)
        finally:
            if self.last_miner is not None and self.last_miner.auditor:
                self.audit_report.extend(self.last_miner.auditor.report)

    # ------------------------------------------------------------------
    def _partial(self, cfg: MirageConfig, reason: str) -> PartialResult:
        """Cut a verified partial result at the newest intact *audited*
        checkpoint: load (digest-verified), decode, and re-audit the
        whole frequent-set prefix before trusting it.  With no surviving
        checkpoint the result is the (trivially valid) empty prefix."""
        levels: list = []
        supports: dict = {}
        last_level, audited, minsup = 0, False, None
        if cfg.checkpoint_dir:
            for step in sorted(ckpt.all_steps(cfg.checkpoint_dir),
                               reverse=True):
                path = os.path.join(cfg.checkpoint_dir,
                                    f"step_{step:010d}")
                try:
                    state, meta = ckpt.load_pytree(path)
                except Exception:
                    continue              # corrupt/unreadable: skip down
                if not meta.get("audited"):
                    continue              # only ever cut at audited levels
                try:
                    lv, sp = decode_saved_levels(state)
                    ms = meta.get("minsup")
                    audit_frequent_set(lv, sp, ms,
                                       n_graphs=meta.get("n_graphs", -1))
                except Exception:
                    continue              # failed re-audit: keep walking
                levels, supports = lv, sp
                last_level, audited, minsup = int(step), True, ms
                break
        result = PartialResult(
            levels=levels, supports=supports, minsup=minsup,
            last_level=last_level, reason=reason, audited=audited,
            events=[e.as_dict() for e in self.events])
        self._finish_log(f"partial:{reason}")
        return result

    # ------------------------------------------------------------------
    def _record(self, attempt: int, kind: str, exc: BaseException,
                action: str, detail: str, backoff: float) -> None:
        ev = FaultEvent(
            attempt=attempt, kind=kind, error=repr(exc),
            level=getattr(exc, "level", None),
            action=action, detail=detail, backoff=backoff)
        self.events.append(ev)
        self._log_line(ev.as_dict())

    def _log_line(self, payload: dict) -> None:
        """Crash-safe structured log: one JSON line, flushed on write.
        The first line of a run truncates any stale file."""
        if not self.sup.fault_log_path:
            return
        mode = "a" if self._log_open else "w"
        self._log_open = True
        try:
            with open(self.sup.fault_log_path, mode) as f:
                f.write(json.dumps(payload) + "\n")
                f.flush()
        except OSError:
            pass                          # logging must never kill mining

    def _finish_log(self, outcome: str) -> None:
        self._log_line({"summary": {
            "outcome": outcome, "rung": self.rung,
            "n_events": len(self.events),
            "by_kind": dict(self.budget.by_kind) if self.budget else {},
            "watchdog_trips": len(self.watchdog.trips)
            if self.watchdog else 0}})


def _degrade(cfg: MirageConfig, rung: str) -> MirageConfig:
    """Config override for a degradation-ladder rung, by rung NAME.

    "single_sync" abandons the whole-run device loop for the per-level
    program (same kernels and shapes, one sync per level).  "pallas"
    keeps the current pipeline but drops the fused single-launch kernel
    for the two-launch backend ("pallas" on TPU, its "interpret" twin
    elsewhere).  "legacy" falls all the way back to the host-driven
    pipeline on the "ref" backend — the differential oracle, which
    dispatches no custom kernel at all; a spill-store run, which only
    single_sync can mine, takes the "ref" backend on its own pipeline.
    """
    import jax

    if rung == "as-configured":
        return cfg
    if rung == "single_sync":
        return dataclasses.replace(cfg, pipeline="single_sync")
    if rung == "pallas":
        on_tpu = jax.default_backend() == "tpu"
        pipeline = ("single_sync" if cfg.pipeline == "device_loop"
                    else cfg.pipeline)
        return dataclasses.replace(
            cfg, pipeline=pipeline,
            backend="pallas" if on_tpu else "interpret")
    if cfg.embedding_store == "spill":
        return dataclasses.replace(cfg, backend="ref", packed_support=None)
    return dataclasses.replace(cfg, pipeline="legacy", backend="ref",
                               packed_support=None)


def _default_mesh_factory(n_workers: int) -> MiningMesh:
    import jax

    devices = jax.devices()[:n_workers]
    return MiningMesh(jax_compat.make_mesh(
        (n_workers,), ("w",), devices=devices))
