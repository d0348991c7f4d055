"""Spans and counters of a fit: where each second of ``Mirage.fit`` goes.

Every phase of a fit runs inside a :class:`Span`, a
``jax.profiler.TraceAnnotation`` named ``mirage:<phase>`` that also reads
the clock once as it opens and once as it closes.  With no profiler
recording, the annotation costs one check (under a microsecond), so the
spans are always on: ``LevelStats`` takes its times from them, and a
profiler trace (``jax.profiler.trace``, or ``launch/mine.py --profile``)
shows them on the host timeline, their args as event stats.  The clock
is ``time.time_ns``, the one the profiler stamps host events with, so a
span's reading and its event in a trace agree.

The phases (args in brackets; "levels"/"compiles"/... at close):

  fit               the whole fit [n_graphs, minsup, pipeline; levels,
                    compiles, wire_fetches, gc_gen2, gc_s, canon_tested,
                    canon_early, spill_rows, spill_pairs]
  partition         ``make_partitions`` [n_parts]
  edge_ol_build     the per-partition edge OLs, padded and stacked [F,
                    occurrences, truncated: the directed occurrences
                    placed, and those past ``max_occ``]
  level1            the level-1 OLs and supports [codes]
  upload            the stores' host-to-device copy [bytes]
  level             one level of the mining loop [level, candidates, Cp,
                    S, M, donated, retried, escalations, spec, spill_rows,
                    spill_pairs, compiles]
  candgen           the loop-head candidates: generated, or narrowed from
                    the previous level's speculation [parents, candidates;
                    tested, early]
  candgen_spec      the speculative candgen in the level program's
                    shadow [est_s, window_s; tested, early]
  candidate_meta    candidate metadata, its padding and the parent
                    supports for the device audit
  schedule          the host side of a level dispatch: the fused kernel's
                    tile schedule and the argument arrays [rows, tile_c]
  dispatch          the call of the jitted level program [compiles]
  wire_wait         waiting for the device to finish the level's wire
  wire_decode       the wire's transfer, checksum and decode [attempts]
  retry_materialize a materialize-only retry [escalations, M]
  spill_count       the spill store's count pass and its fetch of the
                    child table's size [rows, pairs]
  spill_write       the spill store's write pass (its dispatch)
  permute           the rebalance's store permutation
  audit             the auditor's host checks
  checkpoint        a checkpoint save
  device_loop       a device_loop run; chunk, one of its program chunks
  gc                a generation-2 collection of Python's GC [collected]

Counters are process-wide totals; a span given ``counts`` reports each
one's change over the span as an arg:

  compiles      jit lowerings (one per jit cache miss), from one
                ``jax.monitoring`` listener
  wire_fetches  level-wire transfers, re-fetches after a checksum
                mismatch included
  gc_gen2       generation-2 collections; gc_s their seconds
  canon_tested  raw candidates ``generate_candidates`` put through the
                canonicality walk (span arg ``tested``; 0 when the
                candidates were narrowed from a speculation)
  canon_early   those of them the walk rejected before their last
                position, on a smaller prefix (span arg ``early``)
  spill_rows    rows the spill store's table holds after each level,
                summed over the levels (level span arg, per level)
  spill_pairs   (pattern, graph) pairs it holds, likewise

Device work is named by ``jax.named_scope`` inside the programs
(``mirage/support_kernel``, ``mirage/reduce``, ``mirage/compact``,
``mirage/audit``, ``mirage/materialize``, ``mirage/wire_pack``,
``mirage/permute``, and under the spill store ``mirage/spill_support``
and ``mirage/spill_materialize``, never inside another ``mirage/``
scope): the scope rides in each HLO op's ``op_name`` metadata, and
changes nothing else of the compiled program.
"""
from __future__ import annotations

import gc
import time
from typing import Optional

import jax

__all__ = ["PREFIX", "FIT_COUNTS", "CANON_COUNTS", "Span", "count"]

PREFIX = "mirage:"

_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"

_totals = {"compiles": 0, "wire_fetches": 0, "gc_gen2": 0, "gc_s": 0.0,
           "canon_tested": 0, "canon_early": 0, "spill_rows": 0,
           "spill_pairs": 0}

#: the counters the candgen spans report, as args ``tested`` and ``early``
CANON_COUNTS = {"tested": "canon_tested", "early": "canon_early"}

#: the counters the ``fit`` span reports, under their own names
FIT_COUNTS = {name: name for name in _totals}


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    _totals[name] += n


class Span:
    """One phase of a fit: a profiler annotation and its clock readings.

    ``counts`` maps an arg name to a counter whose change over the span
    is set as that arg when the span closes.  ``start_ns``/``end_ns``
    are ``time.time_ns`` readings; ``seconds`` is the closed span's
    length and ``elapsed()`` the open span's age."""

    __slots__ = ("start_ns", "end_ns", "_counts", "_ann", "_base")

    def __init__(self, phase: str, counts: Optional[dict] = None, **args):
        self._counts = counts or {}
        self.start_ns = self.end_ns = 0
        self._ann = jax.profiler.TraceAnnotation(PREFIX + phase, **args)
        self._base: dict = {}

    def __enter__(self) -> "Span":
        self._base = {arg: _totals[c] for arg, c in self._counts.items()}
        self._ann.__enter__()
        self.start_ns = time.time_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.time_ns()
        if self._counts:
            self._ann.set_metadata(**{
                arg: _totals[c] - self._base[arg]
                for arg, c in self._counts.items()})
        self._ann.__exit__(*exc)

    def set(self, **args) -> None:
        """Attach args to the span's trace event (ints, floats, strs)."""
        self._ann.set_metadata(**args)

    def elapsed(self) -> float:
        """Seconds since the span opened."""
        return (time.time_ns() - self.start_ns) / 1e9

    @property
    def seconds(self) -> float:
        """Length of the closed span, in seconds."""
        return (self.end_ns - self.start_ns) / 1e9


def _on_event(name: str, _secs: float, **_kw) -> None:
    if name == _LOWERING:
        count("compiles")


_gc_span: Optional[Span] = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if info["generation"] != 2:
        return
    if phase == "start":
        _gc_span = Span("gc").__enter__()
    elif _gc_span is not None:
        sp, _gc_span = _gc_span, None
        sp.set(collected=info["collected"])
        sp.__exit__(None, None, None)
        count("gc_gen2")
        count("gc_s", sp.seconds)


jax.monitoring.register_event_duration_secs_listener(_on_event)
gc.callbacks.append(_on_gc)
