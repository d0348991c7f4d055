"""The program's own phases in a JAX profiler trace: host spans and
device scopes.

``Mirage.fit`` runs each host phase inside a ``mirage:<phase>`` span (a
``TraceAnnotation`` whose args come back as event stats) and names the
stages of its device programs with ``jax.named_scope``
(``mirage/<stage>`` in each HLO op's ``op_name``);
``src/repro/runtime/tracing.py`` lists both.  The spans are read with
``jax.profiler.ProfileData``.  A TPU's ``XLA Ops`` events carry no
op_name of their own, so an op's scope is looked up in its program's
optimized ``HloProto`` on the ``/host:metadata`` plane: a TPU v5 lite
trace holds them even with ``ProfileOptions.enable_hlo_proto`` off (the
CPU's only with it on).  An op's program is the ``XLA Modules`` event
it lies in on its chip, named like the proto's entry there
(``jit_core(<id>)``).  The protos are parsed with TensorFlow's
``xplane_pb2`` and ``hlo_pb2`` (about 12 s to import); a trace without
them, or of a program without scopes, has no scopes.

    python bench/phases.py TRACE.xplane.pb[.gz]

prints a trace's time by phase, by level and phase, and by device
scope, the readings below, and chip 0's longest idle gaps named by the
program span in them.  Times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
import re
import sys
from pathlib import Path
from typing import Iterable, Optional

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

PREFIX = "mirage:"
MODULE_LINE = "XLA Modules"
PROTO_STAT = b"Hlo Proto"
_SCOPE = re.compile(r"(?:^|/)mirage/([A-Za-z_]+)")

#: phases read by each program-span reading
PREP = ("partition", "edge_ol_build", "level1", "upload")
CANDGEN = ("candgen", "candidate_meta", "schedule")
#: spans that hold other spans; every other phase is a leaf
OUTER = ("fit", "level", "device_loop")

Span = tuple   # (phase, start_ns, duration_ns, args)
Op = tuple     # (HLO text, start_ns, duration_ns, scope or "")


@dataclasses.dataclass
class Phases:
    spans: list[Span]                   # the program's spans, by start
    ops: dict[int, list[Op]]            # chip id -> its ops, by start
    scoped: bool                        # some op has a mirage/ scope


def _scope(op_name: str) -> str:
    m = _SCOPE.search(op_name)
    return m.group(1) if m else ""


def _scope_maps(data: bytes) -> dict[str, dict[str, str]]:
    """Program name -> {HLO instruction name: scope}, from the HloProtos
    of the ``/host:metadata`` plane."""
    from tensorflow.compiler.xla.service import hlo_pb2
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(data)
    out: dict[str, dict[str, str]] = {}
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        for md in plane.event_metadata.values():
            for stat in md.stats:
                if not stat.bytes_value:
                    continue
                proto = hlo_pb2.HloProto()
                proto.ParseFromString(stat.bytes_value)
                out[md.name] = {
                    ins.name: _scope(ins.metadata.op_name)
                    for comp in proto.hlo_module.computations
                    for ins in comp.instructions}
    return out


def _within(modules: list[tuple[int, int, str]], start: int) -> str:
    """The name of the module event that holds ``start``, or ""."""
    i = bisect.bisect_right(modules, (start, float("inf"), "")) - 1
    if i >= 0 and start < modules[i][1]:
        return modules[i][2]
    return ""


def load(path: str | Path) -> Phases:
    """Read the program's spans, and its device ops with their scopes,
    from an ``.xplane.pb`` file or a gzipped one."""
    from jax.profiler import ProfileData

    data = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        data = gzip.decompress(data)
    maps = _scope_maps(data) if PROTO_STAT in data else {}
    pd = ProfileData.from_serialized_xspace(data)
    spans: list[Span] = []
    ops: dict[int, list[Op]] = {}
    for plane in pd.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                for e in (lines[MODULE_LINE].events
                          if MODULE_LINE in lines else []))
            evs = []
            for e in (lines[trace.OP_LINE].events
                      if trace.OP_LINE in lines else []):
                start = int(e.start_ns)
                scopes = maps.get(_within(modules, start), {})
                evs.append((e.name, start, int(e.duration_ns),
                            scopes.get(trace.op_name(e.name), "")))
            ops[int(m.group(1))] = sorted(evs, key=lambda e: (e[1], -e[2]))
        elif plane.name == trace.HOST_PLANE:
            spans.extend((e.name[len(PREFIX):], int(e.start_ns),
                          int(e.duration_ns), dict(e.stats))
                         for line in plane.lines for e in line.events
                         if e.name.startswith(PREFIX))
    return Phases(sorted(spans, key=lambda s: (s[1], -s[2])), ops,
                  any(op[3] for evs in ops.values() for op in evs))


# ---------------------------------------------------------------------------
# readings: None where the trace holds no program span (a program
# without spans) or, for a device reading, no scope
# ---------------------------------------------------------------------------

def _fit(ph: Phases) -> Optional[Span]:
    return next((s for s in ph.spans if s[0] == "fit"), None)


def span_s(ph: Phases, phases: Iterable[str]) -> Optional[float]:
    """Seconds in the spans of ``phases``; 0 where the fit ran none."""
    if _fit(ph) is None:
        return None
    names = set(phases)
    return sum(d for name, _s, d, _a in ph.spans if name in names) / 1e9


def prep_s(ph: Phases) -> Optional[float]:
    return span_s(ph, PREP)


def candgen_s(ph: Phases) -> Optional[float]:
    return span_s(ph, CANDGEN)


def spec_candgen_s(ph: Phases) -> Optional[float]:
    return span_s(ph, ("candgen_spec",))


def wire_wait_s(ph: Phases) -> Optional[float]:
    return span_s(ph, ("wire_wait",))


def gc_s(ph: Phases) -> Optional[float]:
    """The fit's generation-2 GC seconds (its span's ``gc_s`` arg)."""
    fit = _fit(ph)
    return None if fit is None else float(fit[3].get("gc_s", 0.0))


def canon_tested(ph: Phases) -> Optional[int]:
    """Raw candidates the fit put through candgen's canonicality walk
    (its span's ``canon_tested`` arg); None where the program does not
    count them."""
    fit = _fit(ph)
    return None if fit is None else fit[3].get("canon_tested")


def scope_ns(ph: Phases, chips: Iterable[int], scope: str
             ) -> Optional[float]:
    """Device busy time of the ops under ``mirage/<scope>``, mean over
    the ``chips`` the trace holds ops of."""
    chips = [c for c in chips if ph.ops.get(c)]
    if not ph.scoped or not chips:
        return None
    return sum(trace.busy_ns(op[:3] for op in ph.ops[c] if op[3] == scope)
               for c in chips) / len(chips)


def materialize_ms(ph: Phases, chips: Iterable[int]) -> Optional[float]:
    ns = scope_ns(ph, chips, "materialize")
    return None if ns is None else ns / 1e6


def coverage(ph: Phases) -> Optional[float]:
    """Share of the fit's wall time inside some leaf span (every span
    but ``fit``, ``level`` and ``device_loop``)."""
    fit = _fit(ph)
    if fit is None:
        return None
    lo, hi = fit[1], fit[1] + fit[2]
    leaves = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
              for n, s, d, _a in ph.spans
              if n not in OUTER and s < hi and s + d > lo]
    return trace.busy_ns(leaves) / (hi - lo)


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------

def level_of(ph: Phases, start: int, end: int) -> Optional[int]:
    """The level whose span holds [start, end), or None."""
    for name, s, d, args in ph.spans:
        if name == "level" and s <= start and end <= s + d:
            return args.get("level")
    return None


def label(ph: Phases, span: Span) -> str:
    """A span's phase, with its level where it lies in one: L5:candgen."""
    lv = level_of(ph, span[1], span[1] + span[2])
    return span[0] if lv is None else f"L{lv}:{span[0]}"


def by_label(ph: Phases) -> dict[str, float]:
    """Seconds per leaf phase and level, most first."""
    out: dict[str, float] = {}
    for sp in ph.spans:
        if sp[0] not in OUTER:
            key = label(ph, sp)
            out[key] = out.get(key, 0.0) + sp[2] / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def idle_gaps(ph: Phases, events: list, bench_spans: list, n: int = 10,
              window: Optional[tuple[int, int]] = None) -> list[list]:
    """``bench.trace.idle_gaps`` with each gap named by the leaf program
    span covering most of it, with its level (``L5:candgen``), else by
    the level span that holds it (``L5:level``); a gap no program span
    covers keeps the name the benchmark's own spans give it."""
    named = trace.idle_gaps(events, bench_spans, n, window)
    busy = trace.intervals(events)
    if not busy:
        return named
    lo, hi = window if window else (busy[0][0], busy[-1][1])
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    out = []
    for (a, b), (fallback, secs) in zip(gaps, named):
        best = None
        for sp in ph.spans:
            if sp[0] == "fit":
                continue
            c = min(b, sp[1] + sp[2]) - max(a, sp[1])
            # a leaf before a level, then most cover, then the shortest
            key = (sp[0] not in OUTER, c, -sp[2])
            if c > 0 and (best is None or key > best[0]):
                best = (key, sp)
        out.append([label(ph, best[1]) if best else fallback, secs])
    return out


def summary(path: str | Path, chip: int = 0) -> dict:
    ph = load(path)
    tr = trace.load(path)
    fit = _fit(ph)
    window = (fit[1], fit[1] + fit[2]) if fit else None
    scopes = sorted({op[3] for ops in ph.ops.values() for op in ops} - {""})
    return {
        "fit_s": fit[2] / 1e9 if fit else None,
        "fit_args": fit[3] if fit else None,
        "coverage": coverage(ph),
        "prep_s": prep_s(ph), "candgen_s": candgen_s(ph),
        "spec_candgen_s": spec_candgen_s(ph),
        "wire_wait_s": wire_wait_s(ph), "gc_s": gc_s(ph),
        "canon_tested": canon_tested(ph),
        "materialize_ms": materialize_ms(ph, [chip]),
        "scope_ms": {s: scope_ns(ph, [chip], s) / 1e6 for s in scopes},
        "levels": [sp[3] for sp in ph.spans if sp[0] == "level"],
        "by_label_s": by_label(ph),
        "idle_gaps": idle_gaps(ph, tr.devices.get(chip, []), tr.spans,
                               window=window),
    }


if __name__ == "__main__":
    print(json.dumps(summary(sys.argv[1]), indent=1))
