"""Reduce a JAX profiler trace to device busy, idle and per-op time.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData`` alone.  Each chip is a plane named
``/device:TPU:<n>``, whose ``XLA Ops`` line holds one event per
operation the chip ran, named by the op's HLO text
(``%fused_level_packed_pallas.1 = (s32[...]) custom-call(...)``).  Ops
nest there: a ``while`` op's event spans the ops of its body.  The host
plane ``/host:CPU`` holds the benchmark's own spans
(``TraceAnnotation``s named ``bench:<what>``), which name what the host
was doing while the chip sat idle.

An event is ``(text, start_ns, duration_ns)``; :func:`op_name` gives the
HLO instruction name (``fused_level_packed_pallas.1``) that metric
patterns match.  Times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import dataclasses
import gzip
import re
from pathlib import Path
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"

Event = tuple  # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Trace:
    devices: dict[int, list[Event]]     # chip id -> its ops, by start
    spans: list[Event]                  # the benchmark's host spans
    # chip id -> the spans of its asynchronous ops (DMAs, collectives
    # in flight); not busy time of the chip's cores
    async_ops: dict[int, list[Event]] = dataclasses.field(
        default_factory=dict)


def find_xplane(log_dir: str | Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str | Path) -> Trace:
    """Read an ``.xplane.pb`` file, or a gzipped one (``.gz``)."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        pd = ProfileData.from_serialized_xspace(
            gzip.decompress(Path(path).read_bytes()))
    else:
        pd = ProfileData.from_file(str(path))
    devices: dict[int, list[Event]] = {}
    async_ops: dict[int, list[Event]] = {}
    spans: list[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line_name, out in ((OP_LINE, devices),
                                   (ASYNC_LINE, async_ops)):
                evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                       for line in plane.lines if line.name == line_name
                       for e in line.events]
                # a parent op starts with or before the ops nested in it
                out[int(m.group(1))] = sorted(evs,
                                              key=lambda e: (e[1], -e[2]))
        elif plane.name == HOST_PLANE:
            spans.extend((e.name[len(SPAN_PREFIX):], int(e.start_ns),
                          int(e.duration_ns))
                         for line in plane.lines for e in line.events
                         if e.name.startswith(SPAN_PREFIX))
    return Trace(devices, sorted(spans, key=lambda e: e[1]), async_ops)


def intervals(events: Iterable[Event]) -> list[tuple[int, int]]:
    """The union of the events' intervals, as sorted disjoint pairs."""
    out: list[list[int]] = []
    for _name, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return [(a, b) for a, b in out]


def busy_ns(events: Iterable[Event]) -> int:
    return sum(b - a for a, b in intervals(events))


_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")


def op_name(text: str) -> str:
    """The HLO instruction name of an op event's text."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def op_code(text: str) -> str:
    """The HLO opcode of an op event's text (``custom-call``, ``while``),
    or "" where the text has none."""
    m = _OPCODE.search(text.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def matching(events: Iterable[Event], pattern: re.Pattern) -> list[Event]:
    """The events whose op name or opcode matches ``pattern``
    (``re.match``)."""
    return [e for e in events
            if pattern.match(op_name(e[0])) or pattern.match(op_code(e[0]))]


def self_ns(events: list[Event]) -> list[int]:
    """Each event's duration less that of the events nested in it."""
    out = [e[2] for e in events]
    stack: list[int] = []
    for i, (_t, s, d) in enumerate(events):
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] -= d
        stack.append(i)
    return out


def top_ops(events: list[Event], n: int = 10, width: int = 120
            ) -> list[list]:
    """The ``n`` ops with the most device time of their own (nested ops'
    time taken out), by HLO text cut to ``width``: [[text, seconds]]."""
    by: dict[str, int] = {}
    for (text, _s, _d), own in zip(events, self_ns(events)):
        key = text[:width]
        by[key] = by.get(key, 0) + own
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(events: list[Event], spans: list[Event], n: int = 10,
              window: Optional[tuple[int, int]] = None) -> list[list]:
    """The ``n`` longest gaps between the chip's ops, inside ``window``
    when given, each named by the host phase spans that overlap it, the
    one covering most of it first: [[name, seconds]]."""
    busy = intervals(events)
    if not busy:
        return []
    lo, hi = window if window else (busy[0][0], busy[-1][1])
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    out = []
    for a, b in gaps:
        # the phases overlapping the gap, most of it first; "fit" spans
        # every gap of the fit and names none
        cover: dict[str, int] = {}
        for name, s, d in spans:
            c = min(b, s + d) - max(a, s)
            if c > 0 and name != "fit":
                cover[name] = cover.get(name, 0) + c
        names = sorted(cover, key=lambda k: -cover[k])[:3]
        out.append(["+".join(names) or "host outside any phase span",
                    (b - a) / 1e9])
    return out


def chip_mean(trace: Optional[Trace], chips: Iterable[int], fn
              ) -> Optional[float]:
    """Mean of ``fn(ops)`` over the ``chips`` whose ops the trace holds;
    None where it holds none of them."""
    if trace is None:
        return None
    vals = [fn(trace.devices[c]) for c in chips if trace.devices.get(c)]
    return sum(vals) / len(vals) if vals else None
