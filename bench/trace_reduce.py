"""From a profiler trace to device time, idle gaps and their causes.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps two things: the device operations of each chip (the ``XLA Ops``
line of every ``/device:TPU:<n>`` plane) and the benchmark's own host
spans (events named ``bench.*`` on any host line, written by
``jax.profiler.TraceAnnotation``). Everything else here is plain
interval arithmetic on ``(name, start_ns, end_ns)`` tuples, so it is
tested without a trace.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
NO_SPAN = "no bench span"

Event = tuple[str, float, float]           # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    device_ops: dict[int, list[Event]]      # device id -> its op events
    spans: list[Event]                      # the benchmark's host spans

    def window(self, name: str = "bench.window") -> tuple[float, float]:
        """The first span called ``name``: the traced window."""
        for s in self.spans:
            if s[0] == name:
                return s[1], s[2]
        raise LookupError(f"trace has no {name!r} span")

    def spans_named(self, name: str) -> list[Event]:
        return [s for s in self.spans if s[0] == name]


def find_xplane(log_dir: str | Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str | Path) -> Trace:
    """Device ops and bench spans of one ``.xplane.pb`` (or a directory
    holding one)."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.is_dir():
        path = find_xplane(path)
    data = ProfileData.from_file(str(path))
    device_ops: dict[int, list[Event]] = {}
    spans: list[Event] = []
    names: dict[str, str] = {}           # one string per distinct op name
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = device_ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend((names.setdefault(e.name, e.name),
                                e.start_ns, e.end_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    for ops in device_ops.values():
        ops.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: e[1])
    return Trace(device_ops, spans)


def clip(events: list[Event], lo: float, hi: float) -> list[Event]:
    """The events' parts inside ``[lo, hi]``."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def merged(events: list[Event]) -> list[tuple[float, float]]:
    """The union of the events' intervals, as sorted disjoint pieces."""
    out: list[list[float]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: list[Event], lo: float, hi: float) -> float:
    """Time in ``[lo, hi]`` during which at least one event runs."""
    return sum(e - s for s, e in merged(clip(events, lo, hi)))


def idle_gaps(events: list[Event], lo: float, hi: float
              ) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` in which no event runs."""
    gaps, t = [], lo
    for s, e in merged(clip(events, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute(gap: tuple[float, float], spans: list[Event]) -> str:
    """The name of the span that covers most of ``gap``: what the
    benchmark's host side was doing while the device idled."""
    best, best_ns = NO_SPAN, 0.0
    totals: dict[str, float] = {}
    for name, s, e in spans:
        if name == "bench.window":
            continue
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > 0:
            totals[name] = totals.get(name, 0.0) + overlap
    for name, ns in totals.items():
        if ns > best_ns:
            best, best_ns = name, ns
    return best


def matching_ns(events: list[Event], pattern: str) -> tuple[float, int]:
    """Total duration and count of the events whose name matches
    ``pattern`` (a regular expression searched in the name)."""
    rx = re.compile(pattern)
    hit = [e - s for n, s, e in events if rx.search(n)]
    return float(sum(hit)), len(hit)


def short_name(name: str) -> str:
    """``%name = (shape) kind(...), ...`` -> ``%name kind`` (with the
    custom-call target for a custom call)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    m = re.search(r"\)?\s([a-z][\w\-]*)\(", rest)
    kind = m.group(1) if m else ""
    t = re.search(r'custom_call_target="([^"]+)"', rest)
    return " ".join(x for x in (head, kind, t.group(1) if t else "") if x)


def leaves(events: list[Event]) -> list[Event]:
    """The events that contain no other event: a loop or call op whose
    body is traced as ops of its own is left out, so no time counts
    twice."""
    ev = sorted(events, key=lambda x: (x[1], -x[2]))
    return [a for a, b in zip(ev, ev[1:] + [None])
            if b is None or b[1] >= a[2]]


def top_ops(events: list[Event], n: int = 10) -> list[list]:
    """The ``n`` operations that took most device time, by
    :func:`short_name`, ``[[name, seconds], ...]``; containers left
    out (:func:`leaves`)."""
    totals: dict[str, float] = {}
    for name, s, e in leaves(events):
        key = short_name(name)
        totals[key] = totals.get(key, 0.0) + (e - s)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def longest_gaps(gaps: list[tuple[float, float]], spans: list[Event],
                 n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps, each named by :func:`attribute`,
    ``[[span name, seconds], ...]``."""
    ranked = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    return [[attribute(g, spans), (g[1] - g[0]) / 1e9] for g in ranked]
