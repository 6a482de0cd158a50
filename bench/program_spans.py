"""The program's own spans, per engine call of the window.

The program records every stage of an engine call in a process-wide
span log (``repro.core.profiling.span_log()``): name, call id, parent
span, start and end on ``time.perf_counter`` (the harness's clock) and
the bytes moved. :func:`per_call` takes the calls whose
``repro.engine.run`` lies in the window (from the first
``run.engine_calls`` start to the last end) and averages, per call, each
span's own time: its duration less that of the spans opened inside it.
So the own times of one call's spans add up to its top spans' time.

A program without the log, or a log that may have lost a record of the
window (its ring overwrote one), reads ``None``: never a biased number.
"""
from __future__ import annotations

from collections import defaultdict

RUN = "repro.engine.run"


class PerCall:
    """Mean own time (s) and bytes of each span name per engine call."""

    def __init__(self, own_s: dict, nbytes: dict, calls: int):
        self.own_s, self.nbytes, self.calls = own_s, nbytes, calls

    def ms(self, *names: str) -> float | None:
        """The summed own time of ``names`` per call, in ms; ``None``
        if a name never occurs in the window."""
        if any(n not in self.own_s for n in names):
            return None
        return sum(self.own_s[n] for n in names) * 1e3

    def mb(self, *names: str) -> float | None:
        """The summed bytes of ``names`` per call, in MB (10^6 bytes)."""
        if any(n not in self.nbytes for n in names):
            return None
        return sum(self.nbytes[n] for n in names) / 1e6


def span_log():
    """The program's span log, or ``None`` where it has none."""
    try:
        from repro.core import profiling
    except ImportError:
        return None
    reader = getattr(profiling, "span_log", None)
    return None if reader is None else reader()


def window_records(records, lo: float, hi: float) -> tuple[set, list]:
    """The ids of the calls whose ``repro.engine.run`` lies in
    ``[lo, hi]``, and every record of those calls."""
    ids = {r.call_id for r in records
           if r.name == RUN and r.call_id is not None
           and lo <= r.t0 and r.t1 <= hi}
    return ids, [r for r in records if r.call_id in ids]


def lost_in_window(records, dropped: int, ids: set, lo: float) -> bool:
    """Whether a record of the window may have been overwritten. The
    ring drops records in the order they ended, so nothing of the window
    was lost if the oldest record kept ended before the window began and
    belongs to no call of it."""
    if not dropped:
        return False
    if not records:
        return True
    oldest = records[0]
    return oldest.call_id in ids or oldest.t1 >= lo


def own_times(recs) -> PerCall | None:
    """Per-call means of own time and bytes over the records ``recs``
    of whole calls."""
    ids = {r.call_id for r in recs}
    if not ids:
        return None
    inner: dict[tuple, float] = defaultdict(float)
    for r in recs:
        if r.parent is not None:
            inner[(r.call_id, r.parent)] += r.t1 - r.t0
    own: dict[str, float] = defaultdict(float)
    nbytes: dict[str, float] = defaultdict(float)
    for r in recs:
        own[r.name] += (r.t1 - r.t0) - inner[(r.call_id, r.name)]
        nbytes[r.name] += r.nbytes
    n = len(ids)
    return PerCall({k: v / n for k, v in own.items()},
                   {k: v / n for k, v in nbytes.items()}, n)


def per_call(run) -> PerCall | None:
    """The window's per-call span times and bytes, or ``None``."""
    calls = getattr(run, "engine_calls", None)
    log = span_log()
    if not calls or log is None:
        return None
    records = log.records()
    lo = min(c[0] for c in calls)
    hi = max(c[1] for c in calls)
    ids, recs = window_records(records, lo, hi)
    if not ids or lost_in_window(records, log.dropped, ids, lo):
        return None
    return own_times(recs)
