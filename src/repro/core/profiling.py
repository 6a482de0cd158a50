"""Compile-phase profiler (DESIGN.md §12).

The compile pipeline is a handful of named passes, but at compiler
scale (10⁵–10⁶ synapses) the interesting costs live INSIDE one of them
— the multilevel partitioner's coarsen / coarse-search / project /
refine stages. A :class:`PhaseProfiler` accumulates wall seconds (and
optionally allocation deltas) per named phase; the active profiler is
carried in a :class:`contextvars.ContextVar` so deeply nested stages
record phases without threading a profiler argument through every
mapping-strategy signature.

Usage::

    with profiled(PhaseProfiler()) as prof:
        ...                         # any code calling phase("name")
    prof.seconds                    # {"coarsen": 0.07, "refine": 0.61, ...}

``phase("name")`` is a no-op context manager when no profiler is
active, so instrumented code costs nothing in un-profiled runs
(tests/test_profiling.py pins both behaviors). Phases may repeat and
nest; repeated entries accumulate, nested phases are recorded under
their own names (the compile pipeline's top-level pass phases —
``neuron_params``/``partition``/``schedule``/``validate``/``lower``/
``report`` — contain the partitioner's sub-phases, so summing ONLY the
top-level keys gives the pipeline total).

The same module records the serving path's **spans**: ``span(name)``
marks one stage of an engine call (input preparation, upload, launch,
device wait, download, the front end's hand-off) on two clocks at once.
It writes a :class:`jax.profiler.TraceAnnotation`, so a profile shows
the stage on the host timeline beside the device's ops, and it appends
``(name, call_id, parent, t0, t1, nbytes)`` to a bounded process-wide
:class:`SpanLog` (``span_log()``) on ``time.perf_counter``. Recording
is always on and costs about a microsecond per span; with no profiler
attached the annotation is a TraceMe check. Every span of one engine
call, front end and engine alike, carries that call's id
(:func:`call_scope`), and ``parent`` names the span it opened inside.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import time
import tracemalloc
from collections import deque
from typing import NamedTuple

from jax.profiler import TraceAnnotation

#: the compile pipeline's top-level pass phases; they tile the whole
#: compile, so their sum approximates ``CompileReport.compile_seconds``
#: (sub-phases like ``coarsen``/``refine`` nest inside ``partition``)
TOP_LEVEL_PHASES = ("neuron_params", "partition", "schedule", "validate",
                    "lower", "report")


class PhaseProfiler:
    """Accumulates per-phase wall seconds (and, optionally, allocation).

    ``alloc=True`` additionally records each phase's net allocation
    delta and in-phase peak, in MB, via :mod:`tracemalloc` (started by
    :func:`profiled` if not already tracing) — useful for attributing
    the compiler's RSS, at a 2–4x wall-clock cost.
    """

    def __init__(self, *, alloc: bool = False):
        self.alloc = alloc
        self.seconds: dict[str, float] = {}
        self.alloc_mb: dict[str, float] = {}
        self.peak_mb: dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        if self.alloc:
            tracemalloc.reset_peak()
            base, _ = tracemalloc.get_traced_memory()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.seconds[name] = self.seconds.get(name, 0.0) + dt
            if self.alloc:
                cur, peak = tracemalloc.get_traced_memory()
                mb = 1024.0 * 1024.0
                self.alloc_mb[name] = (self.alloc_mb.get(name, 0.0)
                                       + (cur - base) / mb)
                self.peak_mb[name] = max(self.peak_mb.get(name, 0.0),
                                         peak / mb)


_ACTIVE: contextvars.ContextVar[PhaseProfiler | None] = \
    contextvars.ContextVar("suprasnn_phase_profiler", default=None)


def current_profiler() -> PhaseProfiler | None:
    """The profiler installed by the innermost :func:`profiled`, if any."""
    return _ACTIVE.get()


@contextlib.contextmanager
def profiled(profiler: PhaseProfiler | None = None):
    """Install ``profiler`` (a fresh wall-only one if omitted) as the
    active profiler for the dynamic extent of the block."""
    prof = profiler if profiler is not None else PhaseProfiler()
    started_tracing = False
    if prof.alloc and not tracemalloc.is_tracing():
        tracemalloc.start()
        started_tracing = True
    token = _ACTIVE.set(prof)
    try:
        yield prof
    finally:
        _ACTIVE.reset(token)
        if started_tracing:
            tracemalloc.stop()


class _NullPhase:
    """Shared no-op context manager: ``phase()`` without an active
    profiler must cost nothing (no generator frame, no allocation)."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_PHASE = _NullPhase()


def phase(name: str):
    """Record a named phase on the active profiler (no-op when none)."""
    prof = _ACTIVE.get()
    return _NULL_PHASE if prof is None else prof.phase(name)


# -- serving spans ------------------------------------------------------------

#: records the process-wide log holds before it overwrites its oldest
SPAN_LOG_CAPACITY = 1 << 16


class SpanRecord(NamedTuple):
    """One finished span: ``t0``/``t1`` on ``time.perf_counter`` (s),
    ``parent`` the name of the span it was opened inside (``None`` at
    the top of its call), ``nbytes`` the bytes it moved (0 if none)."""
    name: str
    call_id: int | None
    parent: str | None
    t0: float
    t1: float
    nbytes: int


class SpanLog:
    """A fixed-capacity ring of span records.

    :class:`span` appends ``(seq, name, call_id, parent, t0, t1,
    nbytes)``. Once full, each new record pushes out the oldest, which
    ``dropped`` counts; ``records()`` is a snapshot, oldest first (the
    order in which the spans ended). Appending takes no lock:
    ``deque.append`` and ``next`` on a counter are atomic, so the event
    loop and the executor thread may append at once.
    """

    def __init__(self, capacity: int = SPAN_LOG_CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.clear()

    def _snapshot(self) -> list[tuple]:
        return list(self._ring)            # one C call: atomic

    @property
    def written(self) -> int:
        """Records appended since the log was made or cleared."""
        snap = self._snapshot()
        return max(r[0] for r in snap) + 1 if snap else 0

    @property
    def dropped(self) -> int:
        """Records pushed out because the ring was full."""
        snap = self._snapshot()
        return max(r[0] for r in snap) + 1 - len(snap) if snap else 0

    def __len__(self) -> int:
        return len(self._ring)

    def records(self) -> list[SpanRecord]:
        return [SpanRecord(*r[1:]) for r in self._snapshot()]

    def clear(self) -> None:
        self._ring: deque = deque(maxlen=self.capacity)
        self._seq = itertools.count()


_SPAN_LOG = SpanLog()
_CALL_IDS = itertools.count(1)
_tracing = TraceAnnotation.is_enabled    # a profiler is collecting now
_clock = time.perf_counter


class _Call:
    """One engine call: its id and the name of its innermost open span.

    A call's spans run one after another, on the event loop and then on
    the executor thread while the loop waits for it, so one mutable
    record serves both threads."""
    __slots__ = ("id", "open")

    def __init__(self):
        self.id = next(_CALL_IDS)
        self.open: str | None = None


_CALL: contextvars.ContextVar[_Call | None] = \
    contextvars.ContextVar("suprasnn_engine_call", default=None)


def span_log() -> SpanLog:
    """The process-wide log every :func:`span` appends to."""
    return _SPAN_LOG


class span:
    """Time the block as span ``name`` of the current engine call, on
    the profiler's host timeline (a ``TraceAnnotation``) and in
    :func:`span_log`; ``nbytes`` is what the block moved between host
    and device. Outside an engine call the record has no call id and no
    parent.

    A class, not a generator: it runs on every engine call, and each
    span costs about a microsecond. With no profiler collecting, the
    annotation is skipped after one TraceMe check."""
    __slots__ = ("name", "nbytes", "_ann", "_call", "_parent", "_t0")

    def __init__(self, name: str, *, nbytes: int = 0):
        self.name = name
        self.nbytes = nbytes

    def __enter__(self):
        self._call = call = _CALL.get()
        if call is not None:
            self._parent = call.open
            call.open = self.name
        if _tracing():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = _clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = _clock()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        call = self._call
        if call is None:
            call_id = parent = None
        else:
            call_id, parent = call.id, self._parent
            call.open = parent
        log = _SPAN_LOG
        log._ring.append((next(log._seq), self.name, call_id, parent,
                          self._t0, t1, self.nbytes))
        return False


class call_scope:
    """Make the block one engine call: a fresh call if ``new`` or if the
    caller is in none, else the caller's (the front end opens one per
    batch and the engine joins it). The call lives in a ``ContextVar``,
    so an executor thread joins it when run under
    ``contextvars.copy_context().run``. ``with`` yields the call id."""
    __slots__ = ("new", "_tok")

    def __init__(self, *, new: bool = False):
        self.new = new

    def __enter__(self) -> int:
        call = _CALL.get()
        if self.new or call is None:
            call = _Call()
            self._tok = _CALL.set(call)
        else:
            self._tok = None
        return call.id

    def __exit__(self, exc_type, exc, tb):
        if self._tok is not None:
            _CALL.reset(self._tok)
        return False
