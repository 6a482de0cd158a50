"""The program's spans as the benchmark reads them: every stage of an
engine call nests in its call, the readers of the span log return a
number in every tiny cell, the own times tile each call, and a window
the ring may have lost a record of reads ``None``."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench_tiny import tiny_root
import harness
import program_spans
from repro.core import profiling

HERE = Path(__file__).resolve().parent
ENGINE = ("repro.engine.prepare", "repro.engine.upload",
          "repro.engine.launch", "repro.engine.wait",
          "repro.engine.download")
STEADY = ("prep_ms.steady", "dispatch_ms.steady", "device_wait_ms.steady",
          "download_ms.steady", "handoff_ms.steady", "transfer_mb.steady")
OFFLINE = ("prep_ms.offline", "dispatch_ms.offline",
           "device_wait_ms.offline", "download_ms.offline",
           "transfer_mb.offline")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


def traced(root, cell):
    """A traced run of ``cell``, the run's namespace, and the records of
    its window's engine calls."""
    seen = {}
    per_layer = harness.per_layer

    def keep(root_, bench, name, run, device):
        seen["run"] = run
        return per_layer(root_, bench, name, run, device)

    harness.per_layer = keep
    try:
        result = harness.run_cell(root, cell, 2 ** 33 + 3, 0.4, True,
                                  t_process=time.perf_counter(),
                                  require_tpu=False)
    finally:
        harness.per_layer = per_layer
    run = seen["run"]
    lo = min(c[0] for c in run.engine_calls)
    hi = max(c[1] for c in run.engine_calls)
    _, recs = program_spans.window_records(
        profiling.span_log().records(), lo, hi)
    return result, run, recs


def by_call(recs):
    calls = {}
    for r in recs:
        calls.setdefault(r.call_id, {}).setdefault(r.name, []).append(r)
    return calls


@pytest.fixture(scope="module")
def steady(root):
    return traced(root, "tiny.steady")


@pytest.fixture(scope="module")
def offline(root):
    return traced(root, "tiny.offline")


def inside(inner, outer):
    return outer.t0 <= inner.t0 <= inner.t1 <= outer.t1


def test_engine_spans_nest_in_their_call(steady):
    result, run, recs = steady
    calls = by_call(recs)
    assert len(calls) >= 3
    for spans in calls.values():
        (serve,), (batch,) = spans["repro.serve.engine"], \
            spans["repro.serve.batch"]
        (eng,) = spans["repro.engine.run"]
        assert serve.parent is None and batch.parent is None
        assert batch.t1 <= serve.t0
        assert eng.parent == "repro.serve.engine" and inside(eng, serve)
        for name in ENGINE:
            (r,) = spans[name]
            assert r.parent == "repro.engine.run" and inside(r, eng)
        assert set(spans) == {"repro.serve.batch", "repro.serve.engine",
                              "repro.engine.run", *ENGINE}


@pytest.mark.parametrize("cell", ["tiny.steady", "tiny.offline"])
def test_span_readers_return_a_number(steady, offline, cell):
    result, _, _ = steady if cell == "tiny.steady" else offline
    assert result["correct"]
    names = STEADY if cell == "tiny.steady" else OFFLINE
    for name in names:
        assert result["metrics"][name]["value"] >= 0, name
    mb = "transfer_mb.steady" if cell == "tiny.steady" else \
        "transfer_mb.offline"
    assert result["metrics"][mb]["value"] > 0
    # the call is timed from outside too, and still read
    call = "call_ms.steady" if cell == "tiny.steady" else "call_ms.offline"
    assert result["metrics"][call]["value"] > 0


def test_span_readers_on_four_devices(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "dp_spans_run.py"),
                        str(root)], env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["device"]["count"] == 4
    for name in OFFLINE:
        assert result["metrics"][name]["value"] >= 0, name
    # 8 rows per chip of 6 steps: int32 input up, spikes, v and counts down
    assert result["metrics"]["transfer_mb.offline"]["value"] > 0


@pytest.mark.parametrize("cell", ["tiny.steady", "tiny.offline"])
def test_own_times_tile_each_call(steady, offline, cell):
    """Per call, the spans' own times add up to its top spans' time, so
    the readers' parts plus ``repro.engine.run``'s own time are exactly
    the call as the program times it."""
    result, run, recs = steady if cell == "tiny.steady" else offline
    tops = (("repro.serve.batch", "repro.serve.engine")
            if cell == "tiny.steady" else ("repro.engine.run",))
    calls = by_call(recs)
    for spans in calls.values():
        flat = [r for rs in spans.values() for r in rs]
        own = program_spans.own_times(flat)
        whole = sum(r.t1 - r.t0 for t in tops for r in spans[t])
        assert sum(own.own_s.values()) == pytest.approx(whole, rel=1e-9)
        assert all(v >= 0 for v in own.own_s.values())
    mean = program_spans.own_times(recs)
    parts = (mean.ms("repro.serve.batch", "repro.engine.prepare")
             if cell == "tiny.steady" else mean.ms("repro.engine.prepare"))
    parts += (mean.ms("repro.engine.upload", "repro.engine.launch")
              + mean.ms("repro.engine.wait") + mean.ms("repro.engine.download")
              + mean.ms("repro.engine.run"))
    if cell == "tiny.steady":
        parts += mean.ms("repro.serve.engine")
    whole = sum(r.t1 - r.t0 for r in recs if r.name in tops) / len(calls)
    assert parts == pytest.approx(whole * 1e3, rel=1e-9)
    kind = cell.split(".")[1]
    named = sum(result["metrics"][f"{m}.{kind}"]["value"] for m in
                ("prep_ms", "dispatch_ms", "device_wait_ms", "download_ms")
                + (("handoff_ms",) if kind == "steady" else ()))
    assert named + mean.ms("repro.engine.run") == pytest.approx(
        whole * 1e3, rel=1e-9)


def fake_calls(n, log):
    """``n`` engine calls of three spans each into ``log``; their
    ``(t0, t1, rows)`` as the harness records a call."""
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        with profiling.call_scope(), profiling.span("repro.engine.run"):
            with profiling.span("repro.engine.prepare"):
                pass
            with profiling.span("repro.engine.upload", nbytes=10):
                pass
        out.append((t0, time.perf_counter(), 1))
    return out


def test_window_with_dropped_records_reads_none(monkeypatch):
    log = profiling.SpanLog(capacity=8)
    monkeypatch.setattr(profiling, "_SPAN_LOG", log)
    calls = fake_calls(3, log)             # 9 records: the first is lost
    assert log.dropped == 1
    whole = SimpleNamespace(engine_calls=calls, kind="back_to_back")
    assert program_spans.per_call(whole) is None
    later = SimpleNamespace(engine_calls=calls[1:], kind="back_to_back")
    got = program_spans.per_call(later)
    assert got.calls == 2 and got.mb("repro.engine.upload") == 10 / 1e6
    more = fake_calls(1, log)              # the second call loses a span
    assert program_spans.per_call(
        SimpleNamespace(engine_calls=calls[1:] + more, kind="x")) is None


def test_program_without_a_span_log_reads_none(monkeypatch):
    calls = fake_calls(2, profiling.span_log())
    run = SimpleNamespace(engine_calls=calls, kind="back_to_back")
    assert program_spans.per_call(run) is not None
    monkeypatch.delattr(profiling, "span_log")
    assert program_spans.per_call(run) is None
