"""The trace reducer: busy union, kernel time, idle gaps and their
attribution to the benchmark's host spans."""
import gzip
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import trace_reduce as tr

KERNEL = 'custom_call_target="tpu_custom_call"'


def test_busy_is_the_union_clipped_to_the_window():
    ev = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 25, 26),
          ("e", 40, 60)]
    assert tr.busy_ns(ev, 0, 100) == 15 + 10 + 20
    assert tr.busy_ns(ev, 8, 45) == 7 + 10 + 5
    assert tr.busy_ns([], 0, 10) == 0


def test_idle_gaps_complement_the_busy_time():
    ev = [("a", 10, 20), ("b", 15, 30), ("c", 50, 60)]
    gaps = tr.idle_gaps(ev, 0, 100)
    assert gaps == [(0, 10), (30, 50), (60, 100)]
    assert sum(e - s for s, e in gaps) + tr.busy_ns(ev, 0, 100) == 100


def test_gap_is_named_by_the_span_covering_most_of_it():
    spans = [("bench.window", 0, 100), ("bench.send", 30, 35),
             ("bench.engine_call", 32, 50), ("bench.send", 45, 50)]
    assert tr.attribute((30, 50), spans) == "bench.engine_call"
    assert tr.attribute((60, 70), spans) == tr.NO_SPAN
    ranked = tr.longest_gaps([(30, 50), (60, 90), (0, 1)], spans, n=2)
    assert ranked == [[tr.NO_SPAN, 30e-9], ["bench.engine_call", 20e-9]]


def test_containers_are_left_out_of_the_top_ops():
    loop = "%while.2 = (s32[]) while((s32[]) %t), body=%b"
    kern = ('%closed_call.11 = (s32[8,384]) custom-call(s32[8,1024] %p), '
            'custom_call_target="tpu_custom_call"')
    ev = [(loop, 0, 100), (kern, 10, 40), (kern, 50, 80),
          ("%copy.1 = s32[4] copy(s32[4] %x)", 90, 95)]
    top = tr.top_ops(ev)
    assert top[0] == ["%closed_call.11 custom-call tpu_custom_call", 60e-9]
    assert top[1] == ["%copy.1 copy", 5e-9]
    assert len(top) == 2
    assert tr.matching_ns(ev, KERNEL) == (60.0, 2)


def test_window_span_is_required():
    t = tr.Trace(device_ops={0: []}, spans=[("bench.send", 0, 1)])
    with pytest.raises(LookupError):
        t.window()


FIXTURE = Path(__file__).resolve().parent / "fixtures/shd_offline.xplane.pb.gz"


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """Two offline calls of the SHD network (B=128, T=100), traced on one
    TPU v5 lite. ``--keep-trace`` exists to regenerate this fixture:
    ``bench/run.py --workload shd.offline --seed S --seconds 0.05
    --trace 1 --keep-trace DIR`` on the chip, then gzip the
    ``*.xplane.pb`` that the profiler wrote under ``DIR``."""
    path = tmp_path_factory.mktemp("trace") / "shd.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    return tr.load(path)


def test_chip_trace_has_the_device_and_the_bench_spans(chip_trace):
    assert list(chip_trace.device_ops) == [0]
    names = [s[0] for s in chip_trace.spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.engine_call") == 2
    lo, hi = chip_trace.window()
    for _, s, e in chip_trace.spans_named("bench.engine_call"):
        assert lo <= s < e <= hi


def test_chip_trace_kernel_time_and_busy_union(chip_trace):
    lo, hi = chip_trace.window()
    ops = tr.clip(chip_trace.device_ops[0], lo, hi)
    kernel_ns, n = tr.matching_ns(ops, KERNEL)
    assert n == 2 * 100                     # one fused step per timestep
    busy = tr.busy_ns(ops, lo, hi)
    assert kernel_ns < busy < hi - lo       # the loop's other ops count too
    leaf_ns = sum(e - s for _, s, e in tr.leaves(ops))
    assert leaf_ns <= busy + 1              # leaves never overlap
    assert tr.top_ops(ops)[0][0] == ("%closed_call.11 custom-call "
                                     "tpu_custom_call")
    # device ops run inside the host's engine calls: one clock
    calls = chip_trace.spans_named("bench.engine_call")
    inside = sum(1 for _, s, e in ops
                 if any(cs <= s and e <= ce for _, cs, ce in calls))
    assert inside == len(ops)


def test_chip_trace_gaps_are_attributed(chip_trace):
    lo, hi = chip_trace.window()
    ops = chip_trace.device_ops[0]
    gaps = tr.idle_gaps(ops, lo, hi)
    idle = sum(e - s for s, e in gaps)
    assert idle + tr.busy_ns(ops, lo, hi) == pytest.approx(hi - lo)
    named = tr.longest_gaps(gaps, chip_trace.spans)
    assert len(named) == 10
    assert named[0][0] == "bench.engine_call"
    assert {n for n, _ in named} <= {"bench.engine_call", "bench.assemble",
                                     tr.NO_SPAN}
