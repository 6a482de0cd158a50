"""Compile-phase profiler and serving-span tests (DESIGN.md §12).

Pins the three contracts the profiler ships with: the top-level pass
phases tile the whole compile (their sum approximates
``compile_seconds``), the per-phase breakdown survives
``Program.save``/``load``, and un-profiled code paths cost nothing
(``phase()`` without an active profiler is a shared no-op object).

And the span log's: each record carries its call id, parent and bytes,
the ring holds its capacity and counts what it drops, a span shows on
the profiler's timeline, and the engine's outputs are bit-identical
with the spans in place.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_ext, make_hw
from repro.core import ExecutionSpec, compile, random_graph, run_oracle
from repro.core import profiling
from repro.core.mapping.multilevel import multilevel_partition
from repro.core.profiling import (TOP_LEVEL_PHASES, PhaseProfiler, SpanLog,
                                  call_scope, current_profiler, phase,
                                  profiled, span, span_log)
from repro.core.program import Program
from repro.core.scale import scale_hw, synthetic_graph


def test_phase_seconds_tile_compile_time():
    g = random_graph(24, 48, 3000, seed=7)
    prog = compile(g, make_hw(g, m=8))
    rep = prog.report
    assert rep.phase_seconds is not None
    assert set(rep.phase_seconds) <= set(TOP_LEVEL_PHASES)
    assert all(v >= 0.0 for v in rep.phase_seconds.values())
    total = sum(rep.phase_seconds[k] for k in TOP_LEVEL_PHASES
                if k in rep.phase_seconds)
    # the phases tile the pipeline: everything outside them (graph
    # conversion, report attach, phase bookkeeping) is microseconds, so
    # the sum lands within a loose envelope of compile_seconds (which
    # is stamped INSIDE the report phase, hence the two-sided slack)
    assert total == pytest.approx(rep.compile_seconds, rel=0.5, abs=0.05)


def test_multilevel_subphases_recorded():
    g = synthetic_graph(4000, topology="mixed", skew=1.0, seed=0)
    hw = scale_hw(g, n_chips=2, spus_per_chip=4)
    with profiled() as prof:
        res = multilevel_partition(g, hw, coarse_target=500)
    assert res.assign.shape == (g.n_synapses,)
    for name in ("coarsen", "coarse_search", "project", "refine"):
        assert name in prof.seconds, prof.seconds
    assert "place" in prof.seconds          # n_chips > 1: placement ran


def test_compile_reuses_installed_profiler_and_nests_subphases():
    # above COARSE_TARGET so the multilevel sub-phases actually run
    g = synthetic_graph(40_000, topology="mixed", skew=1.0, seed=0)
    hw = scale_hw(g, spus_per_chip=16)
    with profiled(PhaseProfiler()) as prof:
        prog = compile(g, hw, method="multilevel")
    # compile adopted the caller's profiler rather than installing its
    # own, so top-level pass phases and the partitioner sub-phases land
    # in ONE dict (sub-phases nest inside "partition" wall time)
    assert prog.report.phase_seconds == {
        k: pytest.approx(v) for k, v in prof.seconds.items()}
    assert "partition" in prof.seconds
    sub = [k for k in prof.seconds if k not in TOP_LEVEL_PHASES]
    assert sub, "expected multilevel sub-phases on the shared profiler"
    assert sum(prof.seconds[k] for k in sub) <= \
        prof.seconds["partition"] + 1e-6


def test_phase_report_roundtrips_through_save_load(tmp_path):
    g = random_graph(16, 32, 900, seed=2)
    prog = compile(g, make_hw(g, m=8))
    with profiled(PhaseProfiler(alloc=True)):
        prog_alloc = compile(g, make_hw(g, m=8))
    assert prog_alloc.report.phase_alloc_mb is not None
    for p, name in ((prog, "wall.npz"), (prog_alloc, "alloc.npz")):
        path = tmp_path / name
        p.save(path)
        back = Program.load(path)
        assert back.report.phase_seconds == \
            pytest.approx(p.report.phase_seconds)
        if p.report.phase_alloc_mb is None:
            assert back.report.phase_alloc_mb is None
        else:
            assert back.report.phase_alloc_mb == \
                pytest.approx(p.report.phase_alloc_mb)


def test_disabled_profiling_is_none_and_phase_is_noop():
    g = random_graph(10, 20, 300, seed=0)
    prog = compile(g, make_hw(g), profile_phases=False)
    assert prog.report.phase_seconds is None
    assert prog.report.phase_alloc_mb is None
    # identical artifact either way: profiling is observe-only
    ref = compile(g, make_hw(g))
    assert np.array_equal(prog.tables.pre, ref.tables.pre)
    assert prog.report.ot_depth == ref.report.ot_depth

    # no active profiler -> phase() returns the SHARED no-op context
    # manager (no per-call allocation, nothing recorded)
    assert current_profiler() is None
    cm1, cm2 = phase("anything"), phase("else")
    assert cm1 is cm2
    with cm1:
        pass
    with profiled() as prof:
        with phase("x"):
            pass
        with phase("x"):
            pass
    assert set(prof.seconds) == {"x"}       # repeats accumulate, one key
    assert current_profiler() is None       # reset on exit


# -- serving spans ------------------------------------------------------------

ENGINE_SPANS = ("repro.engine.prepare", "repro.engine.upload",
                "repro.engine.launch", "repro.engine.wait",
                "repro.engine.download")


@pytest.fixture(scope="module")
def program():
    g = random_graph(12, 10, 150, seed=4)
    return compile(g, make_hw(g))


def test_span_records_call_parent_and_bytes():
    with span("outside"):
        pass
    with call_scope() as cid:
        with call_scope() as joined:           # joins the open call
            assert joined == cid
            with span("a"):
                with span("b", nbytes=7):
                    pass
        with call_scope(new=True) as other:
            assert other != cid
            with span("c"):
                pass
        with span("d"):                        # back in the first call
            pass
    with span("after"):
        pass
    out, b, a, c, d, after = span_log().records()[-6:]
    assert (out.name, out.call_id, out.parent) == ("outside", None, None)
    assert (b.name, b.call_id, b.parent, b.nbytes) == ("b", cid, "a", 7)
    assert (a.name, a.call_id, a.parent, a.nbytes) == ("a", cid, None, 0)
    assert (c.name, c.call_id, c.parent) == ("c", other, None)
    assert (d.name, d.call_id, d.parent) == ("d", cid, None)
    assert (after.name, after.call_id) == ("after", None)
    assert a.t0 <= b.t0 <= b.t1 <= a.t1


def test_span_log_stays_at_capacity():
    log = span_log()
    assert log.capacity >= 1 << 16
    before = log.written
    extra = 100
    for _ in range(log.capacity + extra):
        with span("fill"):
            pass
    assert len(log) == log.capacity
    assert log.written == before + log.capacity + extra
    assert log.dropped == log.written - log.capacity
    recs = log.records()
    assert len(recs) == log.capacity
    assert all(r.name == "fill" for r in recs)
    assert all(x.t1 <= y.t1 for x, y in zip(recs, recs[1:]))


def test_span_log_ring_keeps_the_newest_engine_calls(program, monkeypatch):
    log = SpanLog(capacity=40)
    monkeypatch.setattr(profiling, "_SPAN_LOG", log)
    eng = program.engine()
    ext = make_ext(program.graph, 3, 5, seed=1)
    for _ in range(20):                         # 6 spans each: 120 > 40
        eng.run(ext)
    assert len(log) == 40 and log.written == 120 and log.dropped == 80
    last = log.records()[-6:]
    assert [r.name for r in last] == [*ENGINE_SPANS, "repro.engine.run"]
    assert len({r.call_id for r in last}) == 1


def test_span_shows_on_the_profiler_timeline(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    with call_scope(), span("repro.test.visible"):
        pass
    jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    names = {e.name for plane in data.planes if plane.name.startswith("/host")
             for line in plane.lines for e in line.events}
    assert "repro.test.visible" in names


@pytest.mark.parametrize("spec", [None, ExecutionSpec(mesh="auto")],
                         ids=["engine", "sharded"])
def test_engine_outputs_bit_identical_with_spans(program, spec):
    """The instrumented run returns the bits the bare compiled scan and
    the oracle give, and records one call of the engine's spans."""
    g = program.graph
    ext = make_ext(g, 4, 7, seed=2)
    eng = program.engine()
    first = span_log().written
    spikes, v, stats = program.run(ext, spec)
    recs = span_log().records()[-(span_log().written - first):]
    shape = (4, eng.lowered.n_internal)
    bare = eng._run(jnp.asarray(ext, jnp.int32), jnp.zeros(shape, jnp.int32),
                    jnp.zeros(shape, jnp.int32))
    assert spikes.tobytes() == np.asarray(bare[0], np.int32).tobytes()
    assert v.tobytes() == np.asarray(bare[1], np.int32).tobytes()
    np.testing.assert_array_equal(stats["packet_counts"],
                                  np.asarray(bare[2], np.int64))
    for i in range(len(ext)):
        s_ref, v_ref = run_oracle(g, ext[i])
        np.testing.assert_array_equal(spikes[i], s_ref)
        np.testing.assert_array_equal(v[i], v_ref)
    assert {r.name for r in recs} == {*ENGINE_SPANS, "repro.engine.run"}
    assert len({r.call_id for r in recs}) == 1
    up = [r for r in recs if r.name == "repro.engine.upload"]
    wait = [r for r in recs if r.name == "repro.engine.wait"]
    down = [r for r in recs if r.name == "repro.engine.download"]
    # the input crosses at one byte per spike
    assert sum(r.nbytes for r in up) == ext.size
    # the wait is the host copy of the int32 packet counts
    assert sum(r.nbytes for r in wait) == ext.shape[0] * ext.shape[1] * 4
    assert sum(r.nbytes for r in down) == spikes.nbytes + v.nbytes


def test_sharded_upload_is_one_byte_per_spike(program):
    """The shard path's upload span counts the int8 train it sends,
    pad rows included: one byte per spike, not four."""
    from repro.serve.sharded import ShardedRunner
    runner = ShardedRunner(program, min_shard=0)
    ext = make_ext(program.graph, 3, 5, seed=4)
    first = span_log().written
    runner.run(ext)
    recs = span_log().records()[-(span_log().written - first):]
    up = [r for r in recs if r.name == "repro.engine.upload"]
    assert len(up) == 1
    assert up[0].nbytes == runner.padded_size(3) * 5 * ext.shape[2]
