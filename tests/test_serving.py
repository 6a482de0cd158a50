"""Conformance/property tests for the serving subsystem (repro.serve).

Covers: (a) micro-batcher queue semantics — FIFO order per stream,
every request served exactly once, buckets always from the policy's
pow2 set, deterministic simulated-clock accounting (exact expected
latencies plus hypothesis properties); (b) overload semantics —
bounded queues, reject / drop-oldest / degrade shedding, dispatch
deadlines, and the bit-exact four-stage latency decomposition;
(c) sharded-vs-single-device bit-exactness over feedforward +
recurrent graphs and ragged batch sizes (1, D-1, D, 3D+1) — spikes,
potentials AND packet counts byte-identical; (d) registry semantics
(duplicate-name rejection, lazy per-model engine ownership, attached
policies); (e) the server's explicit shared / per-engine timeline
accounting; (f) the asyncio front-end (backpressure as exceptions,
real-clock stages); (g) the golden-artifact format pin; and (h) the
seeded serving example reporting identical p50/p99 twice.

Runs on single-device CPU and on the 8-virtual-device CI ``serving``
lane (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — the
device count is read from jax, never assumed.
"""
import asyncio
import importlib.util
import json
import sys
import zipfile
from pathlib import Path

import jax
import numpy as np
import pytest

from conftest import make_ext, make_feedforward, make_hw
from repro.core import ExecutionSpec, Program, compile, random_graph
from repro.launch.mesh import make_serving_mesh
from repro.serve import (AsyncServer, BatchPolicy, DeadlineMissError,
                         MicroBatcher, ProgramRegistry, QueueFullError,
                         Request, SHED_DEADLINE, SHED_QUEUE_FULL, Server,
                         ShardedRunner, ShedError, linear_service_model)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:            # CI installs hypothesis; bare envs skip
    HAVE_HYPOTHESIS = False

GOLDEN = Path(__file__).parent / "golden"


def _recurrent(seed=3):
    g = random_graph(12, 20, 160, seed=seed)
    assert (g.pre >= g.n_inputs).any(), "graph must contain recurrence"
    return g


@pytest.fixture(scope="module")
def ff_program():
    g = make_feedforward()
    return compile(g, make_hw(g), max_iters=4000)


@pytest.fixture(scope="module")
def rec_program():
    g = _recurrent()
    return compile(g, make_hw(g), max_iters=4000)


def ragged_sizes() -> list[int]:
    """1, D-1, D, 3D+1 for the actual device count D (deduplicated)."""
    d = len(jax.devices())
    return sorted({1, max(1, d - 1), d, 3 * d + 1})


# ---------------------------------------------------------------------------
# BatchPolicy
# ---------------------------------------------------------------------------

def test_policy_default_buckets_are_pow2_capped():
    assert BatchPolicy(max_batch=8).buckets == (1, 2, 4, 8)
    # a non-power-of-two max is its own (largest) bucket
    assert BatchPolicy(max_batch=6).buckets == (1, 2, 4, 6)
    assert BatchPolicy(max_batch=1).buckets == (1,)


def test_policy_bucket_of_rounds_up():
    pol = BatchPolicy(max_batch=8)
    assert [pol.bucket_of(n) for n in range(1, 9)] == \
        [1, 2, 4, 4, 8, 8, 8, 8]
    with pytest.raises(ValueError):
        pol.bucket_of(9)
    with pytest.raises(ValueError):
        pol.bucket_of(0)


def test_policy_validation():
    with pytest.raises(ValueError):
        BatchPolicy(max_batch=0)
    with pytest.raises(ValueError):
        BatchPolicy(max_batch=4, max_wait_us=-1.0)
    with pytest.raises(ValueError):
        BatchPolicy(max_batch=4, buckets=(2, 1, 4))       # not ascending
    with pytest.raises(ValueError):
        BatchPolicy(max_batch=8, buckets=(1, 2, 4))       # can't hold 8
    assert BatchPolicy(max_batch=3, buckets=(1, 3)).bucket_of(2) == 3


# ---------------------------------------------------------------------------
# MicroBatcher: deterministic simulated-clock semantics (no engine)
# ---------------------------------------------------------------------------

ARR = np.array([0.0, 10.0, 20.0, 1000.0, 1001.0])
LINEAR = linear_service_model(100.0, 10.0)      # service(b) = 100 + 10 b


def test_batcher_drain_immediate_semantics():
    """max_wait=0: serve what has arrived; engine serially busy."""
    res = MicroBatcher(BatchPolicy(max_batch=2),
                       service_model=LINEAR).drain(ARR)
    # batch 1: only request 0 has arrived at t=0 -> bucket 1, done 110;
    # batch 2: requests 1+2 (both arrived by 110) -> bucket 2, done 230;
    # requests 3, 4 each alone (arrivals 1000, 1001 vs busy-until times)
    np.testing.assert_allclose(res.latencies_us,
                               [110.0, 220.0, 210.0, 110.0, 219.0])
    assert [(b.first, b.size, b.bucket) for b in res.batches] == \
        [(0, 1, 1), (1, 2, 2), (3, 1, 1), (4, 1, 1)]


def test_batcher_max_wait_holds_partial_batches():
    """A partial batch dispatches when the oldest waited max_wait_us."""
    res = MicroBatcher(BatchPolicy(max_batch=4, max_wait_us=50.0),
                       service_model=LINEAR).drain(ARR)
    # requests 0-2 arrive within the 50us window -> dispatch at 50,
    # bucket 4, done 190; requests 3-4 dispatch at 1000+50
    np.testing.assert_allclose(res.latencies_us,
                               [190.0, 180.0, 170.0, 170.0, 169.0])
    assert [(b.first, b.size, b.dispatch_us) for b in res.batches] == \
        [(0, 3, 50.0), (3, 2, 1050.0)]


def test_batcher_full_batch_dispatches_before_deadline():
    arr = np.array([0.0, 1.0, 2.0, 3.0])
    res = MicroBatcher(BatchPolicy(max_batch=4, max_wait_us=1000.0),
                       service_model=LINEAR).drain(arr)
    assert len(res.batches) == 1
    assert res.batches[0].dispatch_us == 3.0     # full at 4th arrival
    np.testing.assert_allclose(res.completion_us, 3.0 + 140.0)


def test_batcher_accounting_identity():
    res = MicroBatcher(BatchPolicy(max_batch=3, max_wait_us=25.0),
                       service_model=LINEAR).drain(ARR)
    np.testing.assert_allclose(res.completion_us - ARR, res.latencies_us)
    assert np.all(res.dispatch_us >= ARR)            # causal dispatch
    assert np.all(np.diff(res.completion_us) >= 0)   # FIFO completions
    sizes = [b.size for b in res.batches]
    assert sum(sizes) == len(ARR)                    # served exactly once
    assert res.metrics()["requests"] == len(ARR)


def test_batcher_input_validation():
    with pytest.raises(ValueError):                  # nothing to simulate
        MicroBatcher(BatchPolicy())
    b = MicroBatcher(BatchPolicy(), service_model=LINEAR)
    with pytest.raises(ValueError):                  # arrivals went back
        b.drain(np.array([0.0, 5.0, 4.0]))
    with pytest.raises(ValueError):                  # 2-D arrivals
        b.drain(np.zeros((2, 2)))
    with pytest.raises(ValueError):                  # runner, no requests
        MicroBatcher(BatchPolicy(), runner=lambda x: x,
                     service_model=LINEAR).drain(np.array([0.0]))


def test_batcher_empty_queue():
    res = MicroBatcher(BatchPolicy(), service_model=LINEAR).drain(
        np.array([], np.float64))
    assert res.n_requests == 0 and res.batches == []
    m = res.metrics()
    assert m["requests"] == 0 and m["batches"] == 0
    # the key set is schema-stable even with nothing served
    assert {"p50_ms", "p99_ms", "mean_ms", "throughput_rps",
            "buckets", "shed", "shed_frac", "stages_us"} <= set(m)


# ---------------------------------------------------------------------------
# Overload semantics: bounded queues, shedding, deadlines, degrade
# ---------------------------------------------------------------------------

def test_policy_overload_validation():
    with pytest.raises(ValueError):
        BatchPolicy(max_queue=-1)
    with pytest.raises(ValueError):
        BatchPolicy(deadline_us=-1.0)
    with pytest.raises(ValueError):
        BatchPolicy(shed="panic")
    # the long-form alias normalizes to the canonical name
    assert BatchPolicy(shed="degrade-to-smaller-bucket").shed == "degrade"
    assert BatchPolicy().shed == "reject"


def test_batcher_reject_sheds_arrivals():
    """shed='reject': an arrival finding the queue full is shed at its
    arrival time; everyone already queued is untouched."""
    pol = BatchPolicy(max_batch=1, max_queue=1, shed="reject")
    res = MicroBatcher(pol, service_model=LINEAR).drain(
        np.array([0.0, 10.0, 20.0, 30.0]))
    # r0 dispatches at 0 (engine busy to 110); r1 waits; r2, r3 find
    # the one waiting slot taken and are rejected on arrival
    np.testing.assert_array_equal(res.served, [True, True, False, False])
    np.testing.assert_array_equal(
        res.shed_reason, [0, 0, SHED_QUEUE_FULL, SHED_QUEUE_FULL])
    np.testing.assert_allclose(res.shed_time_us[2:], [20.0, 30.0])
    np.testing.assert_allclose(res.latencies_us[:2], [110.0, 210.0])
    assert np.isnan(res.latencies_us[2:]).all()
    assert np.isnan(res.completion_us[2:]).all()
    assert list(res.batch_index[2:]) == [-1, -1]
    assert res.metrics()["shed"] == {"queue_full": 2, "deadline": 0}
    assert res.metrics()["shed_frac"] == 0.5


def test_batcher_drop_oldest_shed_head():
    """shed='drop-oldest': the queue head is shed to admit the
    arrival, so the freshest requests survive overload."""
    pol = BatchPolicy(max_batch=1, max_queue=1, shed="drop-oldest")
    res = MicroBatcher(pol, service_model=LINEAR).drain(
        np.array([0.0, 10.0, 20.0, 30.0]))
    np.testing.assert_array_equal(res.served, [True, False, False, True])
    np.testing.assert_allclose(res.shed_time_us[1:3], [20.0, 30.0])
    # r3 dispatches when the engine frees at 110 -> latency 190
    np.testing.assert_allclose(res.latencies_us[[0, 3]], [110.0, 190.0])


def test_batcher_deadline_sheds_unreachable_requests():
    """A request still queued past arrival + deadline_us is shed with
    reason 'deadline' at its expiry time."""
    pol = BatchPolicy(max_batch=1, deadline_us=50.0)
    res = MicroBatcher(pol, service_model=LINEAR).drain(
        np.array([0.0, 10.0, 20.0]))
    # engine busy with r0 until 110; r1 expires at 60, r2 at 70
    np.testing.assert_array_equal(res.served, [True, False, False])
    np.testing.assert_array_equal(
        res.shed_reason, [0, SHED_DEADLINE, SHED_DEADLINE])
    np.testing.assert_allclose(res.shed_time_us[1:], [60.0, 70.0])
    assert res.metrics()["deadline_misses"] == 2


def test_batcher_deadline_aware_hold_window():
    """The batch hold window is clipped to the head's deadline: the
    partial batch dispatches exactly at the deadline and is served."""
    pol = BatchPolicy(max_batch=4, max_wait_us=100.0, deadline_us=40.0)
    res = MicroBatcher(pol, service_model=LINEAR).drain(
        np.array([0.0, 5.0]))
    assert len(res.batches) == 1
    assert res.batches[0].dispatch_us == 40.0     # deadline, not 100
    np.testing.assert_array_equal(res.served, [True, True])
    np.testing.assert_allclose(res.latencies_us, [160.0, 155.0])


def test_batcher_degrade_dispatches_exact_buckets():
    """shed='degrade' never sheds: over max_queue the batcher skips
    the hold window and serves the largest exact bucket (zero pad)."""
    pol = BatchPolicy(max_batch=8, max_queue=2, max_wait_us=1000.0,
                      shed="degrade")
    res = MicroBatcher(pol, service_model=LINEAR).drain(np.zeros(6))
    assert res.n_shed == 0
    # backlog 6 > 2: degraded dispatch of exactly 4 at t=0 (no pad);
    # backlog 2 <= 2: normal held dispatch at the 1000us horizon
    assert [(b.size, b.bucket, b.degraded, b.dispatch_us)
            for b in res.batches] == [(4, 4, True, 0.0),
                                      (2, 2, False, 1000.0)]
    assert np.all(res.pad_us == 0.0)              # exact buckets only
    assert res.metrics()["degraded_batches"] == 1


def test_stage_decomposition_sums_bit_exactly():
    """queue_wait + fill_wait + pad + compute == latencies_us, to the
    bit, served requests only; shed rows carry zero stages."""
    rng = np.random.default_rng(5)
    arr = np.cumsum(rng.exponential(30.0, 400))
    pol = BatchPolicy(max_batch=8, max_wait_us=40.0, max_queue=6,
                      deadline_us=900.0, shed="reject")
    res = MicroBatcher(pol, service_model=LINEAR).drain(arr)
    assert 0 < res.n_served < res.n_requests      # both populations
    s = res.served
    np.testing.assert_array_equal(res.stage_sum()[s], res.latencies_us[s])
    # the wall-clock identity holds to float rounding
    np.testing.assert_allclose(res.completion_us[s] - arr[s],
                               res.latencies_us[s])
    for stage in (res.queue_wait_us, res.fill_wait_us, res.pad_us,
                  res.compute_us):
        assert np.all(stage >= 0.0)
        assert np.all(stage[~s] == 0.0)
    m = res.metrics()
    assert set(m["stages_us"]) == {"queue_wait", "batch_fill", "pad",
                                   "compute"}
    assert sum(m["stages_us"].values()) == pytest.approx(
        res.latencies_us[s].mean())


def test_default_policy_has_no_overload_behavior():
    """max_queue=0 / deadline_us=0 reproduces the original unbounded
    queue bit-exactly: nothing shed, same pinned latencies."""
    res = MicroBatcher(BatchPolicy(max_batch=2),
                       service_model=LINEAR).drain(ARR)
    assert res.n_shed == 0 and np.all(res.served)
    np.testing.assert_allclose(res.latencies_us,
                               [110.0, 220.0, 210.0, 110.0, 219.0])


# ---------------------------------------------------------------------------
# MicroBatcher: hypothesis properties
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    policies = st.builds(
        BatchPolicy,
        max_batch=st.integers(min_value=1, max_value=16),
        max_wait_us=st.sampled_from([0.0, 30.0, 500.0]))
    arrival_gaps = st.lists(
        st.floats(min_value=0.0, max_value=800.0,
                  allow_nan=False, allow_infinity=False),
        min_size=1, max_size=64)

    @given(policies, arrival_gaps)
    @settings(max_examples=80, deadline=None)
    def test_property_served_exactly_once(policy, gaps):
        arr = np.cumsum(np.asarray(gaps))
        res = MicroBatcher(policy, service_model=LINEAR).drain(arr)
        # batches tile [0, N) contiguously: everything served once
        firsts = [b.first for b in res.batches]
        sizes = [b.size for b in res.batches]
        assert firsts[0] == 0 and sum(sizes) == len(arr)
        assert all(f + s == nf for f, s, nf
                   in zip(firsts, sizes, firsts[1:] + [len(arr)]))
        assert np.all(res.latencies_us > 0)

    @given(policies, arrival_gaps)
    @settings(max_examples=80, deadline=None)
    def test_property_buckets_always_in_policy_set(policy, gaps):
        arr = np.cumsum(np.asarray(gaps))
        res = MicroBatcher(policy, service_model=LINEAR).drain(arr)
        for b in res.batches:
            assert b.bucket in policy.buckets
            assert 1 <= b.size <= policy.max_batch <= max(policy.buckets)
            assert b.bucket >= b.size

    @given(policies, arrival_gaps,
           st.integers(min_value=1, max_value=5))
    @settings(max_examples=80, deadline=None)
    def test_property_fifo_preserved_per_stream(policy, gaps, n_streams):
        arr = np.cumsum(np.asarray(gaps))
        streams = np.arange(len(arr)) % n_streams   # interleaved clients
        res = MicroBatcher(policy, service_model=LINEAR).drain(arr)
        for s in range(n_streams):
            comp = res.completion_us[streams == s]
            assert np.all(np.diff(comp) >= 0)       # arrival order kept

    @given(policies, arrival_gaps)
    @settings(max_examples=80, deadline=None)
    def test_property_simulated_clock_monotone(policy, gaps):
        arr = np.cumsum(np.asarray(gaps))
        res = MicroBatcher(policy, service_model=LINEAR).drain(arr)
        # completions monotone in arrival order; dispatch causal and
        # serialized (engine busy until the previous batch finished)
        assert np.all(np.diff(res.completion_us) >= 0)
        assert np.all(res.dispatch_us >= arr)
        for prev, nxt in zip(res.batches, res.batches[1:]):
            assert nxt.dispatch_us >= prev.completion_us

    # overload policies: every shed mode, bounded queues, deadlines
    overload_policies = st.builds(
        BatchPolicy,
        max_batch=st.integers(min_value=1, max_value=8),
        max_wait_us=st.sampled_from([0.0, 30.0, 500.0]),
        max_queue=st.integers(min_value=0, max_value=4),
        deadline_us=st.sampled_from([0.0, 150.0, 2000.0]),
        shed=st.sampled_from(["reject", "drop-oldest", "degrade"]))

    @given(overload_policies, arrival_gaps)
    @settings(max_examples=100, deadline=None)
    def test_property_shed_requests_never_complete(policy, gaps):
        arr = np.cumsum(np.asarray(gaps))
        res = MicroBatcher(policy, service_model=LINEAR).drain(arr)
        assert res.n_served + res.n_shed == len(arr)
        shed = ~res.served
        # a shed request has no completion, no batch, a recorded
        # reason + time; a served one has all three and no reason
        assert np.isnan(res.completion_us[shed]).all()
        assert np.isnan(res.latencies_us[shed]).all()
        assert np.all(res.batch_index[shed] == -1)
        assert np.all(res.shed_reason[shed] != 0)
        assert not np.isnan(res.shed_time_us[shed]).any()
        assert not np.isnan(res.completion_us[res.served]).any()
        assert np.all(res.shed_reason[res.served] == 0)
        served_members = [r for b in res.batches for r in b.members]
        assert sorted(served_members) == \
            sorted(np.flatnonzero(res.served))
        if policy.shed == "degrade":    # degrade never sheds for
            assert res.shed_counts()["queue_full"] == 0   # queue-full

    @given(overload_policies, arrival_gaps)
    @settings(max_examples=100, deadline=None)
    def test_property_stage_sum_is_latency_bit_exact(policy, gaps):
        arr = np.cumsum(np.asarray(gaps))
        res = MicroBatcher(policy, service_model=LINEAR).drain(arr)
        s = res.served
        assert np.array_equal(res.stage_sum()[s], res.latencies_us[s])
        for stage in (res.queue_wait_us, res.fill_wait_us, res.pad_us,
                      res.compute_us):
            assert np.all(stage[~s] == 0.0) and np.all(stage >= 0.0)

    @given(overload_policies, arrival_gaps,
           st.integers(min_value=1, max_value=5))
    @settings(max_examples=100, deadline=None)
    def test_property_fifo_per_stream_survives_backpressure(
            policy, gaps, n_streams):
        arr = np.cumsum(np.asarray(gaps))
        streams = np.arange(len(arr)) % n_streams
        res = MicroBatcher(policy, service_model=LINEAR).drain(arr)
        for s in range(n_streams):
            comp = res.completion_us[(streams == s) & res.served]
            assert np.all(np.diff(comp) >= 0)   # survivors stay FIFO

    @given(st.lists(st.integers(min_value=0, max_value=800),
                    min_size=1, max_size=64),
           st.sampled_from([0.25, 0.5]),
           st.sampled_from([120.0, 400.0, 1500.0]))
    @settings(max_examples=100, deadline=None)
    def test_property_deadline_misses_monotone_in_offered_load(
            gaps, scale, deadline):
        """Compressing every inter-arrival gap (raising offered load)
        never decreases any request's queue wait — the Lindley
        recursion for the serial max_batch=1 queue — so the count of
        would-be deadline misses is monotone in offered load.
        Integer gaps + a power-of-two scale keep every simulated
        quantity exact in float64, so the comparison is bit-level."""
        arr = np.cumsum(np.asarray(gaps, np.float64))
        pol = BatchPolicy(max_batch=1)       # serial queue, no hold
        base = MicroBatcher(pol, service_model=LINEAR).drain(arr)
        loaded = MicroBatcher(pol, service_model=LINEAR).drain(
            arr * scale)
        assert np.all(loaded.queue_wait_us >= base.queue_wait_us)
        assert (loaded.queue_wait_us > deadline).sum() >= \
            (base.queue_wait_us > deadline).sum()
else:                                   # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_batcher_suite():
        pass


# ---------------------------------------------------------------------------
# MicroBatcher over the real engine: outputs bit-exact per request
# ---------------------------------------------------------------------------

def test_batcher_outputs_match_unbatched_runs(ff_program):
    g = ff_program.graph
    n = 10
    reqs = make_ext(g, n, 8, seed=2)
    arr = np.cumsum(np.full(n, 40.0))
    batcher = MicroBatcher(BatchPolicy(max_batch=4, max_wait_us=100.0),
                           runner=ff_program.run, service_model=LINEAR)
    res = batcher.drain(arr, reqs)
    assert res.outputs is not None
    spikes, v, pkts = res.outputs
    assert spikes.shape[0] == v.shape[0] == pkts.shape[0] == n
    for i in range(n):                   # padding never leaks into rows
        s1, v1, st1 = ff_program.run(reqs[i])
        assert spikes[i].tobytes() == s1.tobytes()
        assert v[i].tobytes() == v1.tobytes()
        np.testing.assert_array_equal(pkts[i], st1["packet_counts"])


def test_batcher_measured_mode_warms_buckets(ff_program):
    """service_model=None: real wall-clock service times, with one
    warm-up call per bucket so jit compile never lands in a latency."""
    g = ff_program.graph
    calls = []

    def runner(batch):
        calls.append(len(batch))
        return ff_program.run(batch)

    n = 5
    reqs = make_ext(g, n, 6, seed=9)
    arr = np.zeros(n)                    # all arrive at once
    res = MicroBatcher(BatchPolicy(max_batch=4),
                       runner=runner).drain(arr, reqs)
    # warm-up hit every bucket (1, 2, 4) before any timed batch
    assert calls[:3] == [1, 2, 4]
    assert np.all(res.latencies_us > 0)
    np.testing.assert_allclose(res.completion_us - arr, res.latencies_us)
    assert [b.service_us > 0 for b in res.batches] == [True, True]


def test_batcher_warm_cache_skips_repeat_drains(ff_program):
    """Warming is cached per (bucket, T, dtype): a second drain on the
    same shapes issues only real batch calls, no warm-up calls."""
    g = ff_program.graph
    calls = []

    def runner(batch):                   # plain function: no precompile
        calls.append(len(batch))         # hook, so warming is observable
        return ff_program.run(batch)

    batcher = MicroBatcher(BatchPolicy(max_batch=4), runner=runner)
    reqs = make_ext(g, 5, 6, seed=9)
    batcher.drain(np.zeros(5), reqs)
    # 3 warm calls (buckets 1, 2, 4) + 2 batch calls (sizes 4, 1)
    assert len(calls) == 5
    batcher.drain(np.zeros(5), reqs)     # same shapes: cache hit
    assert len(calls) == 7
    assert calls[5:] == [4, 1]           # batch dispatches only
    # a new T axis is a new compilation: warming runs again
    batcher.drain(np.zeros(5), make_ext(g, 5, 7, seed=9))
    assert calls[7:10] == [1, 2, 4]


# ---------------------------------------------------------------------------
# Sharded execution: bit-exact vs the single-device engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["feedforward", "recurrent"])
def test_sharded_bit_exact_ragged_batches(kind, ff_program, rec_program):
    program = ff_program if kind == "feedforward" else rec_program
    g = program.graph
    forced = ShardedRunner(program, min_shard=0)       # no fallback: every
    for b in ragged_sizes():                           # size pads-and-masks
        ext = make_ext(g, b, 12, seed=b)
        s1, v1, st1 = program.run(ext)                 # single-device jax
        for s2, v2, st2 in (program.run(ext, ExecutionSpec(mesh="auto")),
                            forced.run(ext)):
            assert s2.tobytes() == s1.tobytes(), f"spikes differ at B={b}"
            assert v2.tobytes() == v1.tobytes(), f"v_final differs at B={b}"
            assert st2["packet_counts"].tobytes() == \
                st1["packet_counts"].tobytes(), f"packets differ at B={b}"
            assert st2["mean_packets_per_step"] == \
                st1["mean_packets_per_step"]


def test_sharded_unbatched_input_squeezes(rec_program):
    g = rec_program.graph
    ext = make_ext(g, 1, 9, seed=1)[0]                 # [T, n_in]
    s1, v1, st1 = rec_program.run(ext)
    s2, v2, st2 = rec_program.run(ext, ExecutionSpec(mesh="auto"))
    assert s2.shape == s1.shape and v2.shape == v1.shape
    assert s2.tobytes() == s1.tobytes()
    np.testing.assert_array_equal(st2["packet_counts"],
                                  st1["packet_counts"])


def test_sharded_runner_owned_and_cached(rec_program):
    r1 = rec_program.sharded_runner()
    assert rec_program.sharded_runner() is r1          # cached like engines
    mesh = make_serving_mesh()
    assert rec_program.sharded_runner(mesh) is \
        rec_program.sharded_runner(mesh)
    assert r1.n_shards == int(mesh.shape["data"])
    assert r1.padded_size(1) == r1.n_shards            # pad-and-mask rule
    assert r1.padded_size(3 * r1.n_shards + 1) == 4 * r1.n_shards


def test_sharded_rejects_bad_requests(rec_program):
    with pytest.raises(ValueError, match="mesh= shards the jax"):
        ExecutionSpec(engine="python", mesh="auto")
    # the deprecated kwargs shim keeps its exact historical error
    with pytest.deprecated_call(), \
            pytest.raises(ValueError, match="sharded=True runs the jax"):
        rec_program.run(make_ext(rec_program.graph, 1, 4), sharded=True,
                        engine="python")
    with pytest.raises(ValueError, match="lack 'data'"):
        ShardedRunner(rec_program, jax.make_mesh((1,), ("model",)))
    with pytest.raises(ValueError, match="ext_spikes shape"):
        rec_program.sharded_runner().run(np.zeros((4, 5), np.int32))


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------

def test_registry_rejects_duplicate_names(ff_program, rec_program):
    reg = ProgramRegistry()
    reg.register("m", ff_program)
    with pytest.raises(ValueError, match="already registered"):
        reg.register("m", rec_program)
    with pytest.raises(ValueError):
        reg.register("", ff_program)
    assert reg.names() == ("m",) and "m" in reg and len(reg) == 1


def test_registry_lookup_and_unregister(ff_program):
    reg = ProgramRegistry()
    with pytest.raises(KeyError, match="not registered"):
        reg.get("missing")
    reg.register("m", ff_program)
    assert reg.get("m") is ff_program
    assert reg.unregister("m") is ff_program
    with pytest.raises(KeyError):
        reg.unregister("m")
    reg.register("m", ff_program)                      # re-register ok


def test_registry_engine_ownership_per_model(ff_program, rec_program):
    reg = ProgramRegistry()
    reg.register("a", ff_program)
    reg.register("b", rec_program)
    # engines are lazy, owned by each Program, reused across lookups
    assert reg.get("a").engine() is reg.get("a").engine()
    assert reg.get("a").engine() is not reg.get("b").engine()
    sharded_spec = ExecutionSpec(mesh="auto")
    assert reg.runner("a", sharded_spec).__self__ is \
        reg.runner("a", sharded_spec).__self__         # one ShardedRunner
    ext = make_ext(ff_program.graph, 2, 6, seed=0)
    s1, _, _ = reg.runner("a")(ext)
    s2, _, _ = ff_program.run(ext)
    np.testing.assert_array_equal(s1, s2)


def test_registry_load_from_artifact(ff_program, tmp_path):
    path = ff_program.save(tmp_path / "m.npz")
    reg = ProgramRegistry()
    p = reg.load("m", path)
    assert p.ot_depth == ff_program.ot_depth
    ext = make_ext(ff_program.graph, 2, 6, seed=3)
    np.testing.assert_array_equal(p.run(ext)[0], ff_program.run(ext)[0])


# ---------------------------------------------------------------------------
# Server loop
# ---------------------------------------------------------------------------

def _stream(ff_program, rec_program, seed=4, n=12):
    rng = np.random.default_rng(seed)
    stream, t = [], 0.0
    for i in range(n):
        t += float(rng.exponential(150.0))
        name = "ff" if i % 3 else "rec"
        g = (ff_program if name == "ff" else rec_program).graph
        ext = (rng.random((8, g.n_inputs)) < 0.3).astype(np.int32)
        stream.append(Request(name, ext, t, stream=i % 2))
    return stream


def test_server_metrics_dict(ff_program, rec_program):
    reg = ProgramRegistry()
    reg.register("ff", ff_program)
    reg.register("rec", rec_program)
    srv = Server(reg, policy=BatchPolicy(max_batch=4, max_wait_us=60.0),
                 service_model=LINEAR)
    metrics = srv.serve(_stream(ff_program, rec_program))
    assert set(metrics) == {"models", "total"}
    assert set(metrics["models"]) == {"ff", "rec"}
    for m in metrics["models"].values():
        assert {"p50_ms", "p99_ms", "throughput_rps",
                "buckets"} <= set(m)
        assert all(b in (1, 2, 4) for b in m["buckets"])
    assert metrics["total"]["requests"] == 12
    assert metrics["total"]["models"] == 2
    # deterministic: same stream, same metrics (simulated clock)
    assert srv.serve(_stream(ff_program, rec_program)) == metrics


def test_server_rejects_unknown_model(ff_program):
    reg = ProgramRegistry()
    reg.register("ff", ff_program)
    srv = Server(reg, service_model=LINEAR)
    bad = [Request("nope", np.zeros((4, 16), np.int32), 0.0)]
    with pytest.raises(KeyError, match="nope"):
        srv.serve(bad)


def test_server_per_model_policy_override(ff_program, rec_program):
    reg = ProgramRegistry()
    reg.register("ff", ff_program)
    reg.register("rec", rec_program)
    srv = Server(reg, policy=BatchPolicy(max_batch=4, max_wait_us=1e6),
                 policies={"rec": BatchPolicy(max_batch=1)},
                 service_model=LINEAR)
    metrics = srv.serve(_stream(ff_program, rec_program))
    assert set(metrics["models"]["rec"]["buckets"]) == {1}   # no batching
    assert max(metrics["models"]["ff"]["buckets"]) > 1       # held + batched


def test_server_two_model_shared_timeline_regression(ff_program,
                                                     rec_program):
    """Totals regression: two models, one request each at t=0, on ONE
    engine. The pre-timeline server reported both models completing at
    110us as if they ran concurrently; on the shared timeline the
    second dispatch waits for the first, so the corrected span is
    220us and throughput exactly halves."""
    reg = ProgramRegistry()
    reg.register("ff", ff_program)
    reg.register("rec", rec_program)
    mk = lambda name, p: Request(
        name, np.zeros((8, p.graph.n_inputs), np.int32), 0.0)
    stream = [mk("ff", ff_program), mk("rec", rec_program)]

    shared = Server(reg, policy=BatchPolicy(max_batch=1),
                    service_model=LINEAR).serve(stream)
    t = shared["total"]
    assert t["timeline"] == "shared" and t["requests"] == 2
    # queue order is sorted model names: ff at [0, 110], rec [110, 220]
    assert t["p50_ms"] == pytest.approx(0.165)          # (110+220)/2 us
    assert t["throughput_rps"] == pytest.approx(2 / 220e-6)

    per = Server(reg, policy=BatchPolicy(max_batch=1),
                 service_model=LINEAR,
                 timeline="per-engine").serve(stream)
    # dedicated engines: both complete at 110us, double the throughput
    assert per["total"]["timeline"] == "per-engine"
    assert per["total"]["p50_ms"] == pytest.approx(0.110)
    assert per["total"]["throughput_rps"] == pytest.approx(2 / 110e-6)

    with pytest.raises(ValueError, match="timeline"):
        Server(reg, timeline="concurrent-ish", service_model=LINEAR)


def test_server_shared_timeline_interleaves_engine(ff_program,
                                                   rec_program):
    """Per-model completions on the shared timeline reflect the one
    serially-busy engine, not per-model clocks from zero."""
    reg = ProgramRegistry()
    reg.register("a", ff_program)
    reg.register("b", rec_program)
    ext = {n: np.zeros((8, p.graph.n_inputs), np.int32)
           for n, p in (("a", ff_program), ("b", rec_program))}
    srv = Server(reg, policy=BatchPolicy(max_batch=1),
                 service_model=LINEAR)
    srv.serve([Request("a", ext["a"], 0.0), Request("b", ext["b"], 0.0)])
    np.testing.assert_allclose(
        srv.last_results["a"].completion_us, [110.0])
    np.testing.assert_allclose(
        srv.last_results["b"].completion_us, [220.0])


def test_server_ragged_shapes_raise_named_valueerror(ff_program):
    reg = ProgramRegistry()
    reg.register("ff", ff_program)
    srv = Server(reg, service_model=LINEAR)
    n_in = ff_program.graph.n_inputs
    good = Request("ff", np.zeros((8, n_in), np.int32), 0.0, stream=0)
    ragged = Request("ff", np.zeros((9, n_in), np.int32), 1.0, stream=3)
    with pytest.raises(ValueError, match=r"request #1 for model 'ff' "
                                         r"\(stream 3\)"):
        srv.serve([good, ragged])
    flat = Request("ff", np.zeros(n_in, np.int32), 0.0, stream=1)
    with pytest.raises(ValueError, match="2-D"):
        srv.serve([flat])


def test_server_resolves_registry_attached_policy(ff_program):
    reg = ProgramRegistry()
    reg.register("ff", ff_program, policy=BatchPolicy(max_batch=1))
    assert reg.policy("ff").max_batch == 1
    with pytest.raises(KeyError):
        reg.policy("missing")
    srv = Server(reg, policy=BatchPolicy(max_batch=8),
                 service_model=LINEAR)
    assert srv.policy_for("ff").max_batch == 1     # registry wins default
    srv2 = Server(reg, policies={"ff": BatchPolicy(max_batch=4)},
                  service_model=LINEAR)
    assert srv2.policy_for("ff").max_batch == 4    # explicit wins registry
    reg.unregister("ff")
    reg.register("ff", ff_program)                 # policy was dropped too
    assert reg.policy("ff") is None
    assert srv.policy_for("ff").max_batch == 8     # falls back to default


def test_server_metrics_carry_shed_and_stage_accounting(ff_program):
    reg = ProgramRegistry()
    reg.register("ff", ff_program)
    n_in = ff_program.graph.n_inputs
    stream = [Request("ff", np.zeros((8, n_in), np.int32), 10.0 * i)
              for i in range(4)]
    srv = Server(reg, policy=BatchPolicy(max_batch=1, max_queue=1,
                                         shed="reject"),
                 service_model=LINEAR)
    m = srv.serve(stream)
    assert m["models"]["ff"]["shed"] == {"queue_full": 2, "deadline": 0}
    assert m["total"]["shed"] == {"queue_full": 2, "deadline": 0}
    assert m["total"]["shed_frac"] == 0.5
    assert m["total"]["deadline_misses"] == 0
    assert set(m["total"]["stages_us"]) == {"queue_wait", "batch_fill",
                                            "pad", "compute"}
    res = srv.last_results["ff"]
    s = res.served
    np.testing.assert_array_equal(res.stage_sum()[s],
                                  res.latencies_us[s])


# ---------------------------------------------------------------------------
# AsyncServer: real-clock backpressure as exceptions
# ---------------------------------------------------------------------------

SLOW_50MS = linear_service_model(50_000.0, 0.0)


async def _eventually(pred, timeout=5.0):
    """Poll until ``pred()`` — bounds timing races without sleeps
    tuned to scheduler luck."""
    loop = asyncio.get_running_loop()
    end = loop.time() + timeout
    while not pred():
        if loop.time() > end:
            raise AssertionError("condition not reached in time")
        await asyncio.sleep(0.005)


def _async_registry(program):
    reg = ProgramRegistry()
    reg.register("m", program)
    return reg


def _req(program, seed=0):
    g = program.graph
    rng = np.random.default_rng(seed)
    return Request("m", (rng.random((6, g.n_inputs)) < 0.3)
                   .astype(np.int32), 0.0, stream=seed)


def test_async_server_serves_with_bit_exact_stages(ff_program):
    async def main():
        srv = AsyncServer(
            _async_registry(ff_program),
            policy=BatchPolicy(max_batch=4, max_wait_us=3000.0),
            service_model=linear_service_model(2000.0, 100.0))
        async with srv:
            done = await asyncio.gather(
                *[srv.submit(_req(ff_program, i)) for i in range(8)])
        for c in done:
            total = ((c.queue_wait_us + c.fill_wait_us)
                     + c.pad_us) + c.compute_us
            assert total == c.latency_us            # bit-exact, real clock
            assert c.model == "m" and c.bucket in (1, 2, 4)
            assert 1 <= c.batch_size <= 4 and not c.degraded
        assert sorted(c.stream for c in done) == list(range(8))
        m = srv.metrics()
        assert m["total"]["requests"] == 8
        assert m["total"]["timeline"] == "real"
        assert m["total"]["shed"] == {"queue_full": 0, "deadline": 0}
        assert set(m["total"]["stages_us"]) == {"queue_wait", "batch_fill",
                                                "pad", "compute"}
    asyncio.run(main())


def test_async_server_lifecycle_and_unknown_model(ff_program):
    async def main():
        srv = AsyncServer(_async_registry(ff_program),
                          service_model=SLOW_50MS)
        with pytest.raises(RuntimeError, match="not started"):
            await srv.submit(_req(ff_program))
        async with srv:
            with pytest.raises(KeyError, match="nope"):
                await srv.submit(Request("nope", np.zeros((4, 2),
                                                          np.int32), 0.0))
            with pytest.raises(RuntimeError, match="already started"):
                await srv.start()
    asyncio.run(main())


def test_async_server_reject_backpressure(ff_program):
    async def main():
        srv = AsyncServer(
            _async_registry(ff_program),
            policy=BatchPolicy(max_batch=1, max_queue=1, shed="reject"),
            service_model=SLOW_50MS)
        async with srv:
            t1 = asyncio.create_task(srv.submit(_req(ff_program, 1)))
            await _eventually(lambda: srv._dequeued["m"] == 1)
            t2 = asyncio.create_task(srv.submit(_req(ff_program, 2)))
            await _eventually(lambda: len(srv._queues["m"]) == 1)
            with pytest.raises(QueueFullError, match="queue full"):
                await srv.submit(_req(ff_program, 3))
            done = await asyncio.gather(t1, t2)
        assert [c.stream for c in done] == [1, 2]   # FIFO survivors
        m = srv.metrics()
        assert m["total"]["shed"] == {"queue_full": 1, "deadline": 0}
        assert m["total"]["shed_frac"] == pytest.approx(1 / 3)
    asyncio.run(main())


def test_async_server_drop_oldest_fails_the_old_await(ff_program):
    async def main():
        srv = AsyncServer(
            _async_registry(ff_program),
            policy=BatchPolicy(max_batch=1, max_queue=1,
                               shed="drop-oldest"),
            service_model=SLOW_50MS)
        async with srv:
            t1 = asyncio.create_task(srv.submit(_req(ff_program, 1)))
            await _eventually(lambda: srv._dequeued["m"] == 1)
            t2 = asyncio.create_task(srv.submit(_req(ff_program, 2)))
            await _eventually(lambda: len(srv._queues["m"]) == 1)
            t3 = asyncio.create_task(srv.submit(_req(ff_program, 3)))
            r1, r2, r3 = await asyncio.gather(t1, t2, t3,
                                              return_exceptions=True)
        assert r1.stream == 1 and r3.stream == 3    # newest survived
        assert isinstance(r2, QueueFullError)       # oldest was shed
        assert "drop-oldest" in str(r2)
    asyncio.run(main())


def test_async_server_deadline_miss_raises(ff_program):
    async def main():
        srv = AsyncServer(
            _async_registry(ff_program),
            policy=BatchPolicy(max_batch=1, deadline_us=10_000.0),
            service_model=linear_service_model(60_000.0, 0.0))
        async with srv:
            t1 = asyncio.create_task(srv.submit(_req(ff_program, 1)))
            await _eventually(lambda: srv._dequeued["m"] == 1)
            t2 = asyncio.create_task(srv.submit(_req(ff_program, 2)))
            r1, r2 = await asyncio.gather(t1, t2, return_exceptions=True)
        assert r1.stream == 1
        assert isinstance(r2, DeadlineMissError)
        assert srv.metrics()["total"]["deadline_misses"] == 1
    asyncio.run(main())


def test_async_server_stop_without_drain_sheds_pending(ff_program):
    async def main():
        srv = AsyncServer(
            _async_registry(ff_program),
            policy=BatchPolicy(max_batch=1),
            service_model=SLOW_50MS)
        await srv.start()
        t1 = asyncio.create_task(srv.submit(_req(ff_program, 1)))
        await _eventually(lambda: srv._dequeued["m"] == 1)
        t2 = asyncio.create_task(srv.submit(_req(ff_program, 2)))
        await _eventually(lambda: len(srv._queues["m"]) == 1)
        await srv.stop(drain=False)
        r1, r2 = await asyncio.gather(t1, t2, return_exceptions=True)
        assert r1.stream == 1                       # in flight: finished
        assert isinstance(r2, ShedError)            # queued: shed
        assert not isinstance(r2, (QueueFullError, DeadlineMissError))
    asyncio.run(main())


def test_async_server_engine_mode_outputs_bit_exact(ff_program):
    async def main():
        srv = AsyncServer(_async_registry(ff_program),
                          policy=BatchPolicy(max_batch=2,
                                             max_wait_us=5000.0))
        reqs = [_req(ff_program, i) for i in range(3)]
        async with srv:
            done = await asyncio.gather(*[srv.submit(r) for r in reqs])
        by_stream = {c.stream: c for c in done}
        for i, r in enumerate(reqs):
            c = by_stream[i]
            s1, v1, st1 = ff_program.run(r.ext)
            assert c.outputs[0].tobytes() == s1.tobytes()
            assert c.outputs[1].tobytes() == v1.tobytes()
            np.testing.assert_array_equal(c.outputs[2],
                                          st1["packet_counts"])
    asyncio.run(main())


# ---------------------------------------------------------------------------
# Golden artifact: the save/load format pin
# ---------------------------------------------------------------------------

def test_golden_artifact_loads_and_runs_bit_exact():
    program = Program.load(GOLDEN / "tiny_program_v1.npz")
    assert program.feasible
    with np.load(GOLDEN / "tiny_program_v1_io.npz") as io:
        for engine in ("python", "jax", "oracle"):
            s, v, stats = program.run(io["ext"], engine)
            np.testing.assert_array_equal(s, io["spikes"], err_msg=engine)
            np.testing.assert_array_equal(v, io["v_final"], err_msg=engine)
            np.testing.assert_array_equal(stats["packet_counts"],
                                          io["packet_counts"],
                                          err_msg=engine)


def test_golden_artifact_roundtrips_byte_exact(tmp_path):
    program = Program.load(GOLDEN / "tiny_program_v1.npz")
    resaved = program.save(tmp_path / "resaved.npz")
    with np.load(GOLDEN / "tiny_program_v1.npz") as a, \
            np.load(resaved) as b:
        assert set(a.files) == set(b.files)
        assert json.loads(str(a["header"][()])) == \
            json.loads(str(b["header"][()]))
        for k in a.files:
            if k != "header":
                assert a[k].tobytes() == b[k].tobytes(), k
                assert a[k].dtype == b[k].dtype, k


def _rewrite_header(src: Path, dst: Path, mutate) -> Path:
    """Copy an artifact npz with a mutated JSON header."""
    with np.load(src) as z:
        arrays = {k: z[k] for k in z.files}
    header = json.loads(str(arrays["header"][()]))
    mutate(header)
    arrays["header"] = np.asarray(json.dumps(header))
    np.savez_compressed(dst, **arrays)
    return dst


def test_golden_artifact_wrong_version_rejected(tmp_path):
    bad = _rewrite_header(
        GOLDEN / "tiny_program_v1.npz", tmp_path / "bad_version.npz",
        lambda h: h.update(version=h["version"] + 1))
    with pytest.raises(ValueError, match="version"):
        Program.load(bad)
    worse = _rewrite_header(
        GOLDEN / "tiny_program_v1.npz", tmp_path / "bad_format.npz",
        lambda h: h.update(format="not-a-program"))
    with pytest.raises(ValueError, match="format"):
        Program.load(worse)
    # not-an-artifact npz
    np.savez_compressed(tmp_path / "junk.npz", x=np.arange(3))
    with pytest.raises(ValueError, match="artifact"):
        Program.load(tmp_path / "junk.npz")
    with zipfile.ZipFile(GOLDEN / "tiny_program_v1.npz") as z:
        assert "header.npy" in z.namelist()            # format layout pin


# ---------------------------------------------------------------------------
# Example seeding: two runs, identical p50/p99
# ---------------------------------------------------------------------------

def _load_example():
    path = Path(__file__).parent.parent / "examples" / "serve_snn.py"
    spec = importlib.util.spec_from_file_location("serve_snn_example", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_example_seed_determinism(tmp_path):
    mod = _load_example()
    argv = ["--artifact", str(tmp_path / "demo.npz"),
            "--requests", "24", "--timesteps", "8", "--seed", "7"]
    m1 = mod.main(argv)
    m2 = mod.main(argv)                 # artifact reloaded, not recompiled
    assert m1["p50_ms"] == m2["p50_ms"]
    assert m1["p99_ms"] == m2["p99_ms"]
    assert m1["buckets"] == m2["buckets"]
    m3 = mod.main(argv[:-1] + ["8"])    # different seed, different stream
    assert (m3["p50_ms"], m3["p99_ms"]) != (m1["p50_ms"], m1["p99_ms"])


def test_async_server_keeps_a_bounded_window_without_outputs(ff_program,
                                                            monkeypatch):
    """The server keeps its last ``KEEP_COMPLETED`` completions, none of
    them holding outputs; each caller's result still carries its own,
    and every batch is one engine call across the executor hop."""
    from repro.core.profiling import span_log
    from repro.serve import async_server
    monkeypatch.setattr(async_server, "KEEP_COMPLETED", 3)

    async def main():
        srv = AsyncServer(_async_registry(ff_program),
                          policy=BatchPolicy(max_batch=2))
        async with srv:
            reqs = [_req(ff_program, i) for i in range(8)]
            done = await asyncio.gather(*[srv.submit(r) for r in reqs])
        return srv, reqs, done

    first = span_log().written
    srv, reqs, done = asyncio.run(main())
    recs = span_log().records()[-(span_log().written - first):]
    assert len(srv._completed["m"]) == 3
    assert len(srv._completion_ts["m"]) == 3
    assert not any(hasattr(c, "outputs") for c in srv._completed["m"])
    assert [c.latency_us for c in srv._completed["m"]] == \
        [c.latency_us for c in done[-3:]]
    for r, c in zip(reqs, done):
        s_ref, v_ref, _ = ff_program.run(r.ext)
        np.testing.assert_array_equal(c.outputs[0], s_ref)
        np.testing.assert_array_equal(c.outputs[1], v_ref)
    m = srv.metrics()
    assert m["total"]["requests"] == 3 and m["total"]["shed_frac"] == 0.0
    calls = {}
    for rec in recs:
        calls.setdefault(rec.call_id, []).append(rec)
    served = [c for c in calls.values()
              if any(x.name == "repro.serve.engine" for x in c)]
    assert len(served) == srv._batch_count["m"]
    for c in served:
        names = sorted(x.name for x in c)
        assert names == sorted(["repro.serve.batch", "repro.serve.engine",
                                "repro.engine.run", "repro.engine.prepare",
                                "repro.engine.upload", "repro.engine.launch",
                                "repro.engine.wait",
                                "repro.engine.download"])
