"""Parity tests for the compiled batched executor (engine_jax).

The deterministic-commit property must survive the lowering: for any
mapped program, ``run_mapped_batched`` must equal ``run_oracle`` (and
hence ``run_mapped``) BIT-EXACTLY, and its per-timestep MC packet counts
must equal ``run_mapped``'s stats so CycleModel reports are unchanged.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import make_ext, make_feedforward, make_hw
from repro.configs.snn_paper import mnist_scale_random_graph
from repro.core import compile as program_compile
from repro.core import (ExecutionSpec, JaxMappedEngine, KERNELS,
                        lower_tables, random_graph, run_mapped,
                        run_mapped_batched, run_oracle)
from repro.core.engine_jax import normalize_ext_spikes
from repro.core.engine import oracle_packet_counts


_hw, _feedforward, _ext = make_hw, make_feedforward, make_ext


@pytest.mark.parametrize("kernel", KERNELS)
def test_recurrent_batched_bit_exact_vs_oracle(kernel):
    g = random_graph(12, 20, 160, seed=3)   # pre spans inputs AND internal
    assert (g.pre >= g.n_inputs).any(), "graph must contain recurrence"
    tables = program_compile(g, _hw(g), max_iters=4000).tables
    ext = _ext(g, b=4, t=9, seed=1)
    s, v, _ = JaxMappedEngine(g, tables,
                              ExecutionSpec(kernel=kernel)).run(ext)
    for b in range(ext.shape[0]):
        s_ref, v_ref = run_oracle(g, ext[b])
        np.testing.assert_array_equal(s[b], s_ref)
        np.testing.assert_array_equal(v[b], v_ref)


def test_feedforward_batched_bit_exact_vs_oracle():
    g = _feedforward()
    tables = program_compile(g, _hw(g), max_iters=4000).tables
    ext = _ext(g, b=3, t=12, rate=0.5, seed=2)
    s, v, _ = JaxMappedEngine(g, tables).run(ext)
    for b in range(ext.shape[0]):
        s_ref, v_ref = run_oracle(g, ext[b])
        np.testing.assert_array_equal(s[b], s_ref)
        np.testing.assert_array_equal(v[b], v_ref)


def test_packet_counts_match_run_mapped_stats():
    g = random_graph(10, 14, 100, seed=7)
    tables = program_compile(g, _hw(g), max_iters=4000).tables
    ext = _ext(g, b=3, t=8, seed=4)
    _, _, stats = JaxMappedEngine(g, tables).run(ext)
    assert stats["packet_counts"].shape == (3, 8)
    for b in range(3):
        _, _, ref = run_mapped(g, tables, ext[b])
        np.testing.assert_array_equal(stats["packet_counts"][b],
                                      ref["packet_counts"])
    assert stats["mean_packets_per_step"] == pytest.approx(
        float(stats["packet_counts"].mean()))


def test_unbatched_input_matches_run_mapped_shapes():
    g = random_graph(8, 10, 60, seed=9)
    tables = program_compile(g, _hw(g), max_iters=4000).tables
    ext = _ext(g, b=1, t=6, seed=5)[0]
    s_j, v_j, st_j = JaxMappedEngine(g, tables).run(ext)
    s_p, v_p, st_p = run_mapped(g, tables, ext)
    assert s_j.shape == s_p.shape and v_j.shape == v_p.shape
    np.testing.assert_array_equal(s_j, s_p)
    np.testing.assert_array_equal(v_j, v_p)
    np.testing.assert_array_equal(st_j["packet_counts"],
                                  st_p["packet_counts"])


def test_mnist_scale_graph_bit_exact():
    """Acceptance: bit-exact on the MNIST-scale graph (784-126, 16 SPUs)."""
    g, hw = mnist_scale_random_graph()
    program = program_compile(g, hw, max_iters=40000)
    tables = program.tables
    assert program.report.feasible
    ext = _ext(g, b=2, t=10, rate=0.2, seed=0)
    s, v, stats = JaxMappedEngine(g, tables).run(ext)
    for b in range(2):
        s_ref, v_ref = run_oracle(g, ext[b])
        np.testing.assert_array_equal(s[b], s_ref)
        np.testing.assert_array_equal(v[b], v_ref)
    _, _, ref = run_mapped(g, tables, ext[0])
    np.testing.assert_array_equal(stats["packet_counts"][0],
                                  ref["packet_counts"])


def test_engine_reuse_and_ownership():
    g = random_graph(8, 10, 60, seed=11)
    tables = program_compile(g, _hw(g), max_iters=4000).tables
    eng = JaxMappedEngine(g, tables)
    a = eng.run(_ext(g, 2, 5, seed=1))
    b = eng.run(_ext(g, 2, 5, seed=1))          # same input, same engine
    np.testing.assert_array_equal(a[0], b[0])
    # engines are owned by the Program artifact now; the fragile
    # id()-keyed module cache is gone and the wrapper warns
    from repro.core import engine_jax
    assert not hasattr(engine_jax, "_ENGINE_CACHE")
    with pytest.deprecated_call():
        c = run_mapped_batched(g, tables, _ext(g, 2, 5, seed=1))
    np.testing.assert_array_equal(a[0], c[0])
    prog = program_compile(g, _hw(g), max_iters=4000)
    assert prog.engine() is prog.engine()       # reused across run() calls


def test_lower_tables_covers_all_synapses():
    g = random_graph(10, 12, 90, seed=13)
    tables = program_compile(g, _hw(g), max_iters=4000).tables
    lw = lower_tables(g, tables)
    assert lw.n_ops == g.n_synapses
    got = sorted(zip(lw.op_pre.tolist(),
                     (lw.op_post_local + g.n_inputs).tolist(),
                     lw.op_weight.tolist()))
    want = sorted(zip(g.pre.tolist(), g.post.tolist(), g.weight.tolist()))
    assert got == want
    # slot-major commit order
    assert (np.diff(lw.op_slot) >= 0).all()
    # routing bitmap: SPU i flagged for q iff q has a synapse mapped there
    for q in range(g.n_neurons):
        spus = set(tables.assign[g.pre == q].tolist())
        assert set(np.flatnonzero(lw.routing[q]).tolist()) == spus


# -- the input train crosses to the device as int8 ---------------------------

INPUT_DTYPES = [np.int32, np.int8, np.uint8, np.bool_]


@pytest.fixture(scope="module")
def rec_program():
    g = random_graph(12, 20, 160, seed=3)
    return program_compile(g, _hw(g), max_iters=4000)


def _assert_matches_oracle(g, ext, out):
    s, v, stats = out
    for b in range(ext.shape[0]):
        s_ref, v_ref = run_oracle(g, ext[b].astype(np.int32))
        np.testing.assert_array_equal(s[b], s_ref)
        np.testing.assert_array_equal(v[b], v_ref)
        np.testing.assert_array_equal(
            stats["packet_counts"][b],
            oracle_packet_counts(ext[b].astype(np.int32), s_ref))


@pytest.mark.parametrize("dtype", INPUT_DTYPES,
                         ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("kernel", KERNELS)
def test_every_input_dtype_bit_exact_vs_oracle(rec_program, kernel, dtype):
    """A caller's int32, int8, uint8 or bool train gives the oracle's
    spikes, potentials and packet counts on every tier."""
    g = rec_program.graph
    ext = _ext(g, b=3, t=7, seed=6).astype(dtype)
    out = rec_program.run(ext, ExecutionSpec(kernel=kernel))
    _assert_matches_oracle(g, ext, out)


@pytest.mark.parametrize("dtype", INPUT_DTYPES,
                         ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("min_shard", [0, 1 << 20],
                         ids=["shard_path", "fallback"])
def test_sharded_every_input_dtype_bit_exact_vs_oracle(rec_program,
                                                       min_shard, dtype):
    """The same on the sharded runner, through the shard path and
    through its single-device fallback."""
    from repro.serve.sharded import ShardedRunner
    runner = ShardedRunner(rec_program, min_shard=min_shard)
    g = rec_program.graph
    ext = _ext(g, b=3, t=7, seed=7).astype(dtype)
    assert runner._use_fallback(3) == bool(min_shard)
    _assert_matches_oracle(g, ext, runner.run(ext))


_FOUR_DEVICES = """
import numpy as np
from conftest import make_ext, make_hw
from repro.core import compile, random_graph, run_oracle
from repro.core.profiling import span_log
from repro.serve import ShardedRunner
g = random_graph(10, 20, 160, seed=3)
r = ShardedRunner(compile(g, make_hw(g), max_iters=4000), min_shard=0)
assert r.n_shards == 4
ext = make_ext(g, 6, 7, seed=8)             # the last chip holds 2 pad rows
first = span_log().written
s, v, st = r.run(ext.astype(np.uint8))
up = [x for x in span_log().records()[-(span_log().written - first):]
      if x.name == "repro.engine.upload"]
assert [x.nbytes for x in up] == [8 * 7 * 10], up
for i in range(6):
    s_ref, v_ref = run_oracle(g, ext[i])
    assert (s[i] == s_ref).all() and (v[i] == v_ref).all()
spikes = r.shard_outputs(ext)[0]
assert sorted(x.device.id for x in spikes.addressable_shards) == [0, 1, 2, 3]
bad = ext.copy()
bad[5, 3, 2] = 256                          # in the third chip's rows
try:
    r.run(bad)
except ValueError as e:
    assert "must be 0/1" in str(e)
else:
    raise AssertionError("256 was not refused")
print("ok")
"""


def test_sharded_train_splits_over_four_devices():
    """On four devices each chip's rows are checked, narrowed, padded
    and sent apart: the outputs match the oracle, the upload counts one
    byte per spike of the padded batch, and a 256 in a later chip's
    rows is refused."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(sys.path))
    out = subprocess.run([sys.executable, "-c", _FOUR_DEVICES], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().splitlines()[-1] == "ok"


@pytest.mark.parametrize("dtype,value", [
    (np.int32, 256), (np.int32, -255), (np.int64, 1 << 32),
    (np.int8, 2), (np.int8, -1), (np.uint8, 2), (np.float32, 1.5)],
    ids=lambda x: str(x) if not isinstance(x, type) else np.dtype(x).name)
def test_non_binary_refused_before_narrowing(rec_program, dtype, value):
    """The 0/1 check reads the caller's values, not their low byte: a
    256 or a -255 would narrow to 0 or 1, and is refused."""
    ext = _ext(rec_program.graph, 2, 5, seed=0).astype(dtype)
    ext[1, 3, 2] = value
    with pytest.raises(ValueError, match="must be 0/1"):
        normalize_ext_spikes(ext, rec_program.graph.n_inputs)


def test_validated_train_is_int8(rec_program):
    n = rec_program.graph.n_inputs
    ext = _ext(rec_program.graph, 2, 5, seed=1)
    got, squeeze = normalize_ext_spikes(ext, n)
    assert got.dtype == np.int8 and not squeeze
    np.testing.assert_array_equal(got, ext)
    got, squeeze = normalize_ext_spikes(ext[0].T.copy().T, n)   # [T, n] F
    assert got.dtype == np.int8 and got.shape == (1, 5, n) and squeeze
    np.testing.assert_array_equal(got[0], ext[0])
