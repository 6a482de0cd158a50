"""Bit-exactness pins for the fused step megakernel (kernels/fused_step).

The fused tier collapses routing + per-SPU accumulation + Neuron Unit
into one pallas_call; the deterministic-commit property (paper §4.2)
says it must be BIT-identical — spikes, final potentials AND per-step
MC packet counts — to the unfused tiers and the dense oracle. Pinned
here over feedforward + recurrent graphs at ragged batch sizes
(1, D-1, D, 3D+1), random quantized nets (hypothesis), and the golden
artifact re-run through the fused tier.
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (STEP_BLOCKS, block_of_kind, make_ext,
                      make_feedforward, make_hw)
from repro.core import ExecutionSpec, JaxMappedEngine, Program, compile, \
    lower_tables, random_graph, run_mapped, run_oracle
from repro.core.engine import oracle_packet_counts
from repro.kernels import fused_step as fs
from repro.kernels.fused_step import pack_dense, fused_step
from repro.snn.lif import LIFIntParams

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:            # CI installs hypothesis; bare envs skip
    HAVE_HYPOTHESIS = False

GOLDEN = Path(__file__).parent / "golden"


def _ragged_sizes():
    d = len(jax.devices())
    return sorted({1, max(1, d - 1), d, 3 * d + 1})


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


# ---------------------------------------------------------------------------
# Fused vs unfused tiers: spikes, potentials, packet counts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["feedforward", "recurrent"])
def test_fused_bit_exact_vs_unfused_ragged_batches(kind, ff_program,
                                                   rec_program):
    program = ff_program if kind == "feedforward" else rec_program
    g = program.graph
    fused = ExecutionSpec(kernel="fused")
    for b in _ragged_sizes():
        ext = make_ext(g, b, 11, seed=b)
        s_f, v_f, st_f = program.run(ext, fused)
        for tier in ("lif", "reference"):
            s_u, v_u, st_u = program.run(ext, ExecutionSpec(kernel=tier))
            assert s_f.tobytes() == s_u.tobytes(), (tier, b)
            assert v_f.tobytes() == v_u.tobytes(), (tier, b)
            assert st_f["packet_counts"].tobytes() == \
                st_u["packet_counts"].tobytes(), (tier, b)
        # and vs the dense oracle + python reference executor
        for i in range(b):
            s_ref, v_ref = run_oracle(g, ext[i])
            np.testing.assert_array_equal(s_f[i], s_ref)
            np.testing.assert_array_equal(v_f[i], v_ref)
            _, _, ref = run_mapped(g, program.tables, ext[i])
            np.testing.assert_array_equal(st_f["packet_counts"][i],
                                          ref["packet_counts"])


def test_fused_is_the_default_tier(rec_program):
    ext = make_ext(rec_program.graph, 2, 7, seed=0)
    s_d, v_d, st_d = rec_program.run(ext)
    s_f, v_f, st_f = rec_program.run(ext, ExecutionSpec(kernel="fused"))
    assert rec_program.engine() is rec_program.engine(
        ExecutionSpec(kernel="fused"))
    assert s_d.tobytes() == s_f.tobytes()
    assert v_d.tobytes() == v_f.tobytes()
    np.testing.assert_array_equal(st_d["packet_counts"],
                                  st_f["packet_counts"])


def test_fused_step_handles_non_tile_multiples():
    """Shapes straddling the (8, 128, 128) tile must pad-and-slice."""
    g = random_graph(120, 140, 2500, seed=11)     # n_neurons=260 > 2 tiles
    tables = compile(g, make_hw(g, m=8), max_iters=6000).tables
    ext = make_ext(g, b=9, t=5, seed=2)           # 9 = one tile + 1
    s_f, v_f, st_f = JaxMappedEngine(
        g, tables, ExecutionSpec(kernel="fused")).run(ext)
    s_u, v_u, st_u = JaxMappedEngine(
        g, tables, ExecutionSpec(kernel="lif")).run(ext)
    assert s_f.tobytes() == s_u.tobytes()
    assert v_f.tobytes() == v_u.tobytes()
    np.testing.assert_array_equal(st_f["packet_counts"],
                                  st_u["packet_counts"])


@pytest.mark.parametrize("kind", STEP_BLOCKS)
def test_fused_step_tiled_grid_matches_single_tile(kind, monkeypatch):
    """Each TPU block — the fixed (8, 128, 128) tile, the block resolved
    from the shapes, its fallback with a tiled pre axis (multi-step
    reduction grid, VMEM scratch carries) — must be bit-identical to
    the one-tile CPU path: tiling only reorders an associative int32
    reduction."""
    rng = np.random.default_rng(0)
    b, n_all, n_int = 9, 260, 140                 # straddles every axis
    block = block_of_kind(kind, monkeypatch, b, n_all, n_int, 1, jnp.int8)
    s_all = (rng.random((b, n_all)) < 0.4).astype(np.int32)
    v = rng.integers(-40, 40, (b, n_int)).astype(np.int32)
    w = rng.integers(-7, 8, (n_all, n_int)).astype(np.int8)
    p = LIFIntParams(leak_shift=3, v_threshold=30, v_reset=0)
    one = fused_step(np.asarray(s_all), np.asarray(v), np.asarray(w), p,
                     interpret=True)              # single full-array tile
    tiled = fused_step(np.asarray(s_all), np.asarray(v), np.asarray(w), p,
                       block=block, interpret=True)
    for a, t in zip(one, tiled):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(t))


def test_fused_step_bf16_form_matches_int_contraction():
    """The bf16 x bf16 -> f32 form (int16 planes in [-256, 256]) gives
    the exact int32 currents, tiled or in one tile."""
    rng = np.random.default_rng(1)
    b, n_all, n_int = 9, 260, 140
    s_all = (rng.random((b, n_all)) < 0.4).astype(np.int32)
    v = rng.integers(-40, 40, (b, n_int)).astype(np.int32)
    w = rng.integers(-256, 257, (n_all, n_int)).astype(np.int16)
    p = LIFIntParams(leak_shift=3, v_threshold=300, v_reset=0)
    v_upd = v - (v >> 3) + s_all @ w.astype(np.int32)
    want_s = (v_upd >= 300).astype(np.int32)
    want_v = np.where(want_s == 1, 0, v_upd)
    for block in (None, (8, 128, 128)):
        v_n, s_n, pkt = fused_step(np.asarray(s_all), np.asarray(v),
                                   np.asarray(w, jnp.bfloat16), p,
                                   block=block, interpret=True)
        np.testing.assert_array_equal(np.asarray(v_n), want_v)
        np.testing.assert_array_equal(np.asarray(s_n), want_s)
        np.testing.assert_array_equal(np.asarray(pkt), s_all.sum(1))


def test_fused_step_rejects_non_mxu_weight_dtype():
    p = LIFIntParams(leak_shift=3, v_threshold=30, v_reset=0)
    with pytest.raises(TypeError, match="kernel='lif'"):
        fused_step(np.zeros((2, 5), np.int32), np.zeros((2, 3), np.int32),
                   np.zeros((5, 3), np.int32), p, interpret=True)


@pytest.mark.parametrize("value", [2, 200, -1, 256])
def test_fused_tier_rejects_non_binary_spikes(rec_program, value):
    """The MXU contraction is proven exact for 0/1 spikes only (an int8
    operand wraps 200, bf16 rounds past 256): the engine boundary
    refuses anything else, single-device and sharded alike."""
    ext = make_ext(rec_program.graph, 2, 5, seed=0)
    ext[1, 2, 0] = value
    with pytest.raises(ValueError, match="must be 0/1"):
        rec_program.run(ext, ExecutionSpec(kernel="fused"))
    with pytest.raises(ValueError, match="must be 0/1"):
        rec_program.run(ext, ExecutionSpec(mesh="auto"))


def _wide_weight_program(hi):
    g = random_graph(12, 10, 110, seed=2, weight_lo=-hi, weight_hi=hi)
    return compile(g, make_hw(g), max_iters=4000)


def test_fused_int16_plane_bit_exact_vs_oracle():
    """A folded plane that needs int16 runs the fused tier in the bf16
    form, bit-exact in spikes, v and packet counts."""
    program = _wide_weight_program(255)
    d = pack_dense(program.lowered)
    assert d.dtype == np.int16 and d.operand_dtype == "bfloat16"
    g = program.graph
    ext = make_ext(g, 3, 9, seed=1)
    s, v, st = program.run(ext, ExecutionSpec(kernel="fused"))
    for i in range(len(ext)):
        s_ref, v_ref = run_oracle(g, ext[i])
        np.testing.assert_array_equal(s[i], s_ref)
        np.testing.assert_array_equal(v[i], v_ref)
        np.testing.assert_array_equal(st["packet_counts"][i],
                                      oracle_packet_counts(ext[i], s_ref))


def test_fused_refuses_plane_without_exact_mxu_form():
    """Entries past 256 have no proven MXU form: the fused tier raises,
    naming kernel='lif', and never falls back on its own."""
    program = _wide_weight_program(600)
    with pytest.raises(ValueError, match="kernel='lif'"):
        pack_dense(program.lowered)
    ext = make_ext(program.graph, 2, 5, seed=0)
    with pytest.raises(ValueError, match="kernel='lif'"):
        program.run(ext, ExecutionSpec(kernel="fused"))
    s, v, _ = program.run(ext, ExecutionSpec(kernel="lif"))
    s_ref, v_ref = run_oracle(program.graph, ext[0])
    np.testing.assert_array_equal(s[0], s_ref)
    np.testing.assert_array_equal(v[0], v_ref)


# ---------------------------------------------------------------------------
# pack_dense: exact densification + narrowest-dtype packing
# ---------------------------------------------------------------------------

def test_pack_dense_sums_duplicates_and_narrows(rec_program):
    g = rec_program.graph
    lw = lower_tables(g, rec_program.tables)
    d = pack_dense(lw)
    assert d.weight.shape == (g.n_neurons, g.n_internal)
    w_ref = np.zeros((g.n_neurons, g.n_internal), np.int64)
    np.add.at(w_ref, (lw.op_pre, lw.op_post_local), lw.op_weight)
    np.testing.assert_array_equal(d.weight.astype(np.int64), w_ref)
    # narrowest signed dtype holding every SUMMED entry
    lo, hi = int(w_ref.min()), int(w_ref.max())
    want = next(dt for dt in (np.int8, np.int16, np.int32)
                if np.iinfo(dt).min <= lo and hi <= np.iinfo(dt).max)
    assert d.dtype == np.dtype(want)


def test_pack_dense_size_guard(monkeypatch, rec_program):
    from repro.kernels import fused_step as fs
    monkeypatch.setattr(fs, "MAX_DENSE_BYTES", 16)
    lw = lower_tables(rec_program.graph, rec_program.tables)
    with pytest.raises(ValueError, match="kernel='lif'"):
        fs.pack_dense(lw)


def test_fused_step_packet_counts_count_all_senders():
    """Packets = every nonzero spike-plane entry (external ‖ internal)."""
    p = LIFIntParams(leak_shift=3, v_threshold=100, v_reset=0)
    s_all = np.array([[1, 0, 1, 0, 1], [0, 0, 0, 0, 0]], np.int32)
    v = np.zeros((2, 2), np.int32)
    w = np.zeros((5, 2), np.int8)
    _, _, pkt = fused_step(np.asarray(s_all), np.asarray(v),
                           np.asarray(w), p, interpret=True)
    np.testing.assert_array_equal(np.asarray(pkt), [3, 0])


# ---------------------------------------------------------------------------
# The block resolved from the shapes on the TPU
# ---------------------------------------------------------------------------

# the paper's planes (n_neurons x n_internal) and their input widths
PAPER_PLANES = {"mnist": (784, 126), "shd": (700, 320), "ssc": (700, 835)}


@pytest.mark.parametrize("operand", [jnp.int8, jnp.bfloat16])
@pytest.mark.parametrize("batch", [1, 8, 32, 128, 512])
@pytest.mark.parametrize("plane", sorted(PAPER_PLANES))
def test_resolve_block_takes_batch_and_pre_axis_whole(plane, batch, operand):
    """On every paper plane the resolved block is aligned to the TPU
    tiling, covers the batch in the fewest blocks of at most 128 rows and
    the pre axis in one block, and fits the VMEM budget."""
    n_in, n_int = PAPER_PLANES[plane]
    n_all = n_in + n_int
    for n_state in (1, 2):
        bb, bn, bk = block = fs.resolve_block(batch, n_all, n_state,
                                              operand)
        assert bb % 8 == 0 and bb <= 128
        assert -(-batch // bb) == -(-batch // 128)
        assert bn % 128 == 0 and bk % 128 == 0
        assert bk >= n_all
        assert fs.vmem_bytes(block, n_state, operand) <= fs.VMEM_BUDGET


@pytest.mark.parametrize("batch", [32, 128, 512])
def test_resolve_block_tiles_pre_axis_past_the_budget(batch):
    """A plane at the densification limit (16,384 x 4,096 int8) falls
    back to the fewest pre blocks, aligned, that fit the budget."""
    n_all, n_int = 16384, 4096
    assert n_all * n_int * 4 <= fs.MAX_DENSE_BYTES
    bb, bn, bk = fs.resolve_block(batch, n_all, 2, jnp.int8)
    assert bk % 128 == 0 and bk < n_all
    assert fs.vmem_bytes((bb, bn, bk), 2, jnp.int8) <= fs.VMEM_BUDGET
    nk = -(-n_all // bk)
    assert nk > 1
    fewer = -(-n_all // (nk - 1) // 128) * 128       # one pre block fewer
    assert fs.vmem_bytes((bb, bn, fewer), 2, jnp.int8) > fs.VMEM_BUDGET


def _pallas_grids(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["grid_mapping"].grid
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", p)
            if hasattr(inner, "eqns"):
                yield from _pallas_grids(inner)


@pytest.mark.parametrize("plane,grid", [("ssc", (1, 7, 1)),
                                        ("shd", (1, 3, 1)),
                                        ("mnist", (1, 1, 1))])
def test_engine_kernel_grid_on_paper_planes(plane, grid):
    """At B=128 a timestep of the fused kernel is 7 grid steps on the
    SSC plane (per-neuron kernel), 3 on SHD's and 1 on MNIST's — the
    grid the engine's scan traces its kernel at on the TPU."""
    from conftest import alif_params, with_params
    n_in, n_int = PAPER_PLANES[plane]
    g = random_graph(n_in, n_int, 3000, seed=1)
    if plane == "ssc":
        g = with_params(g, alif_params(n_int, seed=2, n_readout=35))
    eng = compile(g, make_hw(g)).engine(
        ExecutionSpec(kernel="fused", interpret=False))
    assert eng.kernel_grid(128) == grid
    assert eng.kernel_grid(512) == (4,) + grid[1:]
    lw = eng.lowered
    for b in (1, 32, 128):
        traced = jax.make_jaxpr(eng.step_fn)(
            jax.ShapeDtypeStruct((b, 2, lw.n_inputs), jnp.int8),
            *[jax.ShapeDtypeStruct((b, lw.n_internal), jnp.int32)]
            * eng.n_state)
        assert list(_pallas_grids(traced.jaxpr)) == [eng.kernel_grid(b)]
    # interpret mode runs one full-array tile; other tiers run no kernel
    assert compile(g, make_hw(g)).engine(ExecutionSpec(
        kernel="fused", interpret=True)).kernel_grid(128) == (1, 1, 1)
    assert compile(g, make_hw(g)).engine(ExecutionSpec(
        kernel="reference")).kernel_grid(128) is None


def test_sharded_kernel_grid_is_one_chips_rows(rec_program):
    """The sharded runner's grid is the engine's at one chip's rows of
    the padded batch, or at the whole batch where it falls back."""
    from repro.serve import ShardedRunner
    r = ShardedRunner(rec_program, spec=ExecutionSpec(
        kernel="fused", interpret=False, mesh="auto"), min_shard=4)
    eng, d = r._engine, r.n_shards
    assert r.kernel_grid(512) == eng.kernel_grid(r.padded_size(512) // d)
    small = 4 * d - 1
    assert r._use_fallback(small)
    assert r.kernel_grid(small) == eng.kernel_grid(small)


# ---------------------------------------------------------------------------
# Hypothesis: random quantized nets stay bit-exact across tiers
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 10_000),
           n_inputs=st.integers(4, 24),
           n_internal=st.integers(4, 24),
           rate=st.floats(0.05, 0.9))
    def test_fused_bit_exact_random_quantized_nets(seed, n_inputs,
                                                   n_internal, rate):
        rng = np.random.default_rng(seed)
        n_syn = int(rng.integers(n_internal, 4 * (n_inputs + n_internal)))
        g = random_graph(n_inputs, n_internal, n_syn, seed=seed)
        tables = compile(g, make_hw(g), max_iters=2500).tables
        ext = make_ext(g, b=int(rng.integers(1, 5)),
                       t=int(rng.integers(2, 9)), rate=rate, seed=seed)
        s_f, v_f, st_f = JaxMappedEngine(
            g, tables, ExecutionSpec(kernel="fused")).run(ext)
        s_u, v_u, st_u = JaxMappedEngine(
            g, tables, ExecutionSpec(kernel="reference")).run(ext)
        assert s_f.tobytes() == s_u.tobytes()
        assert v_f.tobytes() == v_u.tobytes()
        assert st_f["packet_counts"].tobytes() == \
            st_u["packet_counts"].tobytes()


# ---------------------------------------------------------------------------
# Golden artifact through the fused tier
# ---------------------------------------------------------------------------

def test_golden_artifact_fused_tier_bit_exact():
    program = Program.load(GOLDEN / "tiny_program_v1.npz")
    with np.load(GOLDEN / "tiny_program_v1_io.npz") as io:
        s, v, stats = program.run(io["ext"], ExecutionSpec(kernel="fused"))
        np.testing.assert_array_equal(s, io["spikes"])
        np.testing.assert_array_equal(v, io["v_final"])
        np.testing.assert_array_equal(stats["packet_counts"],
                                      io["packet_counts"])
