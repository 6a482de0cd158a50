"""Tests for the `Program` artifact API (repro.core.program).

Covers: the compile() pass pipeline, uniform run() shapes across the
three engines, save/load bit-exact round-trips WITHOUT re-partitioning,
format-version rejection, init-packet determinism, owned-engine caching,
profile(), and the deprecated wrappers' delegation.
"""
import json

import numpy as np
import pytest

from conftest import make_ext, make_feedforward, make_hw
from repro.core import (ENGINES, CycleModel, ExecutionSpec, Program, compile,
                        compile_snn, random_graph, run_mapped, run_oracle)
from repro.core.execution import default_interpret

_hw, _feedforward, _ext = make_hw, make_feedforward, make_ext


def _recurrent(seed=3):
    g = random_graph(12, 20, 160, seed=seed)
    assert (g.pre >= g.n_inputs).any(), "graph must contain recurrence"
    return g


@pytest.fixture(scope="module")
def recurrent_program():
    g = _recurrent()
    return compile(g, _hw(g), max_iters=4000)


# ---------------------------------------------------------------------------
# compile() and the artifact's parts.
# ---------------------------------------------------------------------------

def test_compile_owns_all_parts(recurrent_program):
    p = recurrent_program
    assert p.feasible and p.report.feasible
    assert p.ot_depth == p.tables.depth == p.report.ot_depth
    assert p.lowered.n_ops == p.graph.n_synapses
    assert p.part.assign.shape == (p.graph.n_synapses,)
    assert len(p.init_packets()) == p.report.n_init_packets


def test_compile_matches_deprecated_wrapper():
    g = _recurrent(seed=21)
    p = compile(g, _hw(g), seed=4, max_iters=4000)
    with pytest.deprecated_call():
        tables, report, part = compile_snn(g, _hw(g), seed=4,
                                           max_iters=4000)
    np.testing.assert_array_equal(p.tables.pre, tables.pre)
    np.testing.assert_array_equal(p.tables.weight, tables.weight)
    np.testing.assert_array_equal(p.part.assign, part.assign)
    assert p.report.ot_depth == report.ot_depth


def test_compile_rejects_unknown_engine_and_method():
    g = _recurrent(seed=23)
    with pytest.raises(ValueError, match="engine"):
        compile(g, _hw(g), engine="verilog")
    with pytest.raises(ValueError, match="method"):
        compile(g, _hw(g), method="astrology")


# ---------------------------------------------------------------------------
# Uniform run() surface.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
def test_run_uniform_shapes_and_bits(recurrent_program, engine):
    p = recurrent_program
    ext_b = _ext(p.graph, b=3, t=7, seed=1)
    s, v, st = p.run(ext_b, engine)
    assert s.shape == (3, 7, p.graph.n_internal)
    assert v.shape == (3, p.graph.n_internal)
    assert st["packet_counts"].shape == (3, 7)
    s1, v1, st1 = p.run(ext_b[0], engine)           # unbatched
    assert s1.shape == (7, p.graph.n_internal)
    assert st1["packet_counts"].shape == (7,)
    np.testing.assert_array_equal(s1, s[0])
    np.testing.assert_array_equal(v1, v[0])
    # every engine bit-exact vs the dense oracle, incl. packet counts
    for b in range(3):
        s_ref, v_ref = run_oracle(p.graph, ext_b[b])
        np.testing.assert_array_equal(s[b], s_ref)
        np.testing.assert_array_equal(v[b], v_ref)
        _, _, ref = run_mapped(p.graph, p.tables, ext_b[b])
        np.testing.assert_array_equal(st["packet_counts"][b],
                                      ref["packet_counts"])


def test_run_rejects_bad_engine_and_shape(recurrent_program):
    p = recurrent_program
    with pytest.raises(ValueError, match="engine"):
        p.run(_ext(p.graph, 1, 4), "fpga")
    with pytest.raises(ValueError, match="shape"):
        p.run(np.zeros((4, p.graph.n_inputs + 1), np.int32))


# ---------------------------------------------------------------------------
# save() / load() round trip.
# ---------------------------------------------------------------------------

def _no_repartition(monkeypatch):
    import importlib
    import repro.core.passes as passes_mod
    # the package re-exports the `partition` FUNCTION, shadowing the
    # submodule attribute — resolve the modules via importlib
    part_mod = importlib.import_module("repro.core.partition")
    search_mod = importlib.import_module("repro.core.mapping.search")

    def boom(*a, **kw):
        raise AssertionError("partitioner must not run on load")
    monkeypatch.setattr(part_mod, "partition", boom)
    monkeypatch.setattr(search_mod, "framework_partition", boom)
    monkeypatch.setattr(search_mod, "_Population", boom)
    monkeypatch.setattr(passes_mod, "partition_pass", boom)
    monkeypatch.setattr(passes_mod, "search_pass", boom)


@pytest.mark.parametrize("kind", ["feedforward", "recurrent"])
def test_save_load_bit_exact_no_repartition(tmp_path, monkeypatch, kind):
    g = _feedforward() if kind == "feedforward" else _recurrent()
    p = compile(g, _hw(g), max_iters=4000)
    path = p.save(tmp_path / f"{kind}.npz")
    assert path.exists()

    _no_repartition(monkeypatch)
    p2 = Program.load(path)
    for f in ("pre", "post", "weight", "pre_end", "post_end", "assign"):
        np.testing.assert_array_equal(getattr(p2.tables, f),
                                      getattr(p.tables, f))
    assert p2.tables.send_slot == p.tables.send_slot
    assert p2.tables.send_order == p.tables.send_order
    np.testing.assert_array_equal(p2.part.assign, p.part.assign)
    assert p2.hw == p.hw

    ext = _ext(g, b=3, t=9, seed=2)
    s, v, st = p2.run(ext, "jax")
    for b in range(3):
        s_ref, v_ref = run_oracle(g, ext[b])
        np.testing.assert_array_equal(s[b], s_ref)
        np.testing.assert_array_equal(v[b], v_ref)
        _, _, ref = run_mapped(g, p.tables, ext[b])
        np.testing.assert_array_equal(st["packet_counts"][b],
                                      ref["packet_counts"])


def test_save_appends_npz_suffix(tmp_path, recurrent_program):
    path = recurrent_program.save(tmp_path / "artifact")
    assert path.name == "artifact.npz" and path.exists()


def test_load_rejects_version_mismatch(tmp_path, recurrent_program):
    path = recurrent_program.save(tmp_path / "versioned.npz")
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    header = json.loads(str(arrays["header"][()]))
    header["version"] += 1
    arrays["header"] = np.asarray(json.dumps(header))
    np.savez(tmp_path / "future.npz", **arrays)
    with pytest.raises(ValueError, match="version"):
        Program.load(tmp_path / "future.npz")


def test_load_rejects_foreign_npz(tmp_path):
    np.savez(tmp_path / "foreign.npz", weights=np.zeros(3))
    with pytest.raises(ValueError, match="artifact"):
        Program.load(tmp_path / "foreign.npz")


def test_init_packets_deterministic_across_save_load(tmp_path,
                                                     recurrent_program):
    p = recurrent_program
    p2 = Program.load(p.save(tmp_path / "pkts.npz"))
    pkts, pkts2 = p.init_packets(), p2.init_packets()
    assert pkts == pkts2
    assert len(pkts2) == p2.report.n_init_packets == p.report.n_init_packets


# ---------------------------------------------------------------------------
# profile().
# ---------------------------------------------------------------------------

def test_profile_matches_cycle_model(recurrent_program):
    p = recurrent_program
    ext = _ext(p.graph, b=2, t=8, seed=3)
    _, _, st = p.run(ext, "python")
    prof = p.profile(st)
    assert len(prof.per_sample) == 2
    cm = CycleModel(p.hw)
    for b in range(2):
        ref = cm.run(st["packet_counts"][b], p.tables.depth,
                     p.graph.n_synapses)
        assert prof.per_sample[b] == ref
    assert prof.latency_us == pytest.approx(
        np.mean([r.latency_us for r in prof.per_sample]))
    assert prof.resources == p.report.resources
    # unbatched stats -> aggregate IS the single sample
    _, _, st1 = p.run(ext[0], "python")
    prof1 = p.profile(st1)
    assert prof1.cycle == prof1.per_sample[0]
    # n_synapses override changes only the per-synapse denominator
    prof_q = p.profile(st1, n_synapses=2 * p.graph.n_synapses)
    assert prof_q.energy_per_synapse_nj == pytest.approx(
        prof1.energy_per_synapse_nj / 2)


# ---------------------------------------------------------------------------
# Owned engines.
# ---------------------------------------------------------------------------

def test_engines_are_owned_and_keyed_on_resolved_spec(recurrent_program):
    p = recurrent_program
    assert p.engine() is p.engine()
    # unset fields resolve to platform defaults before keying, so every
    # spelling of the default spec maps to the same engine instance
    assert p.engine() is p.engine(ExecutionSpec())
    assert p.engine() is p.engine(
        ExecutionSpec(kernel="fused", interpret=default_interpret()))
    assert p.engine(ExecutionSpec(kernel="reference")) is not p.engine()
    # legacy kwargs still reach the same cache, through a warning shim
    with pytest.deprecated_call():
        legacy = p.engine(nu_kernel=True)
    assert legacy is p.engine(ExecutionSpec(kernel="lif"))
    # no module-level cache left behind
    from repro.core import engine_jax
    assert not hasattr(engine_jax, "_ENGINE_CACHE")
