"""Per-neuron Neuron Unit parameters and the adaptive LIF (ALIF).

A program whose neurons carry their own shifts, adaptive thresholds,
subtractive reset or never-firing readouts (``NeuronParams``) runs the
per-neuron kernel ``fused_step_alif`` and carries the adaptation ``a``
as a second state; a uniform non-adaptive program keeps the scalar
``fused_step`` bit for bit. Pinned here against the dense oracle over
feed-forward and recurrent graphs, ragged batches, heterogeneous shifts
and readout neurons; with the range proof, the artifact and the tiers
that refuse.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (STEP_BLOCKS, alif_params, block_of_kind, make_ext,
                      make_feedforward, make_hw, with_params)
from repro.core import ExecutionSpec, Program, compile, random_graph
from repro.core.engine import run_oracle_state
from repro.core.profiling import span_log
from repro.snn.lif import (NEVER_FIRES, LIFIntParams, NeuronParams,
                           alif_step_int, lif_step_int)

LIF = LIFIntParams(leak_shift=2, v_threshold=15, v_reset=0)


def _graph(kind, seed=3):
    g = (make_feedforward(seed=seed) if kind == "feedforward"
         else random_graph(12, 20, 160, seed=seed))
    return with_params(g, alif_params(g.n_internal, seed=seed, n_readout=3))


@pytest.fixture(scope="module", params=["feedforward", "recurrent"])
def alif_program(request):
    g = _graph(request.param)
    return compile(g, make_hw(g), max_iters=4000)


def _assert_matches_oracle(prog, ext, out):
    s, v, st = out
    for b in range(ext.shape[0]):
        s_ref, v_ref, a_ref = run_oracle_state(prog.graph, ext[b])
        np.testing.assert_array_equal(s[b], s_ref)
        np.testing.assert_array_equal(v[b], v_ref)
        np.testing.assert_array_equal(st["adaptation"][b], a_ref)
        pkts = np.count_nonzero(ext[b], axis=1)
        pkts[1:] += np.count_nonzero(s_ref[:-1], axis=1)
        np.testing.assert_array_equal(st["packet_counts"][b], pkts)


@pytest.mark.parametrize("kernel", ["fused", "reference"])
@pytest.mark.parametrize("batch", [1, 3, 9])
def test_alif_tiers_bit_exact_vs_oracle(alif_program, kernel, batch):
    """Spikes, potentials, final adaptation and packet counts of the
    fused (interpret mode) and reference tiers equal the oracle's."""
    ext = make_ext(alif_program.graph, batch, 13, seed=batch)
    out = alif_program.run(ext, ExecutionSpec(kernel=kernel))
    _assert_matches_oracle(alif_program, ext, out)
    assert out[0].any(), "the network must fire"
    # the readouts never fire
    assert not out[0][..., -3:].any()


@pytest.mark.parametrize("kind", STEP_BLOCKS)
def test_alif_tiled_grid_matches_single_tile(kind, monkeypatch):
    """Tiling the per-neuron kernel over (batch, post, pre) blocks with
    ragged edges — the fixed (8, 128, 128) tile, the resolved block, its
    fallback with a tiled pre axis — gives the single-tile bits,
    parameter rows included."""
    from repro.kernels.fused_step import fused_step_alif
    rng = np.random.default_rng(0)
    b, n_all, n_int = 11, 300, 150
    block = block_of_kind(kind, monkeypatch, b, n_all, n_int, 2, jnp.int8)
    p = alif_params(n_int, seed=2, n_readout=5)
    s_all = jnp.asarray(rng.integers(0, 2, (b, n_all)), jnp.int32)
    w = jnp.asarray(rng.integers(-20, 20, (n_all, n_int)), jnp.int8)
    v = jnp.asarray(rng.integers(-40, 40, (b, n_int)), jnp.int32)
    a = jnp.asarray(rng.integers(0, 9, (b, n_int)), jnp.int32)
    pk = jnp.asarray(p.packed())
    one = fused_step_alif(s_all, v, a, w, pk, interpret=True)
    tiled = fused_step_alif(s_all, v, a, w, pk, block=block,
                            interpret=True)
    for x, y in zip(one, tiled):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # and the epilogue is alif_step_int on the contraction
    cur = np.asarray(s_all) @ np.asarray(w, np.int32)
    v1, a1, s1 = alif_step_int(np.asarray(v), np.asarray(a),
                               cur.astype(np.int32), p)
    np.testing.assert_array_equal(np.asarray(one[0]), v1)
    np.testing.assert_array_equal(np.asarray(one[1]), a1)
    np.testing.assert_array_equal(np.asarray(one[2]), s1)


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jnp"])
def test_uniform_params_step_is_lif_step(xp):
    rng = np.random.default_rng(1)
    v = xp.asarray(rng.integers(-60, 60, (4, 9)), xp.int32)
    cur = xp.asarray(rng.integers(-20, 30, (4, 9)), xp.int32)
    p = NeuronParams.uniform(LIF, 9)
    if xp is jnp:
        p = NeuronParams(*(jnp.asarray(x) for x in p))
    v1, a1, s1 = alif_step_int(v, xp.zeros_like(v), cur, p)
    v2, s2 = lif_step_int(v, cur, LIF)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert not np.asarray(a1).any()


@pytest.mark.parametrize("kind", ["feedforward", "recurrent"])
def test_uniform_non_adaptive_params_are_todays_lif(kind):
    """``NeuronParams.uniform(lif)`` is the scalar LIF: the same scalar
    kernel (no adaptation state) and the same bits as ``lif``."""
    g = (make_feedforward() if kind == "feedforward"
         else random_graph(12, 20, 160, seed=3))
    g_vec = with_params(g, NeuronParams.uniform(g.lif, g.n_internal))
    assert g_vec.scalar_lif == g.lif
    p_lif = compile(g, make_hw(g), max_iters=4000)
    p_vec = compile(g_vec, make_hw(g_vec), max_iters=4000)
    ext = make_ext(g, 5, 9, seed=4)
    for kernel in ("fused", "lif", "reference"):
        spec = ExecutionSpec(kernel=kernel)
        assert p_vec.engine(spec).n_state == 2
        a, b = p_lif.run(ext, spec), p_vec.run(ext, spec)
        assert a[0].tobytes() == b[0].tobytes()
        assert a[1].tobytes() == b[1].tobytes()
        assert "adaptation" not in b[2]


def _kernel_names(eng, b=2, t=3):
    lw = eng.lowered
    args = [jnp.zeros((b, t, lw.n_inputs), jnp.int8)] + [
        jnp.zeros((b, lw.n_internal), jnp.int32)] * eng.n_state
    jaxpr = jax.make_jaxpr(eng.step_fn)(*args)
    names = []

    def walk(jx):
        for e in jx.eqns:
            if e.primitive.name == "pallas_call":
                names.append(str(e.params["name"]))
            for p in e.params.values():
                sub = getattr(p, "jaxpr", None)
                if sub is not None:
                    walk(getattr(sub, "jaxpr", sub))
    walk(jaxpr.jaxpr)
    return names


def test_kernel_variant_follows_the_parameters(alif_program):
    """A LIF program calls ``fused_step``; a per-neuron one calls
    ``fused_step_alif`` and carries ``(v, a, s)``."""
    g = random_graph(12, 20, 160, seed=3)
    lif_eng = compile(g, make_hw(g)).engine(ExecutionSpec(kernel="fused"))
    assert lif_eng.n_state == 2
    assert _kernel_names(lif_eng) == ["fused_step"]
    eng = alif_program.engine(ExecutionSpec(kernel="fused"))
    assert eng.n_state == 3
    assert _kernel_names(eng) == ["fused_step_alif"]


def test_heterogeneous_non_adaptive_leaks_take_the_per_neuron_kernel():
    g = random_graph(12, 20, 160, seed=5)
    rng = np.random.default_rng(5)
    p = NeuronParams.make(g.n_internal,
                          leak_shift=rng.integers(1, 5, g.n_internal),
                          v_threshold=15)
    assert not p.adaptive and p.scalar() is None
    prog = compile(with_params(g, p), make_hw(g))
    ext = make_ext(g, 3, 9, seed=5)
    _assert_matches_oracle(prog, ext, prog.run(ext))


@pytest.mark.parametrize("where", ["lif_tier", "python_engine"])
def test_scalar_only_tiers_refuse_per_neuron_programs(alif_program, where):
    ext = make_ext(alif_program.graph, 2, 4)
    with pytest.raises(ValueError, match="kernel='fused'"):
        if where == "lif_tier":
            alif_program.run(ext, ExecutionSpec(kernel="lif"))
        else:
            alif_program.run(ext, "python")


def test_launch_span_counts_the_neuron_unit_state(alif_program):
    ext = make_ext(alif_program.graph, 5, 4, seed=2)
    log = span_log()
    first = log.written
    alif_program.run(ext)
    recs = log.records()[-(log.written - first):]
    launch = [r for r in recs if r.name == "repro.engine.launch"]
    n = alif_program.graph.n_internal
    assert [r.nbytes for r in launch] == [2 * 5 * n * 4]
    down = [r for r in recs if r.name == "repro.engine.download"]
    assert down[0].nbytes == 5 * 4 * n * 4 + 2 * 5 * n * 4


_FOUR_DEVICES = """
import numpy as np
from conftest import alif_params, make_ext, make_hw, with_params
from repro.core import compile, random_graph
from repro.core.engine import run_oracle_state
from repro.serve import ShardedRunner
g = random_graph(10, 20, 160, seed=3)
g = with_params(g, alif_params(g.n_internal, seed=4, n_readout=2))
r = ShardedRunner(compile(g, make_hw(g), max_iters=4000), min_shard=0)
assert r.n_shards == 4
ext = make_ext(g, 6, 7, seed=8)             # the last chip holds 2 pad rows
s, v, st = r.run(ext)
assert s.any()
for i in range(6):
    s_ref, v_ref, a_ref = run_oracle_state(g, ext[i])
    assert (s[i] == s_ref).all() and (v[i] == v_ref).all()
    assert (st["adaptation"][i] == a_ref).all()
print("ok")
"""


def test_sharded_alif_on_four_devices():
    """The sharded runner carries ``(v, a, s)`` on four host devices and
    matches the oracle, pad rows masked."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(sys.path))
    out = subprocess.run([sys.executable, "-c", _FOUR_DEVICES], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().splitlines()[-1] == "ok"


# -- the range proof ---------------------------------------------------------

def test_range_proof_bounds_the_state_it_proves_safe(alif_program):
    """An adaptive program is proven int32-safe, its int8 plane exact on
    the MXU, and every potential and adaptation an oracle run reaches
    lies in the proven intervals."""
    r = alif_program.verify().stats["ranges"]
    assert r["int32_safe"] and r["mxu_operand"] == "int8"
    assert alif_program.report.phase_seconds["neuron_params"] >= 0.0
    g = alif_program.graph
    ext = make_ext(g, 1, 60, rate=0.6, seed=9)[0]
    w = np.zeros((g.n_neurons, g.n_internal), np.int64)
    w[g.pre, g.local(g.post)] = g.weight
    v = np.zeros(g.n_internal, np.int64)
    a = np.zeros(g.n_internal, np.int64)
    s = np.zeros(g.n_internal, np.int64)
    p = NeuronParams(*(x.astype(np.int64) for x in g.neurons))
    for t in range(60):
        cur = np.concatenate([ext[t], s]) @ w
        u = v - (v >> p.leak_shift) + cur
        assert r["acc_lo"] <= u.min() and u.max() <= r["acc_hi"]
        v, a, s = alif_step_int(v, a, cur, p)
        assert r["membrane_lo"] <= v.min() and v.max() <= r["membrane_hi"]
        assert 0 <= a.min() and a.max() <= r["adapt_hi"]


def test_range_proof_refuses_an_overflowing_network():
    """Adaptation whose bound ``adapt_inc << adapt_shift`` takes the
    threshold past int32 is refused at compile, before any mapping, and
    reported as RANGE002 by the verifier."""
    g = random_graph(12, 20, 160, seed=3)
    p = alif_params(g.n_internal, seed=3)
    bad = p._replace(adapt_inc=np.full(g.n_internal, 1 << 20, np.int32),
                     adapt_shift=np.full(g.n_internal, 12, np.int32))
    with pytest.raises(ValueError, match="exceeds int32"):
        compile(with_params(g, bad), make_hw(g))
    prog = compile(with_params(g, p), make_hw(g))
    prog.graph.lif = bad                   # a hand-edited artifact
    rep = prog.verify()
    assert not rep.ok
    assert any(d.code == "RANGE002" for d in rep.diagnostics)
    assert rep.stats["ranges"]["int32_safe"] is False


def test_never_firing_readout_needs_its_proof():
    """A readout is proven never to fire when its leaky integrator's
    bound stays under its threshold."""
    from repro.analysis.ranges import neuron_bounds
    p = NeuronParams.make(2, leak_shift=[3, 3],
                          v_threshold=[NEVER_FIRES, 100])
    b = neuron_bounds(np.array([50, 50]), np.array([-50, -50]), p)
    assert b["membrane_hi"] == 400          # 50 << 3, the readout's
    assert b["threshold_hi"] == NEVER_FIRES


@pytest.mark.parametrize("field,value", [
    ("leak_shift", 32), ("adapt_shift", -1), ("adapt_inc", -1),
    ("subtractive", 2)])
def test_neuron_params_are_validated(field, value):
    kw = dict(leak_shift=2, v_threshold=10)
    kw[field] = value
    with pytest.raises(ValueError, match=field):
        NeuronParams.make(4, **kw)


def test_graph_refuses_params_of_another_size():
    g = random_graph(12, 20, 160, seed=3)
    with pytest.raises(ValueError, match="internal"):
        with_params(g, alif_params(g.n_internal + 1))


# -- the artifact ------------------------------------------------------------

def test_save_load_round_trips_per_neuron_params(tmp_path, alif_program):
    path = alif_program.save(tmp_path / "alif")
    back = Program.load(path)
    for x, y in zip(alif_program.graph.lif, back.graph.lif):
        assert x.dtype == y.dtype and np.array_equal(x, y)
    assert back.content_hash() == alif_program.content_hash()
    ext = make_ext(alif_program.graph, 3, 8, seed=1)
    a, b = alif_program.run(ext), back.run(ext)
    for x, y in zip(a[:2], b[:2]):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(a[2]["adaptation"], b[2]["adaptation"])


@pytest.mark.parametrize("field", NeuronParams._fields)
def test_content_hash_changes_with_any_one_parameter(alif_program, field):
    import dataclasses
    p = alif_program.graph.lif
    x = getattr(p, field).copy()
    x[4] = 1 - x[4] if field == "subtractive" else x[4] + 1
    g2 = dataclasses.replace(alif_program.graph, lif=p._replace(**{field: x}))
    other = dataclasses.replace(alif_program, graph=g2, _engines={})
    assert other.content_hash() != alif_program.content_hash()
