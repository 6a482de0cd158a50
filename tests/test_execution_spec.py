"""ExecutionSpec surface + AOT precompile layer (core/execution, core/aot).

Covers: (a) spec validation and resolution — unknown engines/kernels
rejected at construction, non-jax specs reject jax-only knobs, resolve()
is idempotent and the resolved spec keys the engine cache; (b) as_spec
coercion (None / engine-name string / spec); (c) the deprecated-kwarg
shim — exact nu_kernel/sharded/mesh semantics behind a
DeprecationWarning; (d) AOT bucket precompile on engines, Programs,
registries and the sharded runner, all bit-exact vs the jit path;
(e) the sharded small-batch fallback (min_shard); (f) the batcher's
measured-mode warmup reusing the AOT path; (g) normalize_buckets /
content_hash / enable_persistent_cache.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from conftest import make_ext, make_feedforward, make_hw
from repro.core import (ExecutionSpec, KERNELS, Program, compile,
                        default_kernel, random_graph)
from repro.core.aot import (ENV_CACHE_DIR, cache_dir, content_hash,
                            normalize_buckets)
from repro.core.execution import (as_spec, default_interpret,
                                  spec_from_legacy_kwargs)
from repro.serve import (BatchPolicy, MicroBatcher, ProgramRegistry,
                         ShardedRunner)


@pytest.fixture(scope="module")
def program():
    g = make_feedforward()
    return compile(g, make_hw(g), max_iters=4000)


# ---------------------------------------------------------------------------
# Validation + resolution
# ---------------------------------------------------------------------------

def test_spec_rejects_unknown_engine_and_kernel():
    with pytest.raises(ValueError, match="unknown engine"):
        ExecutionSpec(engine="fpga")
    with pytest.raises(ValueError, match="unknown kernel"):
        ExecutionSpec(kernel="cuda")


@pytest.mark.parametrize("bad", [dict(kernel="fused"), dict(interpret=True),
                                 dict(donate=True)])
def test_spec_rejects_jax_knobs_on_other_engines(bad):
    with pytest.raises(ValueError, match="jax-engine build options"):
        ExecutionSpec(engine="python", **bad)


def test_spec_rejects_mesh_on_other_engines():
    with pytest.raises(ValueError, match="mesh= shards the jax"):
        ExecutionSpec(engine="oracle", mesh="auto")


def test_resolve_folds_platform_defaults_and_is_idempotent():
    r = ExecutionSpec().resolve()
    assert r.resolved and not ExecutionSpec().resolved
    assert r.kernel == default_kernel()
    assert r.interpret == default_interpret()
    assert r.resolve() == r                        # idempotent
    # every explicit spelling of the defaults resolves identically
    assert ExecutionSpec(kernel=default_kernel()).resolve() == r
    # non-jax specs are already resolved (no jax knobs to fold)
    assert ExecutionSpec(engine="python").resolved


def test_resolve_expands_auto_mesh_and_rejects_other_strings():
    r = ExecutionSpec(mesh="auto").resolve()
    assert r.sharded and not isinstance(r.mesh, str)
    assert r.single_device().mesh is None
    assert r.single_device().kernel == r.kernel    # only the mesh drops
    with pytest.raises(ValueError, match="only string form"):
        ExecutionSpec(mesh="ring").resolve()


def test_specs_key_the_engine_cache(program):
    assert program.engine(ExecutionSpec()) is \
        program.engine(ExecutionSpec(interpret=default_interpret()))
    e = {k: program.engine(ExecutionSpec(kernel=k)) for k in KERNELS}
    assert len(set(map(id, e.values()))) == len(KERNELS)


# ---------------------------------------------------------------------------
# as_spec coercion
# ---------------------------------------------------------------------------

def test_as_spec_coercion():
    assert as_spec(None) == ExecutionSpec()
    assert as_spec(None, default_engine="python").engine == "python"
    assert as_spec("oracle") == ExecutionSpec(engine="oracle")
    s = ExecutionSpec(kernel="lif")
    assert as_spec(s) is s
    with pytest.raises(TypeError, match="ExecutionSpec"):
        as_spec(42)
    with pytest.raises(ValueError, match="unknown engine"):
        as_spec("fpga")


# ---------------------------------------------------------------------------
# Deprecated-kwarg shim
# ---------------------------------------------------------------------------

def test_legacy_kwargs_map_onto_specs():
    with pytest.deprecated_call(match="Migration to ExecutionSpec"):
        assert spec_from_legacy_kwargs(nu_kernel=True).kernel == "lif"
    with pytest.deprecated_call():
        assert spec_from_legacy_kwargs(nu_kernel=False).kernel == "reference"
    with pytest.deprecated_call():                 # sharded=True -> auto mesh
        assert spec_from_legacy_kwargs(sharded=True).mesh == "auto"
    with pytest.deprecated_call():                 # old API: mesh needs sharded
        assert spec_from_legacy_kwargs(mesh=object()).mesh is None
    with pytest.deprecated_call():
        assert spec_from_legacy_kwargs(engine="python") == \
            ExecutionSpec(engine="python")
    with pytest.deprecated_call(), \
            pytest.raises(ValueError, match="sharded=True runs the jax"):
        spec_from_legacy_kwargs(sharded=True, engine="oracle")


def test_legacy_run_kwargs_delegate_bit_exact(program):
    ext = make_ext(program.graph, 2, 6, seed=0)
    s_new, v_new, _ = program.run(ext, ExecutionSpec(kernel="lif"))
    with pytest.deprecated_call():
        s_old, v_old, _ = program.run(ext, nu_kernel=True)
    assert s_old.tobytes() == s_new.tobytes()
    assert v_old.tobytes() == v_new.tobytes()
    with pytest.raises(TypeError, match="both"):
        program.run(ext, ExecutionSpec(), engine="jax")


# ---------------------------------------------------------------------------
# AOT precompile
# ---------------------------------------------------------------------------

def test_engine_precompile_is_idempotent_and_bit_exact(program):
    eng = program.engine(ExecutionSpec(donate=False))
    new = eng.precompile([2, 4], timesteps=6)
    assert set(new) == {(2, 6), (4, 6)}
    assert eng.precompile([2, 4], timesteps=6) == []   # already compiled
    ext = make_ext(program.graph, 4, 6, seed=1)
    s_aot, v_aot, st_aot = eng.run(ext)                # hits the executable
    s_jit, _, _ = program.run(ext, ExecutionSpec(kernel="lif"))
    assert s_aot.tobytes() == s_jit.tobytes()
    # non-matching shapes still fall back to the jitted path
    ext5 = make_ext(program.graph, 5, 6, seed=1)
    assert eng.run(ext5)[0].shape == (5, 6, program.graph.n_internal)


@pytest.mark.parametrize("min_shard", [None, 0, 1 << 20],
                         ids=["engine", "shard_path", "fallback"])
def test_precompiled_shape_runs_without_a_new_trace(program, min_shard,
                                                    monkeypatch):
    """``precompile`` declares the int8 train ``run`` sends, so a
    precompiled shape is served by its AOT executable: with the jitted
    fallback made to fail, the call still runs, bit-exact."""
    if min_shard is None:
        runner = owner = program.engine(ExecutionSpec(donate=False))
    else:
        runner = ShardedRunner(program, min_shard=min_shard)
        owner = runner._engine if min_shard else runner
    runner.precompile([3], timesteps=6)
    ext = make_ext(program.graph, 3, 6, seed=2)
    want = program.run(ext, ExecutionSpec(kernel="reference"))

    def no_trace(*args):
        raise AssertionError("a precompiled shape was traced again")

    monkeypatch.setattr(owner, "_run", no_trace)
    got = runner.run(ext)
    assert got[0].tobytes() == want[0].tobytes()
    assert got[1].tobytes() == want[1].tobytes()
    np.testing.assert_array_equal(got[2]["packet_counts"],
                                  want[2]["packet_counts"])


def test_program_precompile_accepts_policy_and_ints(program):
    assert isinstance(program.precompile(BatchPolicy(max_batch=4),
                                         timesteps=5), list)
    assert isinstance(program.precompile(8, timesteps=5), list)
    ext = make_ext(program.graph, 8, 5, seed=4)        # served by the AOT exe
    np.testing.assert_array_equal(
        program.run(ext)[0],
        program.run(ext, ExecutionSpec(kernel="lif"))[0])
    with pytest.raises(TypeError):                     # timesteps required
        program.precompile([2])


def test_load_precompile_requires_timesteps(tmp_path, program):
    path = program.save(tmp_path / "m.npz")
    with pytest.raises(ValueError, match="timesteps"):
        Program.load(path, precompile=[4])
    p = Program.load(path, precompile=[4], timesteps=6)
    ext = make_ext(p.graph, 4, 6, seed=2)
    np.testing.assert_array_equal(p.run(ext)[0], program.run(ext)[0])


def test_registry_register_precompile(tmp_path, program):
    reg = ProgramRegistry()
    with pytest.raises(ValueError, match="timesteps"):
        reg.register("m", program, precompile=[2])
    reg.register("m", program, precompile=[2], timesteps=6)
    assert reg.get("m") is program


def test_normalize_buckets():
    assert normalize_buckets([4, 2, 2, 8]) == (2, 4, 8)
    assert normalize_buckets(3) == (3,)
    assert normalize_buckets(BatchPolicy(max_batch=4)) == (1, 2, 4)
    with pytest.raises(ValueError, match="positive"):
        normalize_buckets([0, 2])
    with pytest.raises(ValueError, match="positive"):
        normalize_buckets([])


def test_content_hash_tracks_the_computation(program):
    h = content_hash(program)
    assert isinstance(h, str) and len(h) == 64
    assert content_hash(program) == h              # deterministic
    g2 = make_feedforward(seed=7)
    other = compile(g2, make_hw(g2), max_iters=4000)
    assert content_hash(other) != h


def _cache_dir_in_new_process(env_dir):
    env = {k: v for k, v in os.environ.items() if k != ENV_CACHE_DIR}
    env["PYTHONPATH"] = os.pathsep.join(sys.path)
    if env_dir is not None:
        env[ENV_CACHE_DIR] = env_dir
    code = ("from repro.core.aot import enable_persistent_cache as e; "
            "d = e(); assert e() == d; print(d)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, check=True)
    return out.stdout.strip().splitlines()[-1]


def test_enable_persistent_cache_idempotent(tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise every process
    lands on the same fixed directory in the checkout."""
    assert _cache_dir_in_new_process(str(tmp_path)) == str(tmp_path)
    repo_cache = str(Path(__file__).resolve().parents[1] / ".jax-cache")
    assert cache_dir() in (repo_cache, os.environ.get(ENV_CACHE_DIR))
    assert [_cache_dir_in_new_process(None) for _ in range(2)] == \
        [repo_cache, repo_cache]


# ---------------------------------------------------------------------------
# Sharded small-batch fallback + batcher warmup
# ---------------------------------------------------------------------------

def test_sharded_small_batch_fallback_bit_exact(program):
    r = ShardedRunner(program, min_shard=4)        # fallback below 4/shard
    b_small = max(1, r.n_shards * r.min_shard - 1)
    ext = make_ext(program.graph, b_small, 6, seed=3)
    assert r._use_fallback(b_small)
    s, v, st = r.run(ext)
    s1, v1, st1 = program.run(ext)
    assert s.tobytes() == s1.tobytes()
    assert v.tobytes() == v1.tobytes()
    np.testing.assert_array_equal(st["packet_counts"],
                                  st1["packet_counts"])
    # min_shard=0 disables the fallback even at B=1
    assert not ShardedRunner(program, min_shard=0)._use_fallback(1)
    # precompile warms fallback buckets on the single-device engine
    warmed = r.precompile([1, 8 * max(1, r.n_shards)], timesteps=6)
    assert warmed is not None


def test_batcher_measured_warmup_uses_aot_precompile(program):
    reg = ProgramRegistry()
    reg.register("m", program)
    runner = reg.runner("m", ExecutionSpec())
    called = []
    orig = runner.precompile
    runner.precompile = lambda buckets, t: (called.append((tuple(buckets),
                                                           t)),
                                            orig(buckets, t))[1]
    g = program.graph
    reqs = make_ext(g, 5, 6, seed=9)
    res = MicroBatcher(BatchPolicy(max_batch=4),
                       runner=runner).drain(np.zeros(5), reqs)
    assert called == [((1, 2, 4), 6)]              # AOT path, not throwaway
    assert res.n_requests == 5
    # non-jax runners expose no precompile hook (nothing to AOT-warm)
    py_runner = reg.runner("m", ExecutionSpec(engine="python"))
    assert not hasattr(py_runner, "precompile")
