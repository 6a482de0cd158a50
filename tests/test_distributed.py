"""Distributed substrate: checkpointing (atomic, sharded, verifiable,
reshardable), gradient compression with error feedback, elastic re-mesh
planning, straggler detection, sharding rules."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.distributed.checkpoint import (CheckpointManager, latest_step,
                                          load_checkpoint, save_checkpoint)
from repro.distributed.compression import (compress_error_feedback,
                                           compress_int8, decompress_int8,
                                           init_error)
from repro.distributed.sharding import (LOGICAL_RULES_1POD, MeshRules,
                                        logical_constraint, mesh_rules,
                                        param_pspec)
from repro.distributed.straggler import StepJournal, StragglerMonitor


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"layers": {"w": jax.random.normal(k, (16, 8)),
                       "b": jnp.zeros((8,))},
            "step_count": jnp.asarray(7, jnp.int32)}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 3, t, n_shards=2, extra={"loss": 1.5})
    assert latest_step(str(tmp_path)) == 3
    like = jax.tree.map(jnp.zeros_like, t)
    restored, extra = load_checkpoint(str(tmp_path), None, like)
    assert extra["loss"] == 1.5
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_detects_corruption(tmp_path):
    t = _tree()
    d = save_checkpoint(str(tmp_path), 1, t)
    shard = os.path.join(d, "shard_00000.npz")
    with open(shard, "r+b") as f:
        f.seek(100)
        f.write(b"\xde\xad")
    with pytest.raises(AssertionError, match="hash mismatch"):
        load_checkpoint(str(tmp_path), 1, jax.tree.map(jnp.zeros_like, t))


def test_checkpoint_uncommitted_ignored(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 1, t)
    # simulate a crash mid-save: step dir without COMMITTED
    os.makedirs(tmp_path / "step_000000005")
    assert latest_step(str(tmp_path)) == 1


def test_checkpoint_manager_async_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    t = _tree()
    for s in (1, 2, 3, 4):
        mgr.save(s, t, blocking=True)
    steps = sorted(n for n in os.listdir(tmp_path) if n.startswith("step_"))
    assert len(steps) == 2 and steps[-1].endswith("4")
    restored, _ = mgr.restore(jax.tree.map(jnp.zeros_like, t))
    np.testing.assert_array_equal(np.asarray(restored["layers"]["w"]),
                                  np.asarray(t["layers"]["w"]))


# ---------------------------------------------------------------------------
# gradient compression + error feedback
# ---------------------------------------------------------------------------


def test_int8_roundtrip_accuracy():
    g = {"a": jax.random.normal(jax.random.PRNGKey(0), (1000,)),
         "b": jax.random.normal(jax.random.PRNGKey(1), (33, 77)) * 5.0}
    c = compress_int8(g, block=128)
    d = decompress_int8(c, g)
    for k in g:
        err = np.abs(np.asarray(d[k]) - np.asarray(g[k])).max()
        scale = np.abs(np.asarray(g[k])).max()
        assert err <= scale / 127.0 + 1e-6


@given(st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_error_feedback_unbiased_over_time(seed):
    """Sum of dequantized grads + final residual == sum of true grads —
    error feedback never loses mass (EF-SGD telescoping identity)."""
    rng = np.random.default_rng(seed)
    g_true = [jnp.asarray(rng.normal(size=(256,)), jnp.float32)
              for _ in range(5)]
    err = init_error({"g": g_true[0]})
    total_deq = jnp.zeros((256,))
    for g in g_true:
        comp, deq, err = compress_error_feedback({"g": g}, err, block=64)
        total_deq = total_deq + deq["g"]
    total_true = sum(np.asarray(g) for g in g_true)
    np.testing.assert_allclose(np.asarray(total_deq + err["g"]),
                               total_true, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# straggler monitor + journal
# ---------------------------------------------------------------------------


def test_straggler_monitor_flags_persistent_slowdowns():
    mon = StragglerMonitor(window=8, threshold=2.0, hysteresis=2)
    import time
    fired = []
    for i in range(12):
        mon.start_step()
        mon._t0 -= 0.01                 # simulate 10 ms steps
        if i >= 10:
            mon._t0 -= 0.05             # 6x slowdown
        fired.append(mon.end_step(i))
    assert fired[11] and not any(fired[:10])
    assert mon.summary()["straggler_events"] >= 2


def test_journal_replay(tmp_path):
    j = StepJournal(str(tmp_path / "j.jsonl"))
    for s in range(5):
        j.record(s, data_offset=s * 128, seed=0, checkpoint_step=s - s % 2)
    rp = j.replay_point()
    assert rp["step"] == 4 and rp["data_offset"] == 512
    # torn tail write must not break replay
    with open(tmp_path / "j.jsonl", "a") as f:
        f.write('{"step": 5, "data_off')
    assert j.replay_point()["step"] == 4


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------


def _rules():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    return MeshRules(mesh, LOGICAL_RULES_1POD)


def test_param_rules_match_paths():
    r = _rules()
    # shardable shapes: every dim divisible by 1 on the (1,1) test mesh
    assert param_pspec("layers/attn/wq", (4, 64, 64), r) == \
        jax.sharding.PartitionSpec(None, "data", "model")
    assert param_pspec("embed", (1024, 64), r) == \
        jax.sharding.PartitionSpec("model", "data")
    assert param_pspec("layers/moe/w_gate", (4, 8, 64, 32), r) == \
        jax.sharding.PartitionSpec(None, "model", "data", None)
    # norm scales fall through to replication
    assert param_pspec("layers/ln1/scale", (64,), r) == \
        jax.sharding.PartitionSpec()


def test_logical_constraint_noop_without_context():
    x = jnp.ones((4, 8))
    y = logical_constraint(x, "batch", None)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_logical_constraint_skips_indivisible():
    r = _rules()
    with mesh_rules(r):
        x = jnp.ones((3, 5))        # nothing divides -> still legal
        y = logical_constraint(x, "batch", "tensor")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_elastic_replan_shapes():
    from repro.distributed.elastic import replan_mesh
    mesh = replan_mesh(1, model_parallel=1)
    assert mesh.devices.size == 1
    assert "model" in mesh.axis_names
