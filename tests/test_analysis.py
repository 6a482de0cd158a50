"""The static artifact verifier (DESIGN.md §13).

Three layers of coverage:

* golden / compile-matrix cleanliness — ``Program.verify()`` emits
  ZERO diagnostics on the pinned golden artifact and on every
  ``compile()`` output across graph shapes, mapping strategies, and
  schedule strategies (plus a hypothesis property over random graphs);
* the mutation self-test — each class of verified field is corrupted
  on a fresh golden load and the expected diagnostic code must fire
  (the checkers prove they actually check something);
* the range analysis — the int8 MNIST-flavored / int16 SHD-flavored
  dense-plane dtype choices are confirmed STATICALLY (no engine, no
  densification) and pinned against what ``pack_dense`` then does.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import (CHECKERS, CODES, Diagnostic, Severity,
                            register_checker, register_code, verify)
from repro.analysis.ranges import (dense_column_abs_bound,
                                   dense_plane_bounds, min_safe_dtype,
                                   mxu_operand_dtype, signed_bits)
from repro.analysis.schedule import check_schedule
from repro.core import HardwareConfig, Program, compile, random_graph
from repro.core.passes import lower_pass
from repro.serve.registry import ProgramRegistry
from repro.snn.lif import LIFIntParams

from conftest import make_feedforward, make_hw

GOLDEN = Path(__file__).parent / "golden" / "tiny_program_v1.npz"
NOP = -1


def golden() -> Program:
    return Program.load(GOLDEN)


# -- cleanliness ------------------------------------------------------------

def test_golden_artifact_is_clean():
    rep = golden().verify()
    assert rep.ok and not rep.diagnostics, rep.summary()
    assert rep.checkers == ["artifact", "schedule", "ranges", "memory"]
    assert rep.wall_ms > 0 and set(rep.checker_wall_ms) == set(rep.checkers)
    assert rep.summary().startswith("clean: 0 diagnostics")


@pytest.mark.parametrize("method", ["framework", "synapse_rr", "hypergraph"])
@pytest.mark.parametrize("recurrent", [False, True])
def test_every_compile_output_is_clean(method, recurrent):
    g = (random_graph(10, 12, 120, seed=3) if recurrent
         else make_feedforward())
    p = compile(g, make_hw(g), method=method)
    rep = p.verify()
    assert rep.ok and not rep.diagnostics, rep.summary()


@pytest.mark.parametrize("schedule_method",
                         ["slack", "consecutive", "load_balance"])
def test_every_schedule_strategy_is_clean(schedule_method):
    g = random_graph(8, 10, 90, seed=11)
    p = compile(g, make_hw(g), schedule_method=schedule_method)
    assert p.verify().ok


def test_leak_shift_zero_is_clean():
    lif = LIFIntParams(leak_shift=0, v_threshold=9, v_reset=-2)
    g = random_graph(6, 8, 40, seed=7, lif=lif)
    p = compile(g, make_hw(g))
    rep = p.verify()
    assert rep.ok, rep.summary()
    # with a full leak the carried state contributes nothing upward and
    # the lower fixpoint degenerates to the one-step sums
    r = rep.stats["ranges"]
    assert r["membrane_hi"] == r["current_hi"]
    assert r["membrane_lo"] == min(0, -2, r["current_lo"])


# -- the mutation self-test --------------------------------------------------

def _mutate_sched001(p):      # truncated op row
    t = p.tables
    s, slot = map(int, np.argwhere(t.pre != NOP)[0])
    t.pre[s, slot] = NOP
    t.post[s, slot] = NOP
    t.weight[s, slot] = 0
    t.pre_end[s, slot] = False
    t.post_end[s, slot] = False


def _mutate_sched003(p):      # Post-End flag drifts off the send slot
    t = p.tables
    s, slot = map(int, np.argwhere(t.post_end)[0])
    post = int(t.post[s, slot])
    t.send_slot[post] = slot + 1


def _mutate_sched004(p):      # duplicate Post-End in one SPU
    t = p.tables
    s, slot = map(int, np.argwhere(t.post_end)[0])
    post = int(t.post[s, slot])
    others = np.argwhere((t.post == post) & (t.pre != NOP) & ~t.post_end)
    others = [o for o in others if int(o[0]) == s]
    assert others, "golden graph needs >= 2 ops per (spu, post)"
    t.post_end[int(others[0][0]), int(others[0][1])] = True


def _mutate_sched005(p):      # missing Post-End
    t = p.tables
    s, slot = map(int, np.argwhere(t.post_end)[0])
    t.post_end[s, slot] = False


def _mutate_sched006(p):      # op lands after its send slot
    t = p.tables
    post = max(t.send_slot, key=t.send_slot.__getitem__)
    assert t.send_slot[post] > 0
    t.send_slot[post] = 0


def _mutate_sched008(p):      # two posts share one send slot
    t = p.tables
    p1, p2 = sorted(t.send_slot)[:2]
    t.send_slot[p2] = t.send_slot[p1]


def _mutate_sched009(p):      # NOP slot carries payload
    t = p.tables
    nops = np.argwhere(t.pre == NOP)
    assert len(nops), "golden tables need at least one NOP slot"
    t.post[int(nops[0][0]), int(nops[0][1])] = 5


def _widen_weight(p, value):  # consistently in graph AND tables
    g, t = p.graph, p.tables
    pre, post = int(g.pre[0]), int(g.post[0])
    g.weight[0] = value
    hits = np.argwhere((t.pre == pre) & (t.post == post))
    assert len(hits) == 1
    t.weight[int(hits[0][0]), int(hits[0][1])] = value


def _mutate_range001(p):      # weight outside the 4-bit UM field
    _widen_weight(p, 100)


def _mutate_range002(p):      # accumulator interval past int32
    _widen_weight(p, 2**31 - 1)


def _mutate_mem001(p):        # Eq. 9 overflow on a feasible-claimed artifact
    p.hw = dataclasses.replace(p.hw, unified_mem_depth=2)


def _mutate_mem002(p):
    p.report.scores[0] += 7


def _mutate_mem003(p):
    p.report.spu_post_counts[0] += 1


def _mutate_mem004(p):
    p.report.ot_depth += 1


def _mutate_mem005(p):        # shrunk memory stat
    p.report.resources.memory_kb *= 0.5


def _mutate_mem006(p):
    p.report.n_init_packets += 3


def _mutate_mem007(p):
    p.hw = dataclasses.replace(p.hw, max_neurons=p.graph.n_neurons - 1)


def _mutate_mem008(p):
    p.hw = dataclasses.replace(p.hw, max_post_neurons=1)


def _mutate_art001(p):        # torn arrays: assignment lost a synapse
    p.tables.assign = p.tables.assign[:-1]


def _mutate_art002(p):        # graph invariant: zero-weight synapse
    p.graph.weight[0] = 0


def _mutate_art003(p):        # partition names a nonexistent SPU
    p.tables.assign[0] = 99


MUTATIONS = [
    ("SCHED001", _mutate_sched001),
    ("SCHED003", _mutate_sched003),
    ("SCHED004", _mutate_sched004),
    ("SCHED005", _mutate_sched005),
    ("SCHED006", _mutate_sched006),
    ("SCHED008", _mutate_sched008),
    ("SCHED009", _mutate_sched009),
    ("RANGE001", _mutate_range001),
    ("RANGE002", _mutate_range002),
    ("MEM001", _mutate_mem001),
    ("MEM002", _mutate_mem002),
    ("MEM003", _mutate_mem003),
    ("MEM004", _mutate_mem004),
    ("MEM005", _mutate_mem005),
    ("MEM006", _mutate_mem006),
    ("MEM007", _mutate_mem007),
    ("MEM008", _mutate_mem008),
    ("ART001", _mutate_art001),
    ("ART002", _mutate_art002),
    ("ART003", _mutate_art003),
]


@pytest.mark.parametrize("code,mutate", MUTATIONS,
                         ids=[c for c, _ in MUTATIONS])
def test_mutation_fires_expected_code(code, mutate):
    p = golden()
    mutate(p)
    rep = p.verify()
    assert code in rep.codes(), \
        f"expected {code}; got {sorted(rep.codes())}\n{rep.summary()}"
    assert not rep.ok
    for d in rep.diagnostics:           # every code is a registered one
        assert d.code in CODES


def test_mutation_matrix_covers_enough_codes():
    # the acceptance floor: the self-test must prove >= 8 distinct
    # diagnostic codes actually fire
    assert len({c for c, _ in MUTATIONS}) >= 8


def test_art001_gates_the_other_checkers():
    p = golden()
    _mutate_art001(p)
    rep = p.verify()
    assert rep.checkers == ["artifact"] and not rep.ok


def test_sched001_wins_legacy_priority():
    # the legacy count assert fired before the multiset assert; the shim
    # must keep that order even though both diagnostics are emitted
    p = golden()
    _mutate_sched001(p)
    diags = check_schedule(p.graph, p.tables)
    codes = {d.code for d in diags}
    assert {"SCHED001", "SCHED002"} <= codes
    with pytest.raises(AssertionError, match=r"ops != \d+ synapses"):
        from repro.core.scheduling import validate_schedule
        validate_schedule(p.graph, p.tables)


def test_diagnostics_carry_location_and_hint():
    p = golden()
    _mutate_sched006(p)
    d = next(x for x in p.verify().diagnostics if x.code == "SCHED006")
    assert d.severity is Severity.ERROR
    assert d.location.post is not None and d.location.spu is not None
    assert d.hint
    assert "SCHED006" in str(d) and "post" in str(d)


# -- the range analysis (static dtype proofs, no engine execution) ----------

def test_range_proof_int8_mnist_flavor():
    # the paper's MNIST net quantizes to 4-bit weights -> int8 plane
    g = make_feedforward()                       # weights in [-7, 7]
    p = compile(g, make_hw(g))
    rep = p.verify()
    r = rep.stats["ranges"]
    assert r["dense_dtype"] == "int8" and r["int32_safe"]
    dense = __import__("repro.kernels.fused_step",
                       fromlist=["pack_dense"]).pack_dense(p.lowered)
    assert dense.dtype == np.int8
    assert (dense.value_min, dense.value_max) == (r["dense_lo"],
                                                  r["dense_hi"])
    assert (int(dense.weight.min()), int(dense.weight.max())) == \
        (r["dense_lo"], r["dense_hi"]) or 0 in (r["dense_lo"], r["dense_hi"])


def test_range_proof_int16_shd_flavor():
    # the paper's SHD net quantizes to 9-bit weights -> int16 plane
    g = random_graph(12, 10, 110, seed=2, weight_lo=-255, weight_hi=255)
    hw = dataclasses.replace(make_hw(g), weight_bits=9, potential_bits=18)
    p = compile(g, hw)
    rep = p.verify()
    assert rep.ok, rep.summary()
    r = rep.stats["ranges"]
    assert r["dense_dtype"] == "int16" and r["int32_safe"]
    assert r["mxu_operand"] == "bfloat16"
    from repro.kernels.fused_step import pack_dense
    assert pack_dense(p.lowered).dtype == np.int16


def test_range_bounds_are_sound_for_actual_runs():
    # the proven interval must contain every membrane value an engine
    # actually produces (checked with the pure-numpy oracle)
    from repro.core.engine import run_oracle
    from conftest import make_ext
    g = random_graph(8, 10, 80, seed=4)
    p = compile(g, make_hw(g))
    r = p.verify().stats["ranges"]
    ext = make_ext(g, 1, 24, rate=0.9)[0]
    _, v = run_oracle(g, ext)
    assert r["membrane_lo"] <= int(v.min()) and \
        int(v.max()) <= r["membrane_hi"]


def test_dense_plane_bounds_folds_duplicates():
    pre = np.array([0, 0, 1], np.int32)
    post = np.array([0, 0, 1], np.int32)
    w = np.array([100, 100, -3], np.int32)
    lo, hi = dense_plane_bounds(pre, post, w, 2, 2)
    assert (lo, hi) == (-3, 200)                 # 100+100 folds past int8
    assert min_safe_dtype(lo, hi) == "int16"


def test_mxu_operand_dtype_proof():
    assert mxu_operand_dtype(-128, 127, 10 ** 9) == "int8"
    assert mxu_operand_dtype(-256, 256, 2 ** 24) == "bfloat16"
    assert mxu_operand_dtype(-256, 256, 2 ** 24 + 1) is None
    assert mxu_operand_dtype(-257, 0, 300) is None
    assert mxu_operand_dtype(0, 257, 300) is None
    pre = np.array([0, 1, 2], np.int32)
    post = np.array([0, 0, 1], np.int32)
    w = np.array([100, -150, 7], np.int32)
    assert dense_column_abs_bound(post, w, 2) == 250
    assert dense_column_abs_bound(pre[:0], w[:0], 2) == 0


def test_min_safe_dtype_ladder():
    assert min_safe_dtype(-128, 127) == "int8"
    assert min_safe_dtype(-129, 0) == "int16"
    assert min_safe_dtype(0, 2**31 - 1) == "int32"
    assert min_safe_dtype(0, 2**31) == "int64"
    assert signed_bits(-8, 7) == 4
    assert signed_bits(0, 0) == 1


def test_pack_dense_guard_names_safe_dtype(monkeypatch):
    import repro.kernels.fused_step as fs
    g = make_feedforward()
    p = compile(g, make_hw(g))
    monkeypatch.setattr(fs, "MAX_DENSE_BYTES", 1)
    with pytest.raises(ValueError, match="minimal safe dtype int8"):
        fs.pack_dense(p.lowered)


def test_empty_style_edges():
    assert dense_plane_bounds(np.array([], np.int32), np.array([], np.int32),
                              np.array([], np.int32), 4, 2) == (0, 0)


# -- driver / registry plumbing ---------------------------------------------

def test_unknown_checker_name_rejected():
    with pytest.raises(KeyError, match="unknown checker"):
        verify(golden(), checkers=["nope"])


def test_unregistered_code_is_refused():
    def rogue(program):
        return [Diagnostic(code="BOGUS99", severity=Severity.ERROR,
                           message="x")], {}
    register_checker("rogue-test", rogue)
    try:
        with pytest.raises(ValueError, match="unregistered code"):
            verify(golden())
        with pytest.raises(ValueError, match="already registered"):
            register_checker("rogue-test", rogue)
    finally:
        CHECKERS.pop("rogue-test")


def test_register_code_title_is_a_contract():
    assert register_code("SCHED001", CODES["SCHED001"]) == "SCHED001"
    with pytest.raises(ValueError, match="already registered"):
        register_code("SCHED001", "something else")


def test_registry_verify_gate(tmp_path):
    reg = ProgramRegistry()
    reg.register("good", golden(), verify=True)
    bad = golden()
    _mutate_mem005(bad)
    with pytest.raises(ValueError, match="failed static verification"):
        reg.register("bad", bad, verify=True)
    assert "bad" not in reg
    # and the load() path forwards the gate
    p = golden()
    p.report.n_init_packets += 1
    path = p.save(tmp_path / "stale.npz")
    with pytest.raises(ValueError, match="MEM006"):
        reg.load("stale", path, verify=True)


# -- CLI --------------------------------------------------------------------

def _run_cli(*args):
    env = dict(os.environ)
    src = str(Path(__file__).parent.parent / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis.verify", *args],
        capture_output=True, text=True, env=env)


def test_cli_clean_artifact():
    r = _run_cli(str(GOLDEN), "--strict")
    assert r.returncode == 0, r.stderr
    assert "clean: 0 diagnostics" in r.stdout
    assert "RuntimeWarning" not in r.stderr     # no double-import of the CLI


def test_cli_json_and_failure_exit(tmp_path):
    p = golden()
    _mutate_mem004(p)
    path = p.save(tmp_path / "stale.npz")
    r = _run_cli(str(path), "--json")
    assert r.returncode == 1
    payload = json.loads(r.stdout)
    rep = payload[str(path)]
    assert rep["ok"] is False
    assert any(d["code"] == "MEM004" for d in rep["diagnostics"])


def test_cli_unreadable_artifact(tmp_path):
    bogus = tmp_path / "nope.npz"
    bogus.write_bytes(b"not an npz")
    r = _run_cli(str(bogus))
    assert r.returncode == 2 and "cannot load" in r.stderr


