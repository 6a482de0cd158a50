"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in the program's executor (the outputs a call
hands back), or in the gather across chips, and the rest of the run is
the harness's own: the check must catch it.
"""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench_tiny import tiny_root
import harness
import repro.core.engine_jax as engine_jax

HERE = Path(__file__).resolve().parent


def state_unchanged(s, v, st):
    v[...] = 0                       # membrane state handed back untouched


def half_batch_left_out(s, v, st):
    b = s.shape[0] // 2              # rows b: never computed
    s[b:], v[b:] = 0, 0
    st["packet_counts"][b:] = 0


def answer_altered(s, v, st):
    s[0, 0, 0] ^= 1                  # one spike flipped where it is produced


FAULTS = {"state_unchanged": state_unchanged,
          "half_batch_left_out": half_batch_left_out,
          "answer_altered": answer_altered}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("faults"))


@pytest.mark.parametrize("cell", ["tiny.steady", "tiny.offline"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_not_correct(root, cell, fault, monkeypatch):
    orig = engine_jax.finalize_outputs

    def broken(*args):
        s, v, st = orig(*args)
        s, v = s.copy(), v.copy()
        st = dict(st, packet_counts=st["packet_counts"].copy())
        FAULTS[fault](s, v, st)
        return s, v, st

    monkeypatch.setattr(engine_jax, "finalize_outputs", broken)
    result = harness.run_cell(root, cell, 3, 0.4, False,
                              t_process=time.perf_counter(),
                              require_tpu=False)
    assert result["correct"] is False
    assert any(v["value"] > v["limit"] for v in result["check"].values())


@pytest.mark.parametrize("fault", ["sound", "no_exchange"])
def test_exchange_between_chips_left_out(root, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "dp_fault_run.py"),
                        str(root), fault], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"] is (fault == "sound")
    if fault == "no_exchange":
        assert result["check"]["rows_wrong"]["value"] > 0
