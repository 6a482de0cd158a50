"""chip_smoke.py on the CPU: its phases at a tiny size in interpret
mode, and its refusal to run anywhere but on a TPU.

The phases are the ones the chip run drives at the paper's widths:
build (init -> quantize -> compile), serve (save -> load into a
registry with AOT precompile -> measured-mode micro-batcher), the
oracle check, and the sharded comparison. ``main()`` alone asserts the
device, so it must refuse here and print no result line.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core import ExecutionSpec, HardwareConfig
from repro.snn.models import SNNConfig
from repro.snn.quantize import QuantConfig

ROOT = Path(__file__).resolve().parents[1]
TINY = SNNConfig(layer_sizes=(24, 16, 4), recurrent=True, sparsity=0.5,
                 timesteps=6)
TINY_HW = HardwareConfig(n_spus=4, unified_mem_depth=256, concentration=2,
                         max_neurons=44, max_post_neurons=20)
INTERPRET = ExecutionSpec(interpret=True)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    return smoke.build_program(TINY, TINY_HW, QuantConfig(4, 5), seed=0,
                               max_iters=4000)


def test_served_requests_match_oracle(smoke, tiny, tmp_path):
    arrivals, reqs = smoke.make_requests(tiny, 20, TINY.timesteps, 0.3,
                                         seed=1)
    served, res, _ = smoke.serve(tiny, INTERPRET, "tiny", tmp_path,
                                 arrivals, reqs)
    assert served is not tiny                    # reloaded from its file
    assert (tmp_path / "tiny.npz").exists()
    assert smoke.check_against_oracle(served, reqs, res) > 0
    assert len(res.batches) > 1
    # the checker is not vacuous: one flipped spike fails it
    res.outputs[0][0, 0, 0] ^= 1
    with pytest.raises(AssertionError):
        smoke.check_against_oracle(served, reqs, res)
    # and interpret mode is never taken for a chip run
    with pytest.raises(AssertionError):
        smoke.check_tpu_executable(served, INTERPRET, TINY.timesteps)


def test_sharded_phase_bit_exact(smoke, tiny):
    n = len(jax.devices())
    _, reqs = smoke.make_requests(tiny, n * 8 + 1, TINY.timesteps, 0.3,
                                  seed=2)
    n_shards, devices = smoke.check_sharded(
        tiny, ExecutionSpec(mesh="auto", interpret=True), reqs)
    assert n_shards == n and len(devices) == n


def _run_script(cwd, env):
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("where", ["checkout", "script_alone"])
def test_main_refuses_without_tpu(smoke, capsys, tmp_path, where):
    out = tmp_path / "out"
    out.mkdir()
    assert smoke.main(["--out", str(out)]) != 0
    assert '"ok"' not in capsys.readouterr().out
    assert not list(out.iterdir())               # refused before any work
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cwd = ROOT
    if where == "script_alone":                  # no repo beside it
        cwd = tmp_path / "alone"
        cwd.mkdir()
        shutil.copy(ROOT / "chip_smoke.py", cwd)
        env.pop("PYTHONPATH", None)
    proc = _run_script(cwd, env)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
