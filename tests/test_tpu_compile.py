"""The main path's Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) cannot show what the chip's
compiler refuses: a contraction off the MXU's operand types, a block
not aligned to the tiling, too much VMEM. These tests compile the
kernels, not run them, for a *described* v5e — the TPU compiler ships
with jaxlib, so no chip is needed — at the paper's plane shapes:

* MNIST SFNN, 784-116-10: 910 x 126 plane, int8 operands;
* SHD SRNN, 700-300-20: 1020 x 320 plane, int8 operands, or an int16
  plane in its bf16 operand form;
* the adaptive SRNN, 700-400-400-35: 1535 x 835 plane, int8 operands.

Each kernel compiles at an explicit (8, 128, 128) block and at the
block it resolves from the shapes on the chip (``block=None``).

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and a module that
loaded it during collection would give the test workers different test
sets. The persistent compilation cache is off around these compiles
(an entry compiled for an absent chip cannot be read back).
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import make_hw
from repro.core import ExecutionSpec, compile, random_graph
from repro.kernels.fused_step import (MAX_DENSE_BYTES, fused_step,
                                      fused_step_alif, resolve_block)
from repro.kernels.lif_update import lif_update_int
from repro.snn.lif import LIFIntParams

LIF = LIFIntParams(leak_shift=3, v_threshold=30, v_reset=0)
TILE = (8, 128, 128)

PLANES = {"mnist-int8": (910, 126, jnp.int8),
          "shd-int16": (1020, 320, jnp.bfloat16)}
RESOLVED_PLANES = {"mnist-int8": (910, 126, jnp.int8),
                   "shd-int8": (1020, 320, jnp.int8),
                   "shd-int16": (1020, 320, jnp.bfloat16),
                   "ssc-int8": (1535, 835, jnp.int8)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:           # no TPU compiler in this jaxlib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("batch", [8, 16])
@pytest.mark.parametrize("plane", sorted(PLANES))
def test_fused_step_compiles_for_v5e(one_chip, plane, batch):
    n_all, n_int, operand = PLANES[plane]

    def step(s_all, v, w):
        return fused_step(s_all, v, w, LIF, block=TILE, interpret=False)

    text = _compiled_text(step, one_chip, ((batch, n_all), jnp.int32),
                          ((batch, n_int), jnp.int32),
                          ((n_all, n_int), operand))
    assert "tpu_custom_call" in text


def test_lif_update_int_compiles_for_v5e(one_chip):
    def nu(v, current):
        return lif_update_int(v, current, LIF, interpret=False)

    text = _compiled_text(nu, one_chip, ((8, 320), jnp.int32),
                          ((8, 320), jnp.int32))
    assert "tpu_custom_call" in text



def test_engine_scan_names_its_kernel_for_v5e(one_chip):
    """The engine's scan, compiled for the chip, calls its kernel by the
    stable name ``fused_step``, still as a ``tpu_custom_call`` (what the
    benchmark's kernel readers match in a device trace), inside the
    ``engine_scan`` scope."""
    g = random_graph(24, 40, 600, seed=3)
    prog = compile(g, make_hw(g))
    eng = prog.engine(ExecutionSpec(kernel="fused", interpret=False))
    lw = eng.lowered
    text = _compiled_text(eng.step_fn, one_chip,
                          ((8, 5, lw.n_inputs), jnp.int32),
                          ((8, lw.n_internal), jnp.int32),
                          ((8, lw.n_internal), jnp.int32))
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    for ln in calls:
        assert re.match(r"\s*(ROOT )?%fused_step[.\d]* = ", ln), ln
        assert re.search(r'op_name="[^"]*engine_scan[^"]*fused_step', ln), ln


@pytest.fixture(scope="module")
def shd_width_engine():
    """A fused engine at the SHD SRNN's input width (700 channels into
    a 320-wide internal plane, int8 operands)."""
    g = random_graph(700, 320, 37433, seed=3)
    prog = compile(g, make_hw(g), max_iters=2000)
    return prog.engine(ExecutionSpec(kernel="fused", interpret=False))


@pytest.mark.parametrize("batch", [8, 16])
def test_engine_scan_takes_int8_trains_for_v5e(one_chip, shd_width_engine,
                                               batch):
    """The engine's scan compiles for the chip with the train as ``run``
    sends it, int8 ``[B, T, 700]``: the executable takes one byte per
    spike and widens it on the device."""
    lw = shd_width_engine.lowered
    text = _compiled_text(shd_width_engine.step_fn, one_chip,
                          ((batch, 100, lw.n_inputs), jnp.int8),
                          ((batch, lw.n_internal), jnp.int32),
                          ((batch, lw.n_internal), jnp.int32))
    entry = text[text.index("ENTRY"):].splitlines()
    params = [ln for ln in entry if " parameter(" in ln]
    assert any(f"s8[{batch},100,{lw.n_inputs}]" in ln for ln in params), \
        params
    assert 'custom_call_target="tpu_custom_call"' in text


@pytest.mark.parametrize("batch", [8, 128])
def test_fused_step_alif_compiles_for_v5e(one_chip, batch):
    """The per-neuron kernel at the ALIF SRNN's plane (700-400-400-35:
    1535 x 835, int8 operands), its parameter rows one [8, 128] block
    per post tile, compiles for the chip under its own name."""
    def step(s_all, v, a, w, p):
        return fused_step_alif(s_all, v, a, w, p, block=TILE,
                               interpret=False)

    text = _compiled_text(step, one_chip, ((batch, 1535), jnp.int32),
                          ((batch, 835), jnp.int32),
                          ((batch, 835), jnp.int32),
                          ((1535, 835), jnp.int8), ((8, 835), jnp.int32))
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all(
        re.match(r"\s*(ROOT )?%fused_step_alif[.\d]* = ", ln) for ln in calls)


def _kernel_text(one_chip, kernel, batch, n_all, n_int, operand):
    """The kernel at ``block=None`` compiled for the chip, so it runs the
    block it resolves from these shapes."""
    state = ((batch, n_int), jnp.int32)
    if kernel == "fused_step":
        return _compiled_text(
            lambda s, v, w: fused_step(s, v, w, LIF, interpret=False),
            one_chip, ((batch, n_all), jnp.int32), state,
            ((n_all, n_int), operand))
    return _compiled_text(
        lambda s, v, a, w, p: fused_step_alif(s, v, a, w, p,
                                              interpret=False),
        one_chip, ((batch, n_all), jnp.int32), state, state,
        ((n_all, n_int), operand), ((8, n_int), jnp.int32))


def _assert_one_kernel(text, kernel):
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    for ln in calls:
        assert re.match(rf"\s*(ROOT )?%{kernel}[.\d]* = ", ln), ln


@pytest.mark.parametrize("batch", [8, 32, 128])
@pytest.mark.parametrize("plane", sorted(RESOLVED_PLANES))
@pytest.mark.parametrize("kernel", ["fused_step", "fused_step_alif"])
def test_resolved_block_compiles_for_v5e(one_chip, kernel, plane, batch):
    """Both kernels compile for the chip at the block they resolve on
    the paper's planes (the whole pre axis in one block, the batch in
    one), with no VMEM or alignment error, under their own names."""
    n_all, n_int, operand = RESOLVED_PLANES[plane]
    assert resolve_block(batch, n_all, 2, operand)[2] >= n_all
    _assert_one_kernel(_kernel_text(one_chip, kernel, batch, n_all, n_int,
                                    operand), kernel)


@pytest.mark.parametrize("kernel", ["fused_step", "fused_step_alif"])
def test_resolved_block_compiles_for_widest_plane_for_v5e(one_chip, kernel):
    """A plane at the densification limit, too wide for its pre axis in
    one block, compiles at the tiled pre axis it falls back to."""
    n_all, n_int = 16384, MAX_DENSE_BYTES // (4 * 16384)
    assert resolve_block(128, n_all, 2, jnp.int8)[2] < n_all
    _assert_one_kernel(_kernel_text(one_chip, kernel, 128, n_all, n_int,
                                    jnp.int8), kernel)


def test_alif_engine_scan_names_its_kernel_for_v5e(one_chip):
    """A per-neuron program's scan carries (v, a, s) and calls
    ``fused_step_alif``, never ``fused_step``; a LIF program of the same
    graph still calls ``fused_step``."""
    from conftest import alif_params, with_params
    g = random_graph(24, 40, 600, seed=3)
    ga = with_params(g, alif_params(g.n_internal, seed=1, n_readout=4))
    shapes = lambda lw, n: [((8, 5, lw.n_inputs), jnp.int8)] + [
        ((8, lw.n_internal), jnp.int32)] * n
    for graph, name, n_state in ((ga, "fused_step_alif", 3),
                                 (g, "fused_step", 2)):
        eng = compile(graph, make_hw(graph)).engine(
            ExecutionSpec(kernel="fused", interpret=False))
        assert eng.n_state == n_state
        text = _compiled_text(eng.step_fn, one_chip,
                              *shapes(eng.lowered, n_state))
        calls = [ln for ln in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln]
        assert calls
        for ln in calls:
            assert re.match(rf"\s*(ROOT )?%{name}[.\d]* = ", ln), ln
