import numpy as np

from repro.core import HardwareConfig, random_graph
from repro.core.graph import SNNGraph


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")


# -- shared graph/hardware fixtures (test_engine_jax, test_program) ---------

def make_hw(g, m=4, k=2):
    """A comfortably-feasible HardwareConfig for graph ``g``."""
    return HardwareConfig(
        n_spus=m, unified_mem_depth=4 * (g.n_synapses // m + g.n_internal),
        concentration=k, max_neurons=g.n_neurons,
        max_post_neurons=g.n_internal)


def make_feedforward(n_inputs=16, n_internal=12, n_synapses=150, seed=5):
    """Random graph restricted to input->internal synapses only."""
    g = random_graph(n_inputs, n_internal, n_synapses, seed=seed)
    ff = g.pre < n_inputs
    assert ff.sum() >= 8
    return SNNGraph(g.n_inputs, g.n_neurons, g.pre[ff], g.post[ff],
                    g.weight[ff], g.lif, g.output_slice)


def make_ext(g, b, t, rate=0.3, seed=0):
    """Binary [B, T, n_inputs] spike train for graph ``g``."""
    rng = np.random.default_rng(seed)
    return (rng.random((b, t, g.n_inputs)) < rate).astype(np.int32)


def alif_params(n_internal, seed=0, n_readout=0, subtractive=1):
    """Per-neuron ALIF parameters: leak shifts in 1..4, adaptation
    shifts in 2..5, adaptation on; the last ``n_readout`` neurons are
    leaky readouts that never fire."""
    from repro.snn.lif import NEVER_FIRES, NeuronParams
    rng = np.random.default_rng(seed)
    readout = np.arange(n_internal) >= n_internal - n_readout
    return NeuronParams.make(
        n_internal, leak_shift=rng.integers(1, 5, n_internal),
        v_threshold=np.where(readout, NEVER_FIRES, 12),
        adapt_shift=rng.integers(2, 6, n_internal),
        adapt_inc=np.where(readout, 0, 3),
        subtractive=np.where(readout, 0, subtractive))


def with_params(g, params):
    """``g`` with its Neuron Unit parameters replaced."""
    return SNNGraph(g.n_inputs, g.n_neurons, g.pre, g.post, g.weight,
                    params, g.output_slice)


# -- the fused kernels' blocks (test_fused_kernel, test_alif) ---------------

STEP_BLOCKS = ["tile", "resolved", "tiled-pre"]


def block_of_kind(kind, monkeypatch, batch, n_all, n_int, n_state, operand):
    """A block for the fused kernels' tiled-vs-single-tile pins: the
    fixed (8, 128, 128) ``"tile"``; the block ``resolve_block`` gives
    these shapes (``"resolved"``, the pre axis in one block); or the one
    it falls back to under a VMEM budget that fits a single 128-wide
    pre block (``"tiled-pre"``)."""
    from repro.kernels import fused_step as fs
    if kind == "tile":
        return 8, 128, 128
    if kind == "tiled-pre":
        bb = fs.resolve_block(batch, n_all, n_state, operand)[0]
        monkeypatch.setattr(fs, "VMEM_BUDGET", fs.vmem_bytes(
            (bb, 128, 128), n_state, operand))
    block = fs.resolve_block(batch, n_all, n_state, operand)
    assert (block[2] < n_all) == (kind == "tiled-pre"), block
    return block
