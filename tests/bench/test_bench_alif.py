"""The adaptive network's cell (``ssc.offline``) and the short feed-forward
offline cell (``mnist.offline``) at a size the CPU holds: whole runs of
the harness, the plain reference against the program, the control, and
a fault in the per-neuron kernel that the check must catch."""
import json
import time

import numpy as np
import pytest

from alif_tiny import CELLS, TINY_ALIF_CONFIG, alif_root
from bench_tiny import REPO
import checking
import control
import harness
import loadgen
import repro.core.engine_jax as engine_jax

ALIF = harness.load_module(REPO / "bench/configs/layered_alif.py")
ALIF_BUILD = harness.load_module(REPO / "bench/programs/layered_alif.py")
SSC = json.loads((REPO / "bench/configs/ssc-alif-srnn.json").read_text())
CELL_NAMES = [c["name"] for c in CELLS]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return alif_root(tmp_path_factory.mktemp("alif"))


def run(root, cell, trace, seed=2 ** 33 + 19):
    return harness.run_cell(root, cell, seed, 0.4, trace,
                            t_process=time.perf_counter(),
                            require_tpu=False)


@pytest.mark.parametrize("cell", CELL_NAMES)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_correct(root, cell, trace):
    """Each cell runs through the harness, is correct, and reports the
    offline cells' metrics: ``setup_s`` and ``frames_per_s`` untraced,
    the per-layer ones it has something to read for traced."""
    line = run(root, cell, trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["_info"]["reference_spikes"] > 0
    bench = harness.load_benchmark(root)
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in harness.metrics_for(bench, cell, kind)}
    assert set(line["metrics"]) <= allowed
    if trace:
        assert {"call_ms.offline", "prep_ms.offline",
                "transfer_mb.offline"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"setup_s", "frames_per_s"}


def test_neuron_unit_state_counter(root):
    """The traced adaptive cell reads the state the engine puts on the
    device per call: ``v`` and ``a``, int32, over the batch of 8; the
    feed-forward LIF cell's holds ``v`` alone. No ALIF kernel shows in
    a CPU trace, so the kernel readers are silent, never zero."""
    for cell, cfg, words in (("tiny-alif.offline", TINY_ALIF_CONFIG, 2),
                             ("tiny.offline", None, 1)):
        if cfg is None:
            cfg = harness.load_config(root, harness.find_cell(
                harness.load_benchmark(root), cell)[1])
        n = sum(cfg["layer_sizes"][1:])
        line = run(root, cell, True)
        assert line["correct"]
        assert line["metrics"]["nu_state_mb.offline"]["value"] == \
            pytest.approx(words * 8 * n * 4 / 1e6)
        assert "alif_kernel_ms.offline" not in line["metrics"]
        assert "alif_step_roofline.offline" not in line["metrics"]


@pytest.mark.parametrize("fault", ["sound", "no_adaptation"])
def test_alif_epilogue_without_adaptation(root, fault, monkeypatch):
    """An ALIF kernel whose epilogue drops the adaptation term (the
    threshold never rises after a spike) makes the adaptive cell not
    correct; the sound kernel is correct."""
    from repro.kernels.fused_step import _ROW
    orig = engine_jax.fused_step_alif

    def no_adaptation(s_all, v, a, w, params, **kw):
        return orig(s_all, v, a, w, params.at[_ROW["adapt_inc"]].set(0),
                    **kw)

    if fault == "no_adaptation":
        monkeypatch.setattr(engine_jax, "fused_step_alif", no_adaptation)
    line = run(root, "tiny-alif.offline", False, seed=3)
    assert line["correct"] is (fault == "sound")
    if fault == "no_adaptation":
        assert line["check"]["spikes_wrong"]["value"] > 0


@pytest.mark.parametrize("seed", [0, 1, 2 ** 33 + 3])
def test_alif_reference_agrees_with_the_program(seed):
    """The fused tier, the reference tier and the oracle all agree with
    the plain reference, and on the final adaptation with each other;
    the readouts never fire."""
    from repro.core import ExecutionSpec
    cfg = TINY_ALIF_CONFIG
    net = ALIF.make_network(cfg, seed)
    program = ALIF_BUILD.build(cfg, net, seed % 2 ** 31)
    assert program.feasible and program.graph.scalar_lif is None
    ext = loadgen.spike_pool(6, cfg["timesteps"], net.n_inputs,
                             cfg["input_spike_rate"], seed)
    want = ALIF.reference(net, ext)
    assert want[0].sum() > 0
    assert not want[0][:, :, -cfg["layer_sizes"][-1]:].any()
    oracle = program.run(ext, "oracle")
    for spec in (ExecutionSpec(kernel="fused"),
                 ExecutionSpec(kernel="reference"), "oracle"):
        s, v, st = program.run(ext, spec)
        assert checking.compare((s, v, st["packet_counts"]), want) == {
            "rows_wrong": 0, "spikes_wrong": 0, "v_wrong": 0,
            "packets_wrong": 0}
        np.testing.assert_array_equal(st["adaptation"],
                                      oracle[2]["adaptation"])


def test_alif_reference_agrees_with_the_oracle_at_full_width():
    from repro.core import from_quantized, oracle_packet_counts, run_oracle
    net = ALIF.make_network(SSC, 11)
    graph = from_quantized(ALIF_BUILD.quantized_snn(SSC, net))
    ext = loadgen.spike_pool(1, SSC["timesteps"], net.n_inputs,
                             SSC["input_spike_rate"], 12)
    s, v, p = ALIF.reference(net, ext)
    assert s.sum() > 0
    s1, v1 = run_oracle(graph, ext[0])
    np.testing.assert_array_equal(s1, s[0])
    np.testing.assert_array_equal(v1, v[0])
    np.testing.assert_array_equal(oracle_packet_counts(ext[0], s1), p[0])


def test_alif_configuration_compiles_feasible_at_full_width():
    """The 700-400-400-35 network, nothing cut, maps feasibly on the
    configuration's hardware with its partitioner, and its int8 plane
    and int32 Neuron Unit state are proven."""
    net = ALIF.make_network(SSC, 2 ** 40 + 7)
    program = ALIF_BUILD.build(SSC, net, 7)
    assert program.feasible
    assert program.n_synapses == net.n_synapses > 700_000
    assert program.graph.scalar_lif is None
    assert "neuron_params" in program.report.phase_seconds
    r = program.verify(["ranges"]).stats["ranges"]
    assert r["int32_safe"] and r["mxu_operand"] == "int8"


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_alif_control_fails_at_full_width(seed):
    net = ALIF.make_network(SSC, seed)
    lower = ALIF.make_network(SSC, seed, SSC["weight_bits"] - 1)
    ext = loadgen.spike_pool(8, SSC["timesteps"], net.n_inputs,
                             SSC["input_spike_rate"], seed)
    numbers = checking.compare(ALIF.reference(lower, ext),
                               ALIF.reference(net, ext))
    numbers["requests_failed"] = 0
    correct, _ = checking.verdict(numbers)
    assert not correct
    assert numbers["rows_wrong"] == 8


@pytest.mark.parametrize("cell", CELL_NAMES)
def test_control_script_fails_each_seed(root, cell):
    for seed in (4, 5, 2 ** 33 + 6):
        out = control.control_numbers(root, cell, seed)
        assert out["correct"] is False
        assert out["check"]["rows_wrong"]["value"] > 0
