"""The reference against the program, and the control against the
reference.

The reference (``bench/configs/layered_lif.py``) must agree bit for
bit with the program it judges, here at a size the CPU holds; the
control (the reference at ``weight_bits - 1``, ``bench/control.py``)
must fail the comparison on every seed, at the real configurations'
widths on a few rows, and on the tiny cells at their full check.
"""
import json

import numpy as np
import pytest

from bench_tiny import REPO, TINY_CONFIG, tiny_root
import checking
import control
import harness
import loadgen

LAYERED = harness.load_module(REPO / "bench/configs/layered_lif.py")
BUILD = harness.load_module(REPO / "bench/programs/layered_lif.py")


@pytest.mark.parametrize("seed", [0, 1, 2 ** 33 + 3])
def test_reference_agrees_with_the_program(seed):
    from repro.core import run_oracle
    cfg = TINY_CONFIG
    net = LAYERED.make_network(cfg, seed)
    program = BUILD.build(cfg, net, seed % 2 ** 31)
    ext = loadgen.spike_pool(6, cfg["timesteps"], net.n_inputs,
                             cfg["input_spike_rate"], seed)
    want = LAYERED.reference(net, ext)
    s, v, st = program.run(ext)
    got = (s, v, st["packet_counts"])
    assert checking.compare(got, want) == {
        "rows_wrong": 0, "spikes_wrong": 0, "v_wrong": 0, "packets_wrong": 0}
    assert want[0].sum() > 0
    s1, v1 = run_oracle(program.graph, ext[0])
    np.testing.assert_array_equal(s1, want[0][0])


@pytest.mark.parametrize("config", ["mnist-sfnn", "shd-srnn"])
def test_reference_agrees_with_the_oracle_at_full_width(config):
    from repro.core import from_quantized, oracle_packet_counts, run_oracle
    cfg = json.loads((REPO / f"bench/configs/{config}.json").read_text())
    net = LAYERED.make_network(cfg, 11)
    graph = from_quantized(BUILD.quantized_snn(cfg, net))
    ext = loadgen.spike_pool(2, cfg["timesteps"], net.n_inputs,
                             cfg["input_spike_rate"], 12)
    s, v, p = LAYERED.reference(net, ext)
    for i in range(2):
        s1, v1 = run_oracle(graph, ext[i])
        np.testing.assert_array_equal(s1, s[i])
        np.testing.assert_array_equal(v1, v[i])
        np.testing.assert_array_equal(oracle_packet_counts(ext[i], s1), p[i])


@pytest.mark.parametrize("config", ["mnist-sfnn", "shd-srnn"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_at_full_width(config, seed):
    cfg = json.loads((REPO / f"bench/configs/{config}.json").read_text())
    net = LAYERED.make_network(cfg, seed)
    lower = LAYERED.make_network(cfg, seed, cfg["weight_bits"] - 1)
    ext = loadgen.spike_pool(8, cfg["timesteps"], net.n_inputs,
                             cfg["input_spike_rate"], seed)
    numbers = checking.compare(LAYERED.reference(lower, ext),
                               LAYERED.reference(net, ext))
    numbers["requests_failed"] = 0
    correct, _ = checking.verdict(numbers)
    assert not correct
    assert numbers["rows_wrong"] == 8


@pytest.mark.parametrize("cell", ["tiny.steady", "tiny.offline"])
def test_control_script_fails_each_seed(tmp_path, cell):
    root = tiny_root(tmp_path)
    for seed in (4, 5, 2 ** 33 + 6):
        out = control.control_numbers(root, cell, seed)
        assert out["correct"] is False
        assert out["check"]["rows_wrong"]["value"] > 0
