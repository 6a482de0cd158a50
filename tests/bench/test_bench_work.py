"""Operations and bytes of a call come from the network's own shapes."""
import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import work


def test_call_work_on_known_shapes():
    ops, nbytes = work.call_work(n_synapses=1000, n_inputs=700,
                                 n_neurons=320, rows=128, timesteps=100,
                                 weight_bits=7)
    assert ops == 2 * 1000 * 128 * 100
    assert nbytes == 1000 + 128 * 100 * 1020 / 8 + 2 * 128 * 320 * 4
    # a 9..16-bit weight packs into two bytes
    _, wide = work.call_work(n_synapses=1000, n_inputs=1, n_neurons=1,
                             rows=0, timesteps=0, weight_bits=9)
    assert wide == 2000


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_fail():
    p = work.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v4")
    with pytest.raises(KeyError):
        work.peaks("source")


def test_roofline_bound_names_the_binding_peak():
    p = work.peaks("TPU v5 lite")
    t, which = work.roofline_bound_s(393e12, 1.0, p)
    assert (t, which) == (1.0, "int8")
    t, which = work.roofline_bound_s(1.0, 819e9, p)
    assert (t, which) == (1.0, "hbm")
