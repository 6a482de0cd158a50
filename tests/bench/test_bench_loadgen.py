"""The traffic: arrivals' determinism and offered rate from the seed,
the spike pool, and the traffic files' parameters."""
import json

import numpy as np
import pytest

from bench_tiny import BENCH
import harness
import loadgen

POISSON = harness.load_module(BENCH / "arrivals/poisson.py")


def poisson_offsets(rate_rps, seconds, seed):
    return POISSON.offsets({"rate_rps": rate_rps}, seconds, seed)


def test_offsets_are_deterministic_and_fill_the_window():
    a = poisson_offsets(3000.0, 10.0, 2 ** 33 + 1)
    b = poisson_offsets(3000.0, 10.0, 2 ** 33 + 1)
    np.testing.assert_array_equal(a, b)
    assert len(a) == 30000
    assert a[0] == 0.0 and np.all(np.diff(a) > 0)
    assert 9.99 < a[-1] < 10.0


def test_seeds_reorder_the_same_gaps():
    a = np.diff(poisson_offsets(500.0, 4.0, 1))
    b = np.diff(poisson_offsets(500.0, 4.0, 2))
    assert not np.array_equal(a, b)
    # same multiset of gaps but the first (which opens the window)
    both = np.sort(np.concatenate([a, b]))
    assert abs(np.mean(a) - np.mean(b)) < 0.02 * np.mean(a)
    assert len(both) == 2 * 1999


def test_gaps_are_exponential():
    gaps = np.diff(poisson_offsets(1000.0, 20.0, 7))
    cv = np.std(gaps) / np.mean(gaps)
    assert 0.95 < cv < 1.05          # an exponential's coefficient is 1


def test_spike_pool_is_seeded_binary_at_the_rate():
    a = loadgen.spike_pool(64, 10, 100, 0.2, 5)
    np.testing.assert_array_equal(a, loadgen.spike_pool(64, 10, 100, 0.2, 5))
    assert a.dtype == np.int32 and set(np.unique(a)) <= {0, 1}
    assert abs(a.mean() - 0.2) < 0.01
    assert not np.array_equal(a, loadgen.spike_pool(64, 10, 100, 0.2, 6))


OPEN = {"generator": "open_loop", "arrivals": "poisson", "rate_rps": 10.0,
        "max_batch": 4, "max_wait_us": 0}


@pytest.mark.parametrize("traffic,sound", [
    ({"generator": "back_to_back", "batch_per_chip": 8}, True),
    (OPEN, True),
    ({"generator": "closed_loop"}, False),
    ({"generator": "open_loop", "arrivals": "poisson"}, False),
    ({**OPEN, "arrivals": "bursty"}, False),
    ({**OPEN, "buckets": "linear"}, False),
    ({"batch_per_chip": 8}, False),
])
def test_traffic_files_are_validated(tmp_path, traffic, sound):
    """A traffic file holds exactly the parameters its generator and
    arrivals read: one that lacks one, or holds one nothing reads, or
    names a generator or arrivals with no file, is refused."""
    (tmp_path / "bench/traffic").mkdir(parents=True)
    for d in ("generators", "arrivals"):
        (tmp_path / "bench" / d).symlink_to(BENCH / d)
    (tmp_path / "bench/traffic/t.json").write_text(json.dumps(traffic))
    if sound:
        got = harness.load_traffic(tmp_path, "t")
        assert got.params == traffic
        assert got.generator.PARAMS
    else:
        with pytest.raises(harness.BenchError):
            harness.load_traffic(tmp_path, "t")
