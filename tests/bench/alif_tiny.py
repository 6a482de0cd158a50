"""The tiny benchmark tree of ``bench_tiny`` with two more cells:
``ssc.offline``'s model (a small adaptive recurrent network) and
``mnist.offline``'s (a small feed-forward network at short T), each
back to back, named in the ``offline`` metrics' cell lists as the tiny
offline cell is."""
from __future__ import annotations

import json
from pathlib import Path

from bench_tiny import TINY_CONFIG, tiny_root

TINY_ALIF_CONFIG = {
    "model": "layered_alif",
    "source": "a small adaptive recurrent network for tests",
    "layer_sizes": [24, 16, 12, 5],
    "recurrent": True,
    "timesteps": 8,
    "sparsity": 0.3,
    "weight_bits": 7,
    "potential_bits": 32,
    "weight_gain": 3.0,
    "recurrent_gain": 1.0,
    "v_threshold": 1.0,
    "adapt_inc": 0.5,
    "leak_shift_range": [1, 3],
    "adapt_shift_range": [2, 4],
    "input_spike_rate": 0.3,
    "hardware": {"n_spus": 4, "unified_mem_depth": 456, "concentration": 2,
                 "weight_bits": 7, "potential_bits": 32, "max_neurons": 57,
                 "max_post_neurons": 33, "clock_mhz": 100.0},
    "partitioner": {"method": "framework", "max_iters": 200},
}
TINY_FF_CONFIG = dict(TINY_CONFIG, layer_sizes=[20, 10, 4], recurrent=False,
                      timesteps=3, weight_bits=4, potential_bits=5,
                      hardware=dict(TINY_CONFIG["hardware"], weight_bits=4,
                                    potential_bits=5, max_neurons=34,
                                    max_post_neurons=14))
CONFIGS = {"tiny-alif": TINY_ALIF_CONFIG, "tiny-ff": TINY_FF_CONFIG}
CELLS = [
    {"name": "tiny-alif.offline", "config": "tiny-alif",
     "traffic": "offline.tiny", "chips": 1,
     "why": "the adaptive network back to back: the per-neuron kernel"},
    {"name": "tiny-ff.offline", "config": "tiny-ff",
     "traffic": "offline.tiny", "chips": 1,
     "why": "a feed-forward network at short T back to back"},
]


def alif_root(tmp: Path) -> Path:
    """``tiny_root(tmp)`` with the two cells added."""
    root = tiny_root(tmp)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name, cfg in CONFIGS.items():
        (root / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "source": "tests",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "tests"})
    bench["workloads"] += CELLS
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.offline" in m.get("workloads", []):
            m["workloads"] += [c["name"] for c in CELLS]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
