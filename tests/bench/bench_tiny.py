"""A benchmark tree at a size the CPU test run holds.

:func:`tiny_root` copies ``bench/`` into a temporary directory, adds a
small recurrent configuration with its own open-loop, back-to-back and
four-chip cells, and writes a ``BENCHMARK.json`` that names them: the
harness then runs them exactly as it runs the real cells, with the
kernel in interpret mode.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TINY_CONFIG = {
    "model": "layered_lif",
    "source": "a small recurrent LIF network for tests",
    "layer_sizes": [24, 16, 6],
    "recurrent": True,
    "timesteps": 6,
    "sparsity": 0.5,
    "leak_alpha": 0.25,
    "v_threshold": 1.0,
    "v_reset": 0.0,
    "weight_bits": 7,
    "potential_bits": 12,
    "weight_gain": 3.0,
    "recurrent_gain": 1.0,
    "input_spike_rate": 0.3,
    "hardware": {"n_spus": 4, "unified_mem_depth": 456, "concentration": 2,
                 "weight_bits": 7, "potential_bits": 12, "max_neurons": 46,
                 "max_post_neurons": 22, "clock_mhz": 100.0},
    "partitioner": {"max_iters": 200},
}
TINY_TRAFFIC = {
    "steady.tiny": {"generator": "open_loop", "arrivals": "poisson",
                    "rate_rps": 60.0, "max_batch": 4, "max_wait_us": 0},
    "offline.tiny": {"generator": "back_to_back", "batch_per_chip": 8},
}
TINY_CELLS = [
    {"name": "tiny.steady", "config": "tiny", "traffic": "steady.tiny",
     "chips": 1, "why": "open loop through the front end"},
    {"name": "tiny.offline", "config": "tiny", "traffic": "offline.tiny",
     "chips": 1, "why": "back to back through Program.run"},
    {"name": "tiny.offline.dp4", "config": "tiny", "traffic": "offline.tiny",
     "chips": 4, "why": "back to back through ShardedRunner"},
]


def tiny_root(tmp: Path) -> Path:
    """A benchmark root under ``tmp`` holding the real ``bench/`` and the
    tiny cells."""
    root = Path(tmp) / "root"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    for name, traffic in TINY_TRAFFIC.items():
        (root / "bench/traffic" / f"{name}.json").write_text(
            json.dumps(traffic))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "tests",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"] += TINY_CELLS
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads")
        if cells is None:
            continue
        if any(c.endswith("steady") for c in cells):
            cells.append("tiny.steady")
        else:
            cells += ["tiny.offline", "tiny.offline.dp4"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
