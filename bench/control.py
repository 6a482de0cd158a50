"""The control of the comparison: the reference one bit coarser.

    python3 bench/control.py --workload shd.steady --seeds 1,2,3

The configurations state their weight precision (``weight_bits``). The
control puts the reference in the program's place, computed with the
same seeded float weights quantized to ``weight_bits - 1`` bits (the
step a later change would be tempted to take: a narrower plane), on the
rows a run of the cell compares, and reads the same numbers
:mod:`checking` compares. Each seed prints one JSON line; every line
must come out not correct. The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import checking  # noqa: E402
import harness  # noqa: E402


def control_numbers(root: Path, cell_name: str, seed: int) -> dict:
    """The compared numbers of the control on one seed of one cell."""
    bench = harness.load_benchmark(root)
    cell, entry = harness.find_cell(bench, cell_name)
    cfg = harness.load_config(root, entry)
    traffic = harness.load_traffic(root, cell["traffic"])
    ref = harness.reference_module(root, cfg)
    seeds = harness.derived_seeds(seed)
    net = ref.make_network(cfg, seeds.weights)
    lower = ref.make_network(cfg, seeds.weights, cfg["weight_bits"] - 1)
    # the inputs a run of the cell draws from, and a sample of them
    pool = traffic.generator.pool(SimpleNamespace(
        cfg=cfg, traffic=traffic, net=net, chips=int(cell["chips"]),
        timesteps=int(cfg["timesteps"]), seeds=seeds))
    pick = np.random.default_rng(seeds.sample).choice(
        len(pool), min(harness.CHECK_ROWS, len(pool)), replace=False)
    inputs = pool[np.sort(pick)]
    got = ref.reference(lower, inputs)
    numbers = harness.check_rows(ref, net, inputs, got)
    numbers["requests_failed"] = 0
    correct, table = checking.verdict(numbers)
    return {"workload": cell_name, "seed": seed, "correct": correct,
            "rows": int(len(inputs)), "weight_bits": cfg["weight_bits"] - 1,
            "check": table}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    args = ap.parse_args(argv)
    bad = 0
    for s in (int(x) for x in args.seeds.split(",")):
        out = control_numbers(ROOT, args.workload, s)
        bad += out["correct"]
        print(json.dumps(out), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
