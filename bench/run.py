"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload shd.steady --seed 7 --seconds 10 --trace 0

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs
the same window under the profiler and prints its per-layer metrics,
``device.busy_s``/``window_s`` and a ``breakdown``. The last line of
standard output is one JSON object; the numbers compared with the
reference, each with its limit, are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.

``--sweep R1,R2,...`` (open-loop cells) serves each offered rate for
``--seconds`` after one set-up and prints one JSON row per rate: the
table the cell's rate is chosen from.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()          # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated offered rates (req/s)")
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="write the profiler trace here and keep it "
                         "(how the reducer's test fixture is recorded)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}; nothing to run",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    rates = ([float(r) for r in args.sweep.split(",")] if args.sweep
             else None)
    try:
        result = harness.run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            t_process=T_PROCESS, keep_trace=args.keep_trace,
            sweep_rates=rates)
    except harness.BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    if rates:
        for row in result["sweep"]:
            print(json.dumps(row), flush=True)
        return 0
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
