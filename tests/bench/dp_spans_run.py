"""Run the tiny four-chip cell traced on four host devices and print
its result line (the per-layer metrics the program's spans feed).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python dp_spans_run.py ROOT
"""
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))
import bench_tiny  # noqa: E402,F401  (puts bench/ on the path)
import harness  # noqa: E402

if __name__ == "__main__":
    result = harness.run_cell(Path(sys.argv[1]), "tiny.offline.dp4",
                              2 ** 33 + 5, 0.4, True,
                              t_process=time.perf_counter(),
                              require_tpu=False)
    result.pop("_info")
    print(json.dumps(result))
