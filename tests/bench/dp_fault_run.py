"""Run the tiny four-chip cell on four host devices, sound or with the
exchange between chips left out; print the result line.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python dp_fault_run.py ROOT {sound,no_exchange}
"""
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))
import bench_tiny  # noqa: E402,F401  (puts bench/ on the path)
import harness  # noqa: E402
from repro.serve.sharded import ShardedRunner  # noqa: E402


def no_exchange(orig):
    """Each chip's rows never reach the others: every shard of the
    gathered outputs is chip 0's."""
    def shard_outputs(self, ext):
        outs = orig(self, ext)
        n = outs[0].shape[0] // self.n_shards
        return tuple(np.concatenate([np.asarray(a)[:n]] * self.n_shards)
                     for a in outs)
    return shard_outputs


if __name__ == "__main__":
    root, fault = Path(sys.argv[1]), sys.argv[2]
    if fault == "no_exchange":
        ShardedRunner.shard_outputs = no_exchange(ShardedRunner.shard_outputs)
    result = harness.run_cell(root, "tiny.offline.dp4", 5, 0.4, False,
                              t_process=time.perf_counter(),
                              require_tpu=False)
    result.pop("_info")
    print(json.dumps(result))
