"""Device: share of the traced window with no operation on the chip, in %.

``1 - busy / window``, busy being the union of the device-op intervals
(``XLA Ops`` line) inside the ``bench.window`` span. On several chips,
the mean over the chips the cell uses.
"""
import numpy as np

import trace_reduce


def read(run):
    trace = getattr(run, "trace", None)
    if trace is None or not run.devices_used:
        return None
    lo, hi = run.trace_window
    busy = [trace_reduce.busy_ns(trace.device_ops[d], lo, hi)
            for d in run.devices_used]
    return float(100.0 * (1.0 - np.mean(busy) / (hi - lo)))
