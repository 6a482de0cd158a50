"""The work of one call, from the network's own shapes, and the peaks.

Operations and bytes count what the network needs, never what a kernel
happens to move: no tile, no padding, no zero block of a dense plane.
A kernel that skips zero tiles, keeps the time loop on chip or retiles
is read against the same work.

* operations: one multiply and one add per synapse per timestep-frame,
  ``2 * n_synapses * rows * timesteps``;
* bytes: the packed weights once (``n_synapses`` entries at the
  configuration's packed width, one byte up to 8-bit weights), the input
  and output spikes at one bit each, and the int32 membrane state of
  every neuron read and written once.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def call_work(*, n_synapses: int, n_inputs: int, n_neurons: int,
              rows: int, timesteps: int, weight_bits: int
              ) -> tuple[float, float]:
    """``(operations, bytes)`` of one call over ``rows`` spike trains."""
    ops = 2.0 * n_synapses * rows * timesteps
    weight_bytes = n_synapses * math.ceil(weight_bits / 8)
    spike_bytes = rows * timesteps * (n_inputs + n_neurons) / 8
    state_bytes = 2 * rows * n_neurons * 4
    return ops, float(weight_bytes + spike_bytes + state_bytes)


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind == "source":
        kinds = sorted(k for k in table if k != "source")
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {kinds}")
    return table[device_kind]


def roofline_bound_s(ops: float, nbytes: float, peak: dict
                     ) -> tuple[float, str]:
    """Least time the chip could take, and which peak sets it."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "int8") if t_ops >= t_bytes else (t_bytes, "hbm")
