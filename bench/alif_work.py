"""The per-neuron (ALIF) kernel in a device trace, and its work.

The kernel is ``fused_step_alif`` (``kernels/fused_step.py``), the
``pallas_call`` a program with per-neuron or adaptive Neuron Unit
parameters runs each timestep; it shows on the ``XLA Ops`` line as
``%fused_step_alif.<n> = ... custom-call(...)``. A program without it
(every LIF program, and a parent that has no such kernel) reads
nothing.

Work of one call over ``rows`` spike trains, from the network's own
shapes (as ``work.call_work``, plus what the per-neuron Neuron Unit
adds):

* operations: ``2 * n_synapses * rows * timesteps``;
* bytes: the packed weights once (``n_synapses`` at the packed width),
  input and output spikes at one bit, the int32 state ``v`` and
  adaptation ``a`` of every neuron read and written once, and the
  per-neuron parameter vectors (six int32 fields) once.
"""
from __future__ import annotations

import math

import trace_reduce

KERNEL_EVENT = r"^%fused_step_alif[.\d]* = "
N_PARAM_FIELDS = 6           # NeuronParams: two shifts, threshold, reset,
                             # adaptation step, reset mode


def call_work(*, n_synapses: int, n_inputs: int, n_neurons: int,
              rows: int, timesteps: int, weight_bits: int
              ) -> tuple[float, float]:
    """``(operations, bytes)`` of one call of the ALIF network."""
    ops = 2.0 * n_synapses * rows * timesteps
    weight_bytes = n_synapses * math.ceil(weight_bits / 8)
    spike_bytes = rows * timesteps * (n_inputs + n_neurons) / 8
    state_bytes = 2 * 2 * rows * n_neurons * 4          # v and a, r + w
    param_bytes = N_PARAM_FIELDS * n_neurons * 4
    return ops, float(weight_bytes + spike_bytes + state_bytes
                      + param_bytes)


def kernel_ns(run) -> float:
    """Device time of the kernel's events in the traced window, summed
    over the chips used (0 where the trace holds none)."""
    trace = getattr(run, "trace", None)
    if trace is None or run.kind != "back_to_back":
        return 0.0
    lo, hi = run.trace_window
    return sum(trace_reduce.matching_ns(
        trace_reduce.clip(trace.device_ops[d], lo, hi), KERNEL_EVENT)[0]
        for d in run.devices_used)
