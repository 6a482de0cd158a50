"""Kernel (``kernels/fused_step.py``): share of the roofline, in %.

The least time the chip could take for the window's calls (the larger
of operations over the int8 peak and bytes over the HBM peak, with the
work from ``work.call_work`` on the network's own shapes, per chip)
divided by the fused step's device time in the trace, summed over the
chips. Which peak binds is the larger term: the int8 one for these
networks (see PERF.md).
"""
import trace_reduce
import work

KERNEL_EVENT = r'custom_call_target="tpu_custom_call"'


def read(run):
    trace = getattr(run, "trace", None)
    if trace is None or run.kind != "back_to_back" or run.peak is None:
        return None
    lo, hi = run.trace_window
    kernel_ns = sum(trace_reduce.matching_ns(
        trace_reduce.clip(trace.device_ops[d], lo, hi), KERNEL_EVENT)[0]
        for d in run.devices_used)
    calls = [s for s in trace.spans_named("bench.engine_call")
             if lo <= s[1] and s[2] <= hi]
    if kernel_ns <= 0 or not calls:
        return None
    net = run.net
    ops, nbytes = work.call_work(
        n_synapses=net.n_synapses, n_inputs=net.n_inputs,
        n_neurons=net.n_neurons, rows=run.rows_per_call // run.chips,
        timesteps=run.timesteps, weight_bits=net.weight_bits)
    bound_s, _ = work.roofline_bound_s(ops, nbytes, run.peak)
    return 100.0 * bound_s * len(calls) * run.chips / (kernel_ns / 1e9)
