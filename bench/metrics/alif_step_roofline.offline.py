"""Kernel (``kernels/fused_step.py``, ``fused_step_alif``): share of the
roofline, in %.

The least time the chip could take for the window's calls (the larger
of operations over the int8 peak and bytes over the HBM peak, with the
work from ``alif_work.call_work`` on the network's own shapes, per
chip) divided by the ALIF kernel's device time in the trace, summed
over the chips. ``None`` where the trace holds no ``fused_step_alif``.
"""
import alif_work
import work


def read(run):
    ns = alif_work.kernel_ns(run)
    if ns <= 0 or run.peak is None:
        return None
    lo, hi = run.trace_window
    calls = [s for s in run.trace.spans_named("bench.engine_call")
             if lo <= s[1] and s[2] <= hi]
    if not calls:
        return None
    net = run.net
    ops, nbytes = alif_work.call_work(
        n_synapses=net.n_synapses, n_inputs=net.n_inputs,
        n_neurons=net.n_neurons, rows=run.rows_per_call // run.chips,
        timesteps=run.timesteps, weight_bits=net.weight_bits)
    bound_s, _ = work.roofline_bound_s(ops, nbytes, run.peak)
    return 100.0 * bound_s * len(calls) * run.chips / (ns / 1e9)
