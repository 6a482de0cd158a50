"""Kernel (``kernels/fused_step.py``): device time of the fused step per
engine call, in ms, from the profiler trace.

Reads the device operations whose name matches ``KERNEL_EVENT`` (the
fused step's ``pallas_call`` as it appears on the ``XLA Ops`` line) in
the traced window, and divides by the engine calls (``bench.engine_call``
spans) that began in it.
"""
import trace_reduce

KERNEL_EVENT = r'custom_call_target="tpu_custom_call"'


def read(run):
    trace = getattr(run, "trace", None)
    if trace is None:
        return None
    lo, hi = run.trace_window
    ns = sum(trace_reduce.matching_ns(
        trace_reduce.clip(trace.device_ops[d], lo, hi), KERNEL_EVENT)[0]
        for d in run.devices_used)
    calls = [s for s in trace.spans_named("bench.engine_call")
             if lo <= s[1] < hi]
    if ns <= 0 or not calls:
        return None
    return ns / len(calls) / 1e6
