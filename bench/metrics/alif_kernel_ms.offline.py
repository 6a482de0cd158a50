"""Kernel (``kernels/fused_step.py``, ``fused_step_alif``): device time
of the ALIF kernel per engine call, in ms, from the profiler trace.

The ``fused_step_alif`` events in the traced window (``alif_work``),
over the engine calls (``bench.engine_call`` spans) that began in it;
``None`` where the trace holds no such kernel.
"""
import alif_work


def read(run):
    ns = alif_work.kernel_ns(run)
    if ns <= 0:
        return None
    lo, hi = run.trace_window
    calls = [s for s in run.trace.spans_named("bench.engine_call")
             if lo <= s[1] < hi]
    if not calls:
        return None
    return ns / len(calls) / 1e6
