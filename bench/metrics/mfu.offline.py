"""Whole step: the window's synaptic operations over the chips' int8
peak, in %.

``frames_per_s * 2 * n_synapses / (chips * int8 peak)``: every
timestep-frame needs one multiply and one add per synapse.
"""


def read(run):
    fps = getattr(run, "frames_per_s", None)
    if fps is None or run.peak is None:
        return None
    ops = fps * 2.0 * run.net.n_synapses
    return 100.0 * ops / (run.chips * run.peak["int8_ops_per_s"])
