"""Neuron Unit state: the bytes of state the engine puts on the device
for a call (``nbytes`` of ``repro.engine.launch``: ``v``, plus the
adaptation ``a`` of a per-neuron program), in MB (10^6 bytes) per
engine call (program counters). ``None`` for a program whose launch
span counts no bytes."""
import program_spans


def read(run):
    if run.kind != "back_to_back":
        return None
    spans = program_spans.per_call(run)
    if spans is None:
        return None
    mb = spans.mb("repro.engine.launch")
    return mb if mb else None
