"""Executor transfer: the copy of the spikes and final potentials to
the host, gathered from every chip on several, and ``finalize_outputs``
(``repro.engine.download``), in ms per engine call (program spans)."""
import program_spans


def read(run):
    if run.kind != "back_to_back":
        return None
    spans = program_spans.per_call(run)
    if spans is None:
        return None
    return spans.ms("repro.engine.download")
