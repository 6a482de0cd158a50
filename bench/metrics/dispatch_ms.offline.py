"""Executor transfer: the input's copy to the device, per chip on
several (``repro.engine.upload``), and the state fill and executable
call (``repro.engine.launch``), in ms per engine call (program spans)."""
import program_spans


def read(run):
    if run.kind != "back_to_back":
        return None
    spans = program_spans.per_call(run)
    if spans is None:
        return None
    return spans.ms("repro.engine.upload", "repro.engine.launch")
