"""Executor transfer: bytes copied host to device and back
(``nbytes`` of ``repro.engine.upload``, and of ``repro.engine.wait`` and
``repro.engine.download``, which copy the outputs back), in MB (10^6
bytes) per engine call (program counters)."""
import program_spans


def read(run):
    if run.kind != "back_to_back":
        return None
    spans = program_spans.per_call(run)
    if spans is None:
        return None
    return spans.mb("repro.engine.upload", "repro.engine.wait",
                    "repro.engine.download")
