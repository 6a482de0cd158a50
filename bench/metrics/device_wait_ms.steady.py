"""Device, as the host waits for it: the host copy of the call's
packet counts, its smallest output, which blocks until the device has
finished (``repro.engine.wait``), in ms per engine call (program
spans)."""
import program_spans


def read(run):
    if run.kind != "open_loop":
        return None
    spans = program_spans.per_call(run)
    if spans is None:
        return None
    return spans.ms("repro.engine.wait")
