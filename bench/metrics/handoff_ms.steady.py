"""Front end: the executor round trip (``repro.serve.engine``) less
the engine call inside it (``repro.engine.run``): the hop to the
executor thread and back and the event loop's delay in resuming the
worker, in ms per engine call (program spans)."""
import program_spans


def read(run):
    if run.kind != "open_loop":
        return None
    spans = program_spans.per_call(run)
    if spans is None:
        return None
    return spans.ms("repro.serve.engine")
