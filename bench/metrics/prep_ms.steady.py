"""Executor input: the front end's stack and pad to the bucket
(``repro.serve.batch``) and the engine's 0/1 validation and int32 cast
(``repro.engine.prepare``), in ms per engine call (program spans)."""
import program_spans


def read(run):
    if run.kind != "open_loop":
        return None
    spans = program_spans.per_call(run)
    if spans is None:
        return None
    return spans.ms("repro.serve.batch", "repro.engine.prepare")
