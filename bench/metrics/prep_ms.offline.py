"""Executor input: the engine's 0/1 validation, int32 cast and, on
several chips, pad to the shard multiple (``repro.engine.prepare``), in
ms per engine call (program spans)."""
import program_spans


def read(run):
    if run.kind != "back_to_back":
        return None
    spans = program_spans.per_call(run)
    if spans is None:
        return None
    return spans.ms("repro.engine.prepare")
