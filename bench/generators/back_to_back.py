"""Back to back through ``Program.run``: an offline job.

``batch_per_chip`` rows per chip in one call, the next call as soon as
the last returned, with its outputs on the host. The calls cycle
through a seeded pool of distinct batches.
"""
from __future__ import annotations

import gc
from types import SimpleNamespace

import numpy as np

import harness
import loadgen
from harness import SPAN, clock, span

PARAMS = ("batch_per_chip",)


def batch_rows(run) -> int:
    return int(run.traffic.params["batch_per_chip"]) * run.chips


def pool(run) -> np.ndarray:
    """``[n_batches * batch, T, n_inputs]``: 1,024 rows, two batches at
    least."""
    batch = batch_rows(run)
    return loadgen.spike_pool(max(2, 1024 // batch) * batch, run.timesteps,
                              run.net.n_inputs,
                              float(run.cfg["input_spike_rate"]),
                              run.seeds.inputs)


def back_to_back(call, batches: np.ndarray, seconds: float, keep_calls: int,
                 rng: np.random.Generator, tracer=None) -> SimpleNamespace:
    """Call ``call(batches[i % len(batches)])`` until ``seconds`` have
    passed; keep the outputs of ``keep_calls`` calls drawn uniformly
    (reservoir) from ``rng``."""
    calls: list[tuple[float, float, int]] = []
    kept: list[tuple[int, tuple]] = []
    if tracer is not None:
        tracer.start()
    start = clock()
    with span(SPAN["window"]):
        i = 0
        while True:
            with span(SPAN["assemble"]):
                p = i % len(batches)
                ext = batches[p]
            t0 = clock()
            with span(SPAN["engine"]):
                s, v, stats = call(ext)
            t1 = clock()
            calls.append((t0, t1, int(ext.shape[0])))
            out = (p, (s, v, stats["packet_counts"]))
            if i < keep_calls:
                kept.append(out)
            else:
                j = int(rng.integers(0, i + 1))
                if j < keep_calls:
                    kept[j] = out
            i += 1
            if t1 - start >= seconds:
                break
    trace = tracer.stop() if tracer is not None else None
    return SimpleNamespace(start=start, calls=calls, kept=kept, trace=trace)


def measure(run) -> SimpleNamespace:
    batch = batch_rows(run)
    t0 = clock()
    batches = pool(run)
    batches = batches.reshape(-1, batch, *batches.shape[1:])
    run.phases["inputs"] = clock() - t0
    t0 = clock()
    run.program.precompile([batch], run.timesteps, run.spec)
    run.precompile_s = run.phases["precompile"] = clock() - t0

    def call(ext):
        return run.program.run(ext, run.spec)

    t0 = clock()
    call(batches[0])                       # the timed path, warm
    run.phases["warm"] = clock() - t0
    gc.freeze()                   # set-up's objects: out of every GC pass
    run.watch.on = True
    res = back_to_back(call, batches, run.seconds,
                       max(1, -(-harness.CHECK_ROWS // batch)),
                       np.random.default_rng(run.seeds.sample), run.tracer)
    run.watch.on = False
    run.setup_s = res.start - run.t_process
    window = res.calls[-1][1] - res.start
    run.engine_calls = res.calls
    run.trace = res.trace
    run.rows_per_call = batch
    run.frames_per_s = len(res.calls) * batch * run.timesteps / window
    run.attempted, run.failed = len(res.calls), 0
    inputs = np.concatenate([batches[p] for p, _ in res.kept])
    got = tuple(np.concatenate([np.asarray(o[k]) for _, o in res.kept])
                for k in range(3))
    return SimpleNamespace(e2e={"setup_s": run.setup_s,
                                "frames_per_s": run.frames_per_s},
                           inputs=inputs, got=got)
