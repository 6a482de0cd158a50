"""Open loop through the program's front end (``AsyncServer``).

Independent users send on a schedule, whatever the server does: the
traffic file's ``arrivals`` (``bench/arrivals/<name>.py``) gives the due
times, and the server batches under ``BatchPolicy(max_batch,
max_wait_us)`` with its power-of-two buckets. Latency runs from a
request's due time to the caller holding its result.
"""
from __future__ import annotations

import asyncio
import gc
import time
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import numpy as np

import harness
import loadgen
from harness import SPAN, clock, span

PARAMS = ("arrivals", "max_batch", "max_wait_us")
POOL_ROWS = 512            # distinct spike trains a run draws from
LATE_WAIT_S = 60.0         # how long past the close a late answer may take


def pool(run) -> np.ndarray:
    return loadgen.spike_pool(POOL_ROWS, run.timesteps, run.net.n_inputs,
                              float(run.cfg["input_spike_rate"]),
                              run.seeds.inputs)


def bench_server_class():
    from repro.serve.async_server import AsyncServer

    class BenchServer(AsyncServer):
        """The program's front end, with the engine call on the
        benchmark's clock and in a host span."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.engine_calls: list[tuple[float, float, int]] = []

        def _run_engine(self, runner, batch):
            with span(SPAN["engine"]):
                t0 = clock()
                out = runner(batch)
                self.engine_calls.append((t0, clock(), int(batch.shape[0])))
            return out

        def forget_completed(self) -> None:
            """Drop the served results the server keeps for its own
            metrics: each holds a view of its call's whole output
            arrays, so a window would otherwise keep every output."""
            for store in (self._completed, self._completion_ts):
                for kept in store.values():
                    kept.clear()

    return BenchServer


async def send(srv, model: str, inputs: np.ndarray, offsets: np.ndarray,
               pick: np.ndarray, keep: set, tracer=None) -> SimpleNamespace:
    """Send request ``i`` at ``start + offsets[i]``; wait for every
    answer, ``LATE_WAIT_S`` past the close at most."""
    from repro.serve.server import Request
    n = len(offsets)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    stages = np.full((n, 6), np.nan)   # queue, fill, pad, compute, bucket, n
    kept: dict[int, tuple] = {}
    errors: list[str] = []
    completed = [0]

    async def one(i: int) -> None:
        sent[i] = clock()
        try:
            c = await srv.submit(Request(model, inputs[pick[i]], 0.0))
        except Exception as exc:      # counted as failed, never raised
            errors.append(f"{type(exc).__name__}: {exc}")
            return
        done[i] = clock()
        stages[i] = (c.queue_wait_us, c.fill_wait_us, c.pad_us,
                     c.compute_us, c.bucket, c.batch_size)
        if i in keep:
            kept[i] = tuple(np.array(x) for x in c.outputs)
        completed[0] += 1
        if completed[0] % 256 == 0:
            srv.forget_completed()

    if tracer is not None:
        tracer.start()
    tasks: set[asyncio.Task] = set()      # the loop holds tasks weakly
    start = clock() + 0.005
    due = start + offsets
    close = start + (offsets[-1] if n else 0.0)
    with span(SPAN["window"]):
        i = 0
        while i < n:
            delay = due[i] - clock()
            if delay > 0:
                await asyncio.sleep(delay)
                continue
            with span(SPAN["send"]):
                now = clock()
                while i < n and due[i] <= now:
                    task = asyncio.ensure_future(one(i))
                    tasks.add(task)
                    task.add_done_callback(tasks.discard)
                    i += 1
        if tasks:
            await asyncio.wait(set(tasks), timeout=max(close - clock(), 0.0)
                               + LATE_WAIT_S)
    trace = tracer.stop() if tracer is not None else None
    for t in list(tasks):
        t.cancel()
    srv.forget_completed()
    return SimpleNamespace(start=start, due=due, sent=sent, done=done,
                           stages=stages, kept=kept, errors=errors,
                           trace=trace)


def _serve(run, overrides: list[dict], tracer) -> tuple[np.ndarray, list]:
    """Set the server up, warm it, and send one window for each of
    ``overrides``, the traffic's parameters with those changed."""
    from repro.serve import BatchPolicy, ProgramRegistry
    traffic, name = run.traffic, run.cell["config"]
    policy = BatchPolicy(max_batch=int(traffic.params["max_batch"]),
                         max_wait_us=float(traffic.params["max_wait_us"]))
    t0 = clock()
    inputs = pool(run)
    run.phases["inputs"] = clock() - t0
    registry = ProgramRegistry()
    t0 = clock()
    registry.register(name, run.program, precompile=policy,
                      timesteps=run.timesteps, spec=run.spec)
    run.precompile_s = run.phases["precompile"] = clock() - t0
    runner = registry.runner(name, run.spec)
    server = bench_server_class()(registry, policy=policy, spec=run.spec,
                                  clock=lambda: clock() * 1e6)
    rng = np.random.default_rng(run.seeds.sample)

    async def serve():
        # the server runs the engine on the loop's default executor:
        # one thread, warmed on every bucket before the window (a
        # thread's first engine call costs ~0.1 s on the chip)
        loop = asyncio.get_running_loop()
        loop.set_default_executor(ThreadPoolExecutor(max_workers=1))
        t0 = clock()
        for b in policy.buckets:
            await loop.run_in_executor(None, runner, inputs[:b])
        run.phases["warm"] = clock() - t0
        out = []
        async with server:
            for override in overrides:
                params = {**traffic.params, **override}
                offsets = traffic.arrivals.offsets(params, run.seconds,
                                                   run.seeds.arrivals)
                pick = rng.integers(0, len(inputs), len(offsets))
                keep = set(rng.choice(len(offsets),
                                      min(harness.CHECK_ROWS, len(offsets)),
                                      replace=False).tolist())
                run.watch.on = True
                cpu0 = time.process_time()
                res = await send(server, name, inputs, offsets, pick, keep,
                                 tracer)
                res.cpu_s = time.process_time() - cpu0
                run.watch.on = False
                res.pick = pick
                out.append(res)
        return out

    gc.freeze()                   # set-up's objects: out of every GC pass
    results = asyncio.run(serve())
    run.engine_calls = server.engine_calls
    return inputs, results


def measure(run) -> SimpleNamespace:
    """One window of the traffic file's arrivals."""
    inputs, (res,) = _serve(run, [{}], run.tracer)
    run.setup_s = res.start - run.t_process
    lat = (res.done - res.due) * 1e3
    ok = ~np.isnan(lat)
    run.requests = res
    run.trace = res.trace
    run.attempted, run.failed = len(lat), int((~ok).sum())
    e2e = {"setup_s": run.setup_s}
    if ok.any():
        e2e["p50_ms"], e2e["p95_ms"] = (float(x) for x in
                                        np.percentile(lat[ok], [50, 95]))
    rows = sorted(res.kept)
    got = tuple(np.stack([res.kept[i][k] for i in rows]) if rows
                else np.zeros((0,)) for k in range(3))
    return SimpleNamespace(e2e=e2e, inputs=inputs[res.pick[rows]], got=got)


def sweep(run, rates: list[float]) -> list[dict]:
    """One set-up, then a window at each offered rate: the table a
    cell's rate is chosen from."""
    _, results = _serve(run, [{"rate_rps": r} for r in rates], None)
    return [sweep_row(r, run.seconds, res) for r, res in zip(rates, results)]


def sweep_row(rate: float, seconds: float, res) -> dict:
    close = res.start + seconds
    lat = (res.done - res.due) * 1e3
    ok = ~np.isnan(lat)
    sent_by_close = int((res.sent <= close).sum())
    done_by_close = int((res.done <= close).sum())
    p50, p95 = (np.percentile(lat[ok], [50, 95]) if ok.any()
                else (np.nan, np.nan))
    return {"offered_rps": len(res.due) / seconds,
            "completed_rps": done_by_close / seconds,
            "backlog_at_close": sent_by_close - done_by_close,
            "p50_ms": float(p50), "p95_ms": float(p95),
            "failed": int((~ok).sum()),
            "gen_late_p95_ms": float(np.nanpercentile(
                (res.sent - res.due) * 1e3, 95)),
            "host_cpu_us_per_request": res.cpu_s / len(res.due) * 1e6}
