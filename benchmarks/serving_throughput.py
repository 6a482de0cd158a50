"""Serving subsystem smoke benchmark: sharded execution + micro-batcher.

Rows (land in BENCH_smoke.json via ``benchmarks.run --smoke``):

* ``serve.sharded.devices``   — devices the measurement ran on
* ``serve.sharded.bit_exact`` — 1.0 iff spikes, v_final AND packet
  counts from the shard_map runner are byte-identical to the
  single-device engine, over a ragged batch that does not divide the
  device count (pad-and-mask path exercised)
* ``serve.sharded.speedup``   — single-device engine time / sharded
  time on the same batch (measured honestly: forced-host CPU devices
  share the physical cores, so expect ~1x in CI; the row tracks the
  trajectory, the acceptance bar is bit_exact)
* ``serve.sharded.dispatch_us`` — per-call overhead of the shard_map
  path at a tiny batch (the reason ``ShardedRunner`` routes
  B < devices x min_shard through the single-device engine)
* ``serve.batcher.p50_ms`` / ``serve.batcher.p99_ms`` — deterministic
  micro-batcher drain under the linear service model
* ``serve.batcher.deterministic`` — 1.0 iff two same-seed drains report
  identical latencies
* ``bench.first_request_ms`` / ``bench.steady_p50_ms`` — median
  genuinely-first request over a few COLD engines after AOT bucket
  precompile vs the p50 of subsequent identical requests; acceptance
  is first <= 2x steady

On a TPU the measurement runs in this process, on the devices JAX
finds: a parent that has touched JAX holds the chip, so a child could
not open it. On any other backend it re-execs itself in a subprocess
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — jax
locks the host device count at first backend init, and the smoke
runner imports other jax-using benchmarks first; the child needs only
the host.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

N_DEVICES = 8
_ROWS_TAG = "SERVING_ROWS_JSON:"


# ---------------------------------------------------------------------------
# Parent entry point: in-process on a chip, else re-exec with the forced
# host device count.
# ---------------------------------------------------------------------------

def run(quick: bool = False) -> list[tuple]:
    import jax
    if jax.default_backend() == "tpu":
        return _measure(quick)
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{N_DEVICES}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [str(root / "src"), env.get("PYTHONPATH")] if p)
    cmd = [sys.executable, "-m", "benchmarks.serving_throughput",
           "--emit-json"] + (["--quick"] if quick else [])
    proc = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=1200)
    payload = None
    for line in proc.stdout.splitlines():
        if line.startswith(_ROWS_TAG):
            payload = json.loads(line[len(_ROWS_TAG):])
    if proc.returncode != 0 or payload is None:
        raise RuntimeError(
            f"serving measurement subprocess failed "
            f"(rc={proc.returncode}):\n{proc.stdout}\n{proc.stderr}")
    return [tuple(row) for row in payload]


# ---------------------------------------------------------------------------
# The measurement (in-process, or the child under the forced count).
# ---------------------------------------------------------------------------

def _timed(fn, repeats: int) -> float:
    fn()                                 # warm the compilation cache
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _measure(quick: bool) -> list[tuple]:
    import jax
    import numpy as np

    from repro.core import HardwareConfig, compile, random_graph
    from repro.core.execution import ExecutionSpec
    from repro.serve import BatchPolicy, MicroBatcher, linear_service_model
    from repro.serve.sharded import ShardedRunner

    n_dev = len(jax.devices())
    rows: list[tuple] = [("serve.sharded.devices", n_dev,
                          f"{jax.devices()[0].platform} devices")]

    g = random_graph(n_inputs=48, n_internal=40, n_synapses=700, seed=0)
    hw = HardwareConfig(
        n_spus=8, unified_mem_depth=4 * (g.n_synapses // 8 + g.n_internal),
        concentration=2, max_neurons=g.n_neurons,
        max_post_neurons=g.n_internal)
    program = compile(g, hw, max_iters=20000)
    runner = program.sharded_runner()

    # -- bit-exactness on a ragged batch (pad-and-mask path) ----------------
    t_steps = 20
    b_ragged = 3 * n_dev + 1
    rng = np.random.default_rng(0)
    ext = (rng.random((b_ragged, t_steps, g.n_inputs)) < 0.3) \
        .astype(np.int32)
    s1, v1, st1 = program.run(ext)                    # single-device engine
    s2, v2, st2 = program.run(ext, ExecutionSpec(mesh="auto"))
    exact = (s1.tobytes() == s2.tobytes() and v1.tobytes() == v2.tobytes()
             and np.array_equal(st1["packet_counts"], st2["packet_counts"]))
    rows.append(("serve.sharded.bit_exact", float(exact),
                 f"spikes+v+packets identical, ragged B={b_ragged} "
                 f"over {n_dev} devices"))

    # -- throughput: one big batch, engine vs sharded runner ----------------
    # 32x the device count: below ~256 samples the per-shard dispatch
    # overhead of forced-host devices dominates and the row under-reports
    b_perf = 32 * n_dev
    ext_p = (rng.random((b_perf, t_steps, g.n_inputs)) < 0.3) \
        .astype(np.int32)
    repeats = 3 if quick else 5
    t_single = _timed(lambda: program.run(ext_p), repeats)
    t_sharded = _timed(lambda: runner.run(ext_p), repeats)
    rows.append(("serve.sharded.speedup", t_single / t_sharded,
                 f"B={b_perf}, single {t_single * 1e3:.1f}ms vs "
                 f"sharded {t_sharded * 1e3:.1f}ms"))

    # -- dispatch overhead: why tiny batches fall back ----------------------
    # min_shard=0 forces the true shard path even at B = n_dev; the
    # delta vs the single-device engine on the same batch is the pure
    # shard_map dispatch cost the B < devices x min_shard fallback saves
    b_small = n_dev
    ext_s = ext[:b_small]
    shard_forced = ShardedRunner(program, min_shard=0)
    t_sh = _timed(lambda: shard_forced.run(ext_s), repeats)
    t_si = _timed(lambda: program.run(ext_s), repeats)
    rows.append(("serve.sharded.dispatch_us", (t_sh - t_si) * 1e6,
                 f"shard_map minus single-device at B={b_small}; "
                 f"ShardedRunner routes smaller batches single-device"))

    # -- cold start: AOT bucket precompile ----------------------------------
    # each JaxMappedEngine below is a FRESH engine on the same artifact
    # (built outside Program's cache), AOT-warmed via precompile — the
    # timed call is that engine's genuinely-first request. A single
    # first request is one sample, so take the median over a few
    # independent cold engines to keep scheduler noise out of the row.
    from repro.core import JaxMappedEngine
    cold = ExecutionSpec(donate=True).resolve()
    policy = BatchPolicy(max_batch=8)
    req = ext[:policy.max_batch]
    firsts, eng = [], None
    for _ in range(3):
        eng = JaxMappedEngine(program.graph, program.lowered, cold)
        eng.precompile(policy.buckets, t_steps)
        t0 = time.perf_counter()
        eng.run(req)
        firsts.append((time.perf_counter() - t0) * 1e3)
    first_ms = float(np.median(firsts))
    steady = []
    for _ in range(10 if quick else 20):
        t0 = time.perf_counter()
        eng.run(req)
        steady.append((time.perf_counter() - t0) * 1e3)
    steady_p50 = float(np.percentile(steady, 50))
    rows.append(("bench.first_request_ms", first_ms,
                 f"median first request over 3 cold AOT-precompiled "
                 f"engines, B={policy.max_batch} T={t_steps}"))
    rows.append(("bench.steady_p50_ms", steady_p50,
                 f"p50 of subsequent identical requests; acceptance: "
                 f"first <= 2x steady"))

    # -- micro-batcher: deterministic drain ---------------------------------
    n_req = 64 if quick else 256
    def drain():
        r = np.random.default_rng(1)
        arrivals = np.cumsum(r.exponential(300.0, n_req))
        # pure queue simulation: with a service model set, engine calls
        # would add nothing to the p50/p99 rows but wall clock
        batcher = MicroBatcher(BatchPolicy(max_batch=8),
                               service_model=linear_service_model())
        return batcher.drain(arrivals)
    res_a, res_b = drain(), drain()
    m = res_a.metrics()
    det = np.array_equal(res_a.latencies_us, res_b.latencies_us)
    rows.append(("serve.batcher.p50_ms", m["p50_ms"],
                 f"{n_req} Poisson requests, linear service model"))
    rows.append(("serve.batcher.p99_ms", m["p99_ms"],
                 f"buckets {dict(sorted(m['buckets'].items()))}"))
    rows.append(("serve.batcher.deterministic", float(det),
                 "two same-seed drains, identical latencies"))
    return rows


def main() -> None:
    quick = "--quick" in sys.argv
    rows = _measure(quick)
    for name, value, derived in rows:
        print(f"{name},{value},{derived}")
    if "--emit-json" in sys.argv:
        print(_ROWS_TAG + json.dumps(rows))


if __name__ == "__main__":
    main()
