"""Smoke run of the served path on a TPU: compile -> Program -> serve.

    python chip_smoke.py [--seed 0] [--out DIR]
    python chip_smoke.py --chips 4          # the sharded path only

Builds the paper's two networks from ``--seed`` (random weights, no
training, no dataset): the MNIST SFNN 784-116-10 (T=10, 4-bit weights,
``MNIST_HW``) and the SHD SRNN 700-300-20 recurrent (T=100, 7-bit
weights, ``SHD_HW``), through ``init_params -> quantize ->
from_quantized -> compile``. Each program is saved, ``Program.load``-ed
into a ``ProgramRegistry`` with AOT precompile of the serving buckets,
and a seeded Poisson stream is drained through ``MicroBatcher`` in
measured wall-clock mode on the default ``fused`` kernel tier. Every
served request is checked bit for bit against ``run_oracle`` in
spikes, final membrane potentials and packet counts.

``--chips 4`` runs only the data-parallel path: ``ShardedRunner`` over
a 4-device ``data`` mesh on both programs at a ragged batch of 4*8+1,
compared bit for bit with the single-device engine and the oracle.

The timings printed on ``smoke:`` lines are context for this run, not
benchmark numbers. The last line of standard output is one JSON object
``{"ok": true, "device": {...}}``; the script exits non-zero before
any work when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from repro.configs.snn_paper import MNIST_HW, SHD_HW  # noqa: E402
from repro.core import (ExecutionSpec, Program, compile,  # noqa: E402
                        from_quantized, oracle_packet_counts, run_oracle)
from repro.kernels.fused_step import pack_dense  # noqa: E402
from repro.serve import (BatchPolicy, MicroBatcher,  # noqa: E402
                         ProgramRegistry, ShardedRunner)
from repro.snn.models import MNIST_CONFIG, SHD_CONFIG, init_params  # noqa: E402
from repro.snn.quantize import QuantConfig, quantize  # noqa: E402

# name -> (network, hardware, quantization, partitioner iterations,
#          input spike rate); settings of examples/mnist_end_to_end.py
# and examples/shd_srnn.py
PAPER = {
    "mnist": (MNIST_CONFIG, MNIST_HW, QuantConfig(4, 5), 40000, 0.2),
    "shd": (SHD_CONFIG, SHD_HW, QuantConfig(7, 12), 60000, 0.05),
}
POLICY = BatchPolicy(max_batch=8)
N_REQUESTS = 48                         # served per program
SHARDED_CHIPS = 4


def build_program(cfg, hw, qcfg, *, seed: int, max_iters: int) -> Program:
    """Random-weight network -> quantized graph -> compiled Program."""
    params = init_params(cfg, jax.random.PRNGKey(seed))
    return compile(from_quantized(quantize(params, cfg, qcfg)), hw,
                   seed=seed, max_iters=max_iters)


def make_requests(program: Program, n: int, timesteps: int, rate: float,
                  seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Seeded Poisson arrivals (µs) and binary spike trains."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(200.0, n))
    reqs = (rng.random((n, timesteps, program.n_inputs)) < rate)
    return arrivals, reqs.astype(np.int32)


def serve(program: Program, spec: ExecutionSpec, name: str, out_dir: Path,
          arrivals: np.ndarray, reqs: np.ndarray) -> tuple:
    """save -> Program.load into a registry (AOT precompile) -> drain
    through the measured-mode MicroBatcher. Returns the registered
    program, the DrainResult and the set-up seconds."""
    out_dir.mkdir(parents=True, exist_ok=True)
    path = program.save(out_dir / f"{name}.npz")
    registry = ProgramRegistry()
    t0 = time.perf_counter()
    served = registry.load(name, path, precompile=POLICY,
                           timesteps=reqs.shape[1], spec=spec)
    setup_s = time.perf_counter() - t0
    batcher = MicroBatcher(POLICY, runner=registry.runner(name, spec),
                           service_model=None)
    return served, batcher.drain(arrivals, reqs), setup_s


def check_against_oracle(program: Program, reqs: np.ndarray, res) -> int:
    """Every served request bit-exact vs ``run_oracle`` (spikes, v,
    packet counts); returns the number of internal spikes checked."""
    assert res.n_served == len(reqs), (res.n_served, len(reqs))
    spikes, v, pkts = res.outputs
    order = np.flatnonzero(res.served)
    for row, i in enumerate(order):
        s_ref, v_ref = run_oracle(program.graph, reqs[i])
        np.testing.assert_array_equal(spikes[row], s_ref)
        np.testing.assert_array_equal(v[row], v_ref)
        np.testing.assert_array_equal(pkts[row],
                                      oracle_packet_counts(reqs[i], s_ref))
    return int(spikes.sum())


def check_sharded(program: Program, spec: ExecutionSpec, reqs: np.ndarray
                  ) -> tuple[int, list[int]]:
    """The shard path (``min_shard=0``) bit-exact vs the single-device
    engine and the oracle; returns the shard count and the ids of the
    devices holding the output shards."""
    runner = ShardedRunner(program, spec=spec, min_shard=0)
    spikes_dev, _, _ = runner.shard_outputs(reqs)
    devices = sorted({s.device.id for s in spikes_dev.addressable_shards})
    s, v, st = runner.run(reqs)
    s1, v1, st1 = program.engine(runner.spec.single_device()).run(reqs)
    assert s.tobytes() == s1.tobytes() and v.tobytes() == v1.tobytes()
    np.testing.assert_array_equal(st["packet_counts"], st1["packet_counts"])
    for i in range(len(reqs)):
        s_ref, v_ref = run_oracle(program.graph, reqs[i])
        np.testing.assert_array_equal(s[i], s_ref)
        np.testing.assert_array_equal(v[i], v_ref)
    return runner.n_shards, devices


def check_tpu_executable(program: Program, spec: ExecutionSpec,
                         timesteps: int) -> None:
    """The served engine compiled the fused kernel for the chip."""
    resolved = spec.resolve()
    assert resolved.kernel == "fused" and resolved.interpret is False, \
        resolved
    exe = program.engine(spec).executable(POLICY.max_batch, timesteps)
    assert "tpu_custom_call" in exe.as_text()


def _smoke(name: str, **fields) -> None:
    print(f"smoke: {name} " + json.dumps(fields), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=Path,
                    default=ROOT / "chiprun_out" / "chip_smoke")
    ap.add_argument("--chips", type=int, choices=(1, SHARDED_CHIPS),
                    default=1, help=f"{SHARDED_CHIPS}: run only the "
                                    f"sharded path across the chips")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX backend is {backend!r}, not 'tpu'; "
              f"refusing to run", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    _smoke("device", **device)

    spec = ExecutionSpec()
    for name, (cfg, hw, qcfg, iters, rate) in PAPER.items():
        t0 = time.perf_counter()
        program = build_program(cfg, hw, qcfg, seed=args.seed,
                                max_iters=iters)
        build_s = time.perf_counter() - t0
        dense = pack_dense(program.lowered)
        t_steps = cfg.timesteps
        if args.chips == SHARDED_CHIPS:
            batch = SHARDED_CHIPS * POLICY.max_batch + 1
            _, reqs = make_requests(program, batch, t_steps, rate, args.seed)
            n_shards, shard_devices = check_sharded(
                program, ExecutionSpec(mesh="auto"), reqs)
            assert n_shards == SHARDED_CHIPS, n_shards
            assert len(shard_devices) == SHARDED_CHIPS, shard_devices
            _smoke(name, phase="sharded", batch=batch, n_shards=n_shards,
                   shard_devices=shard_devices, bit_exact=True)
            continue
        arrivals, reqs = make_requests(program, N_REQUESTS, t_steps, rate,
                                       args.seed)
        served, res, setup_s = serve(program, spec, name, args.out,
                                     arrivals, reqs)
        check_tpu_executable(served, spec, t_steps)
        n_spikes = check_against_oracle(served, reqs, res)
        assert n_spikes > 0, "no internal spike: the check saw no dynamics"
        wall_ms = np.array([b.service_us for b in res.batches]) / 1e3
        _smoke(name, tier=spec.resolve().kernel,
               plane=f"{dense.weight.shape[0]}x{dense.weight.shape[1]} "
                     f"{dense.dtype}", mxu_operand=dense.operand_dtype,
               synapses=program.n_synapses, timesteps=t_steps,
               requests=res.n_served, batches=len(res.batches),
               internal_spikes=n_spikes, bit_exact=True,
               build_s=build_s,               # init + quantize + compile
               compile_s=program.report.compile_seconds,
               load_precompile_s=setup_s,
               first_request_ms=float(wall_ms[0]),
               wall_ms_p50=float(np.percentile(wall_ms, 50)),
               wall_ms_p99=float(np.percentile(wall_ms, 99)))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
