"""Run one cell of the benchmark: build from the seed, measure, check.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* the configuration's file (its ``file`` entry); the ``"model"`` it
  names has its plain reference ``bench/configs/<model>.py``, which
  draws the network from the seed, and its build
  ``bench/programs/<model>.py``, which hands that network to the
  program;
* the traffic file ``bench/traffic/<traffic>.json``, whose
  ``generator`` is ``bench/generators/<name>.py`` and whose
  ``arrivals``, where it has them, are ``bench/arrivals/<name>.py``;
  each declares the parameters it reads (``PARAMS``), and a traffic
  file holds exactly those;
* each per-layer metric's reader ``bench/metrics/<metric>.py``, whose
  ``read(run)`` returns a number, or ``None`` where it finds nothing.

A run builds the network from ``--seed``, compiles it through the
program's normal path, lets the generator warm every shape and measure
one window, and compares a sample of what the timed path produced with
the reference.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

import checking
import trace_reduce
import work

CHECK_ROWS = 256           # rows compared with the reference per run
SPAN = {"window": "bench.window", "send": "bench.send",
        "engine": "bench.engine_call", "assemble": "bench.assemble",
        "check": "bench.check"}


class BenchError(RuntimeError):
    """A run that cannot be made: the caller exits non-zero and prints
    no result."""


def clock() -> float:
    return time.perf_counter()


# -- finding things by name ---------------------------------------------------

def load_benchmark(root: Path) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path}")
    return json.loads(path.read_text())


def find_cell(bench: dict, name: str) -> tuple[dict, dict]:
    """The workload entry called ``name`` and its configuration entry."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise BenchError(f"workload {name!r} names unknown config "
                         f"{cell['config']!r}")
    return cell, configs[cell["config"]]


def load_module(path: Path):
    """Import a benchmark file by path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"no {path}")
    modname = "bench_file_" + "".join(
        c if c.isalnum() else "_" for c in str(path.resolve()))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_config(root: Path, entry: dict) -> dict:
    path = root / entry["file"]
    if not path.is_file():
        raise BenchError(f"config {entry['name']!r}: no {path}")
    return json.loads(path.read_text())


def load_traffic(root: Path, name: str) -> SimpleNamespace:
    """The traffic file ``name`` with its generator and arrivals
    modules; refuses a file that lacks a parameter they read or holds
    one they do not."""
    path = root / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"traffic {name!r}: no {path}")
    params = json.loads(path.read_text())
    if "generator" not in params:
        raise BenchError(f"traffic {name!r} names no generator")
    gen = load_module(root / "bench" / "generators"
                      / f"{params['generator']}.py")
    need = {"generator", *gen.PARAMS}
    arrivals = None
    if "arrivals" in gen.PARAMS and "arrivals" in params:
        arrivals = load_module(root / "bench" / "arrivals"
                               / f"{params['arrivals']}.py")
        need |= set(arrivals.PARAMS)
    missing, unread = need - set(params), set(params) - need
    if missing or unread:
        raise BenchError(f"traffic {name!r}: lacks {sorted(missing)}, "
                         f"holds unread {sorted(unread)}")
    return SimpleNamespace(name=name, params=params, generator=gen,
                           arrivals=arrivals)


def reference_module(root: Path, cfg: dict):
    return load_module(root / "bench" / "configs" / f"{cfg['model']}.py")


def program_module(root: Path, cfg: dict):
    return load_module(root / "bench" / "programs" / f"{cfg['model']}.py")


def metrics_for(bench: dict, cell_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and m["moves"] in names]


def derived_seeds(seed: int) -> SimpleNamespace:
    """Independent streams for each use of ``--seed`` (any size)."""
    kids = np.random.SeedSequence(seed % 2 ** 128).spawn(5)
    w, c, i, a, s = (int(k.generate_state(1)[0]) for k in kids)
    return SimpleNamespace(weights=w, compile=c % 2 ** 31, inputs=i,
                           arrivals=a, sample=s)


# -- the device ---------------------------------------------------------------

def find_devices(chips: int, require_tpu: bool) -> list:
    import jax
    backend = jax.default_backend()
    if require_tpu and backend != "tpu":
        raise BenchError(f"JAX backend is {backend!r}, not 'tpu'; the "
                         f"benchmark runs only on the chip")
    devices = jax.devices()
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices


def device_record(devices: list, chips: int) -> dict:
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class WindowWatch:
    """XLA backend compiles and garbage-collection pauses while ``on``."""

    def __init__(self):
        import jax
        self.on, self.compiles = False, 0
        self.gc_pauses: list[tuple[int, float]] = []   # (generation, s)
        self._gc_t0 = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        gc.callbacks.append(self._gc)

    def _event(self, name, secs, **kw):
        if self.on and name.endswith("backend_compile_duration"):
            self.compiles += 1

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = clock()
        elif self.on:
            self.gc_pauses.append((info["generation"], clock() - self._gc_t0))

    def gc_summary(self) -> dict:
        ms = [s * 1e3 for _, s in self.gc_pauses]
        return {"collections": len(ms),
                "gen2": sum(g == 2 for g, _ in self.gc_pauses),
                "total_ms": sum(ms), "longest_ms": max(ms, default=0.0)}


# -- the program --------------------------------------------------------------

def execution_spec(chips: int):
    from repro.core import ExecutionSpec
    return None if chips == 1 else ExecutionSpec(mesh="auto")


# -- tracing ------------------------------------------------------------------

class Tracer:
    """``jax.profiler`` around the window, or nothing."""

    def __init__(self, enabled: bool, keep_dir: Path | None = None):
        self.enabled = enabled
        self.keep_dir = keep_dir
        self._tmp = None
        self.dir = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax
        if self.keep_dir is not None:
            self.dir = Path(self.keep_dir)
            self.dir.mkdir(parents=True, exist_ok=True)
        else:
            self._tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
            self.dir = Path(self._tmp.name)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def stop(self):
        if not self.enabled:
            return None
        import jax
        jax.profiler.stop_trace()
        trace = trace_reduce.load(self.dir)
        if self._tmp is not None:
            self._tmp.cleanup()
        return trace


def span(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


# -- the comparison -----------------------------------------------------------

def check_rows(ref, net, inputs: np.ndarray, got: tuple) -> dict:
    """Compare ``got`` with the reference over ``inputs``, in blocks."""
    total = {"rows_wrong": 0, "spikes_wrong": 0, "v_wrong": 0,
             "packets_wrong": 0, "reference_spikes": 0}
    with span(SPAN["check"]):
        for lo in range(0, len(inputs), 64):
            hi = lo + 64
            want = ref.reference(net, inputs[lo:hi])
            part = checking.compare(tuple(g[lo:hi] for g in got), want)
            part["reference_spikes"] = int(want[0].sum())
            for k in total:
                total[k] += part[k]
    return total


# -- one run ------------------------------------------------------------------

def run_cell(root: Path, cell_name: str, seed: int, seconds: float,
             trace: bool, *, t_process: float, require_tpu: bool = True,
             keep_trace: Path | None = None, sweep_rates=None) -> dict:
    """One run of one cell; returns the result line (or, with
    ``sweep_rates``, the sweep's table)."""
    bench = load_benchmark(root)
    cell, entry = find_cell(bench, cell_name)
    cfg = load_config(root, entry)
    traffic = load_traffic(root, cell["traffic"])
    ref = reference_module(root, cfg)
    chips = int(cell["chips"])
    devices = find_devices(chips, require_tpu)
    peak = work.peaks(devices[0].device_kind) if require_tpu else None
    run = SimpleNamespace(cell=cell, cfg=cfg, traffic=traffic, chips=chips,
                          timesteps=int(cfg["timesteps"]), peak=peak,
                          kind=traffic.params["generator"],
                          seeds=derived_seeds(seed), seconds=seconds,
                          t_process=t_process, spec=execution_spec(chips),
                          watch=WindowWatch(), tracer=Tracer(trace, keep_trace),
                          phases={"to_devices": clock() - t_process})

    t0 = clock()
    run.net = net = ref.make_network(cfg, run.seeds.weights)
    run.phases["network"] = clock() - t0
    t0 = clock()
    run.program = program_module(root, cfg).build(cfg, net,
                                                  run.seeds.compile)
    run.phases["build_program"] = clock() - t0
    run.compile_s = run.program.report.compile_seconds

    if sweep_rates:
        if not hasattr(traffic.generator, "sweep"):
            raise BenchError(f"generator {run.kind!r} has no sweep")
        return {"sweep": traffic.generator.sweep(run, sweep_rates)}
    out = traffic.generator.measure(run)
    device = device_record(devices, chips)

    numbers = check_rows(ref, net, out.inputs, out.got)
    numbers["requests_failed"] = run.failed
    correct, table = checking.verdict(numbers)
    # a check that saw no row, or no spike, has shown nothing
    correct = (correct and len(out.inputs) > 0
               and numbers["reference_spikes"] > 0)
    info = {"setup_phases_s": run.phases, "rows_checked": int(len(out.inputs)),
            "reference_spikes": numbers["reference_spikes"],
            "compiles_in_window": run.watch.compiles,
            "gc_in_window": run.watch.gc_summary(),
            "longest_call_ms": max((t1 - t0 for t0, t1, _ in run.engine_calls),
                                   default=0.0) * 1e3}

    result = {"correct": bool(correct), "attempted": int(run.attempted),
              "failed": int(run.failed)}
    if trace:
        metrics, breakdown = per_layer(root, bench, cell_name, run, device)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = breakdown
        info["longest_gaps_at_s"] = run.gaps_at_s
    else:
        result["metrics"] = {m["name"]: {"value": out.e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in metrics_for(bench, cell_name,
                                                  "end_to_end")
                             if m["name"] in out.e2e}
        result["device"] = device
    result["check"] = table
    result["_info"] = info
    return result


def per_layer(root: Path, bench: dict, cell_name: str, run, device: dict
              ) -> tuple[dict, dict]:
    """The cell's per-layer metrics, ``device.busy_s``/``window_s`` and
    the breakdown, from the traced run."""
    trace = run.trace
    lo, hi = trace.window()
    used = sorted(trace.device_ops)[:run.chips]
    run.trace_window = (lo, hi)
    run.devices_used = used
    busy = [trace_reduce.busy_ns(trace.device_ops[d], lo, hi) for d in used]
    device["busy_s"] = float(np.mean(busy)) / 1e9 if busy else 0.0
    device["window_s"] = (hi - lo) / 1e9
    metrics = {}
    for m in metrics_for(bench, cell_name, "per_layer"):
        reader = load_module(root / "bench" / "metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    ops = [e for d in used for e in trace_reduce.clip(trace.device_ops[d],
                                                      lo, hi)]
    gaps = [g for d in used
            for g in trace_reduce.idle_gaps(trace.device_ops[d], lo, hi)]
    breakdown = {"device_ops": trace_reduce.top_ops(ops),
                 "idle_gaps": trace_reduce.longest_gaps(gaps, trace.spans)}
    # where in the window the three longest gaps began (s), for stderr
    run.gaps_at_s = [[(g[0] - lo) / 1e9, (g[1] - g[0]) / 1e9]
                     for g in sorted(gaps, key=lambda g: g[0] - g[1])[:3]]
    return metrics, breakdown


def report(result: dict) -> None:
    """The compared numbers on the last lines of standard error, then
    the result line as the last line of standard output."""
    info = result.pop("_info")
    for k, v in info.items():
        print(f"info: {k} {v}", file=sys.stderr)
    for k, v in result["check"].items():
        print(f"check: {k} {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
