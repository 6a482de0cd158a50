"""A whole run of a cell on the CPU: the result line, the refusal to run
without a chip, and cells, traffic and metrics found by name."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench_tiny import REPO, tiny_root
import harness

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


def run(root, cell, trace, seed=2 ** 33 + 17, seconds=0.4):
    return harness.run_cell(root, cell, seed, seconds, trace,
                            t_process=time.perf_counter(),
                            require_tpu=False)


@pytest.mark.parametrize("cell", ["tiny.steady", "tiny.offline"])
@pytest.mark.parametrize("trace", [False, True])
def test_result_line_schema(root, cell, trace, capsys):
    result = run(root, cell, trace)
    harness.report(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[:5] == RESULT_KEYS and list(line)[-1] == "check"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    bench = harness.load_benchmark(root)
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in harness.metrics_for(bench, cell, kind)}
    assert set(line["metrics"]) <= allowed
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert {"host_compile_s", "precompile_s"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == allowed
        assert {"setup_s", {"tiny.steady": "p50_ms",
                            "tiny.offline": "frames_per_s"}[cell]} <= allowed
        assert all(m["value"] > 0 for m in line["metrics"].values())
    # the compared numbers, each beside its limit, end standard error
    tail = err.strip().splitlines()[-len(line["check"]):]
    assert all(t.startswith("check: ") and "(limit 0)" in t for t in tail)


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "mnist.steady", "--seed", "1", "--seconds", "1"],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "not 'tpu'" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for path in bench["paths"]:
        shutil.copytree(REPO / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, *bench["command"][1:], "--workload",
                        "shd.offline", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A new configuration, traffic mix, arrival process and per-layer
    metric take only new files and new entries."""
    root = tiny_root(tmp_path)
    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    cfg["layer_sizes"] = [20, 12, 4]
    (root / "bench/configs/tiny-narrow.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/offline.b4.json").write_text(json.dumps(
        {"generator": "back_to_back", "batch_per_chip": 4}))
    (root / "bench/metrics/rows_per_call.offline.py").write_text(
        "def read(run):\n"
        "    return getattr(run, 'rows_per_call', None)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-narrow", "source": "tests",
                             "file": "bench/configs/tiny-narrow.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"].append({"name": "narrow.b4", "config": "tiny-narrow",
                               "traffic": "offline.b4", "chips": 1,
                               "why": "tests"})
    (root / "bench/arrivals/even.py").write_text(
        "import numpy as np\n"
        "PARAMS = ('rate_rps',)\n"
        "def offsets(params, seconds, seed):\n"
        "    n = max(1, int(params['rate_rps'] * seconds))\n"
        "    return np.arange(n) / params['rate_rps']\n")
    (root / "bench/traffic/steady.even.json").write_text(json.dumps(
        {"generator": "open_loop", "arrivals": "even", "rate_rps": 50.0,
         "max_batch": 2, "max_wait_us": 0}))
    bench["workloads"].append({"name": "narrow.even", "config": "tiny-narrow",
                               "traffic": "steady.even", "chips": 1,
                               "why": "tests"})
    bench["end_to_end"][-1]["workloads"].append("narrow.b4")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.steady" in m.get("workloads", []):
            m["workloads"].append("narrow.even")
    bench["per_layer"].append({"name": "rows_per_call.offline", "unit": "rows",
                               "better": "higher", "source": "host_clock",
                               "layer": "executor", "moves": "frames_per_s",
                               "workloads": ["narrow.b4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    e2e = run(root, "narrow.b4", False)
    assert e2e["correct"] and set(e2e["metrics"]) == {"setup_s",
                                                      "frames_per_s"}
    traced = run(root, "narrow.b4", True)
    assert traced["correct"]
    assert traced["metrics"]["rows_per_call.offline"]["value"] == 4
    even = run(root, "narrow.even", False)
    assert even["correct"] and even["attempted"] == 20
    assert set(even["metrics"]) == {m["name"] for m in harness.metrics_for(
        bench, "narrow.even", "end_to_end")} >= {"setup_s", "p50_ms"}
