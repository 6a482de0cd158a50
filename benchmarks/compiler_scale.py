"""Compiler-scale benchmark: hypergraph mapping quality + multilevel
compile cost (DESIGN.md §11).

Two claim groups:

* ``mapping.*`` — the ``hypergraph`` strategy vs the paper's framework
  heuristic on the fig13 SHD shape (the ROADMAP acceptance bar): OT
  depth under the best registered schedule strategy, and the static
  multicast packet cost of the mapping (total destination-SPU count
  over all fan-out hyperedges — the MC-tree deliveries one spike of
  every source costs). ``mapping.hypergraph.beats_paper`` is 1.0 when
  the hypergraph mapping wins on OT depth OR packets.

* ``compiler_scale.*`` — wall-clock compile seconds and peak RSS of a
  ``method="multilevel"`` + ``compile(n_chips=4)`` compile at a PINNED
  10⁵-synapse synthetic shape (``repro.core.scale``), measured in a
  fresh subprocess so ``ru_maxrss`` reflects this compile alone, not
  whatever benchmark ran before in the smoke process. Full (non-quick)
  mode sweeps additional sizes.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

_ROWS_TAG = "COMPILER_SCALE_ROWS_JSON:"
PINNED = dict(n_synapses=100_000, topology="mixed", skew=1.0, seed=0,
              n_chips=4, spus_per_chip=16)
#: (n_synapses, n_chips) sweep for full (non-quick) mode; the last entry
#: is the §12 million-synapse 4x4-mesh acceptance point
FULL_SWEEP = ((100_000, 4), (300_000, 4), (1_000_000, 16))

# generous soft regression pins for the PINNED 10^5 shape (the tracked
# trajectory point): §12 landed it at ~1.5 s / ~260 MB, so a breach
# means a real regression, not noise
PIN_100K_COMPILE_S = 6.0
PIN_100K_RSS_MB = 900.0
# §12 acceptance envelope for the million-synapse 16-chip compile
PIN_1M_COMPILE_S = 600.0
PIN_1M_RSS_MB = 2048.0


# ---------------------------------------------------------------------------
# Paper-scale mapping quality (in-process; no RSS involved).
# ---------------------------------------------------------------------------

def _best_depth(g, hw, assign) -> int:
    from repro.core.scheduling import (SCHEDULE_STRATEGIES, group_info,
                                       schedule)
    info = group_info(g, assign)
    return min(int(schedule(g, assign, hw, method=name, info=info).depth)
               for name in SCHEDULE_STRATEGIES)


def _quality_rows(quick: bool) -> list[tuple]:
    from benchmarks.partitioner_throughput import fig13_shd_instance
    from repro.core.mapping.hypergraph import (hypergraph_partition,
                                               mapping_traffic)
    from repro.core.mapping.search import framework_partition

    g, hw = fig13_shd_instance()
    iters = 3000 if quick else 20000
    t0 = time.perf_counter()
    fw, _, _ = framework_partition(g, hw, seed=0, restarts=1,
                                   max_iters=iters)
    fw_s = time.perf_counter() - t0
    # before/after the §12 load-balance pass: traffic-first greedy +
    # refinement concentrate fan-in groups onto few SPUs, which blows up
    # the OT depth (the busiest SPU's op count); balance_loads spreads
    # whole fan-in groups within each chip under Eq. (9)
    raw = hypergraph_partition(g, hw, balance=False)
    t0 = time.perf_counter()
    hg = hypergraph_partition(g, hw)
    hg_s = time.perf_counter() - t0

    fw_ot = _best_depth(g, hw, fw.assign)
    raw_ot = _best_depth(g, hw, raw.assign)
    hg_ot = _best_depth(g, hw, hg.assign)
    fw_pk = mapping_traffic(g, fw.assign, hw)["dests_total"]
    raw_pk = mapping_traffic(g, raw.assign, hw)["dests_total"]
    hg_pk = mapping_traffic(g, hg.assign, hw)["dests_total"]
    beats = float(hg_ot < fw_ot or hg_pk < fw_pk)
    return [
        ("mapping.instance.synapses", g.n_synapses, "fig13 SHD shape"),
        ("mapping.framework.ot_depth", fw_ot,
         f"best schedule strategy, {iters} iters"),
        ("mapping.framework.packets", fw_pk,
         "multicast destination-SPU total"),
        ("mapping.framework.seconds", fw_s, ""),
        ("mapping.hypergraph.unbalanced.ot_depth", raw_ot,
         "balance=False: the pre-§12 depth blowup"),
        ("mapping.hypergraph.unbalanced.packets", raw_pk,
         "multicast destination-SPU total"),
        ("mapping.hypergraph.ot_depth", hg_ot,
         "best schedule strategy, after balance_loads"),
        ("mapping.hypergraph.packets", hg_pk,
         "multicast destination-SPU total (depth-vs-packets tradeoff)"),
        ("mapping.hypergraph.seconds", hg_s, ""),
        ("mapping.hypergraph.beats_paper", beats,
         "acceptance: wins OT depth OR packets vs framework"),
    ]


# ---------------------------------------------------------------------------
# Scale compile (child measures; parent re-execs for a clean ru_maxrss).
# ---------------------------------------------------------------------------

def _scale_tag(n_synapses: int) -> str:
    return ("compiler_scale.1m" if n_synapses == 1_000_000
            else f"compiler_scale.{n_synapses // 1000}k")


def _measure_scale(n_synapses: int, topology: str, skew: float, seed: int,
                   n_chips: int, spus_per_chip: int) -> list[tuple]:
    import dataclasses
    import resource

    from repro.core import compile as compile_program
    from repro.core.mapping.hypergraph import mapping_traffic
    from repro.core.mapping.multilevel import multilevel_partition
    from repro.core.scale import scale_hw, synthetic_graph

    g = synthetic_graph(n_synapses, topology=topology, skew=skew, seed=seed)
    hw_all = scale_hw(g, n_chips=n_chips, spus_per_chip=spus_per_chip)
    # per-chip description; compile(n_chips=) replicates it (the API the
    # subsystem ships — exercise it rather than a pre-flattened config)
    hw1 = dataclasses.replace(hw_all, n_spus=hw_all.spus_per_chip, n_chips=1)
    t0 = time.perf_counter()
    prog = compile_program(g, hw1, method="multilevel", n_chips=n_chips,
                           validate=True)
    compile_s = time.perf_counter() - t0
    peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    traffic = mapping_traffic(g, prog.tables.assign, prog.hw)
    hop = prog.hw.inter_chip_hop_cycles
    tag = _scale_tag(n_synapses)
    mx, my = prog.hw.mesh_dims
    rows = [
        (f"{tag}.synapses", g.n_synapses, f"{topology}, skew={skew}"),
        (f"{tag}.compile_s", compile_s,
         f"multilevel, n_chips={n_chips}, validated schedule"),
        (f"{tag}.peak_rss_mb", peak_mb, "subprocess ru_maxrss"),
        (f"{tag}.feasible", float(prog.feasible), "Eq. (9) on every SPU"),
        (f"{tag}.ot_depth", int(prog.ot_depth), ""),
        (f"{tag}.packets", traffic["dests_total"],
         "multicast destination-SPU total"),
        (f"{tag}.inter_chip_total", traffic["inter_chip_total"],
         "forwarded packets if every source fired once"),
        (f"{tag}.mesh_hops_total", traffic["mesh_hops_total"],
         f"XY bounding-box hops on the {mx}x{my} mesh (DESIGN.md §12)"),
    ]
    # per-phase compile profile (§12): where the wall time went
    for name, secs in (prog.report.phase_seconds or {}).items():
        rows.append((f"{tag}.phase_s.{name}", secs, "compile-phase profiler"))
    # regression pins: generous soft thresholds on the tracked shapes
    if n_synapses == PINNED["n_synapses"]:
        assert compile_s < PIN_100K_COMPILE_S, \
            f"100k compile regressed: {compile_s:.2f}s >= {PIN_100K_COMPILE_S}"
        assert peak_mb < PIN_100K_RSS_MB, \
            f"100k compile RSS regressed: {peak_mb:.0f}MB >= {PIN_100K_RSS_MB}"
    if n_synapses == 1_000_000:
        assert prog.feasible, "1m acceptance shape went infeasible"
        assert compile_s < PIN_1M_COMPILE_S and peak_mb < PIN_1M_RSS_MB, \
            f"1m envelope breached: {compile_s:.1f}s / {peak_mb:.0f}MB"
    # mesh-vs-chain counterfactual at the acceptance shape: the same
    # pipeline with the placement stage disabled (§11 consecutive-id
    # chain overlay), compared on hop-weighted static traffic
    if n_synapses == PINNED["n_synapses"]:
        chain = multilevel_partition(g, prog.hw, chip_placement=False)
        tc = mapping_traffic(g, chain.assign, prog.hw)
        placed_cost = traffic["dests_total"] + hop * traffic["mesh_hops_total"]
        chain_cost = tc["dests_total"] + hop * tc["mesh_hops_total"]
        rows += [
            (f"{tag}.hopweighted.placed", placed_cost,
             "dests + hop_cycles * mesh hops, placement on"),
            (f"{tag}.hopweighted.chain", chain_cost,
             "chip_placement=False counterfactual"),
            (f"{tag}.mesh_beats_chain", float(placed_cost <= chain_cost),
             "acceptance: placement never loses to the chain overlay"),
        ]
    return rows


def _scale_rows_subprocess(n_synapses: int, n_chips: int) -> list[tuple]:
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [str(root / "src"), env.get("PYTHONPATH")] if p)
    # the child only maps on the host; on a TPU machine the parent may
    # already hold the chip, which a second process cannot open
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "benchmarks.compiler_scale", "--emit-json",
           "--synapses", str(n_synapses), "--chips", str(n_chips)]
    proc = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=1800)
    payload = None
    for line in proc.stdout.splitlines():
        if line.startswith(_ROWS_TAG):
            payload = json.loads(line[len(_ROWS_TAG):])
    if proc.returncode != 0 or payload is None:
        raise RuntimeError(
            f"compiler_scale subprocess failed (rc={proc.returncode}):\n"
            f"{proc.stdout}\n{proc.stderr}")
    return [tuple(row) for row in payload]


def run(quick: bool = False) -> list[tuple]:
    rows = _quality_rows(quick)
    # the pinned 1e5 shape always runs (the tracked trajectory point);
    # full mode sweeps the larger sizes up to the 10^6 acceptance point
    sweep = (((PINNED["n_synapses"], PINNED["n_chips"]),) if quick
             else FULL_SWEEP)
    for n, chips in sweep:
        rows += _scale_rows_subprocess(n, chips)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--emit-json", action="store_true")
    ap.add_argument("--synapses", type=int,
                    default=PINNED["n_synapses"])
    ap.add_argument("--chips", type=int, default=PINNED["n_chips"])
    args = ap.parse_args()
    out = _measure_scale(args.synapses, PINNED["topology"], PINNED["skew"],
                         PINNED["seed"], args.chips,
                         PINNED["spus_per_chip"])
    if args.emit_json:
        print(_ROWS_TAG + json.dumps(out))
    else:
        for name, value, derived in out:
            print(f"{name},{value},{derived}")
