"""SupraSNN execution engine.

Two layers:

1. ``run_mapped`` — a *functional* executor of the mapped program
   (OpTables): simulates Spike Memory set/clear, per-SPU partial-current
   accumulation, ME-tree merging with slot-alignment assertions, and the
   centralized Neuron Unit's integer LIF update. Its outputs must match
   ``run_oracle`` BIT-EXACTLY — the paper's deterministic-commit property.

2. ``CycleModel`` — cycle-accurate timing of the same execution (MC-tree
   distribution phase + 2-cycles/op synaptic phase + ME/NU pipeline drain),
   used for the latency/energy numbers of Tables 2/3 and Figs. 12/13.

``run_mapped`` is the slow, structure-faithful reference; the compiled
batched counterpart lives in :mod:`repro.core.engine_jax` and must stay
bit-exact with it (tests/test_engine_jax.py). Both are normally reached
through the one compiled artifact —
``repro.core.program.Program.run(ext, engine="python"|"jax"|"oracle")``
— which gives all three executors a uniform surface.

Hardware semantics (paper §4.2): spikes generated in timestep t-1 are
distributed at the start of timestep t; external input spikes for timestep
t arrive through the Spike Handler in the same window.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.graph import SNNGraph
from repro.core.memory_model import HardwareConfig
from repro.core.scheduling import NOP, OpTables
from repro.snn.lif import alif_step_int, lif_step_int


def packet_stats(pkt_counts: np.ndarray) -> dict:
    """Per-run stats dict shared by the Python and JAX executors."""
    return {"packet_counts": pkt_counts,
            "mean_packets_per_step": float(pkt_counts.mean())}


def oracle_packet_counts(ext_spikes: np.ndarray, spikes: np.ndarray
                         ) -> np.ndarray:
    """Per-timestep MC packet counts implied by a dense (oracle) run.

    The distribution phase of timestep t carries one packet per neuron
    that fired: external inputs of t plus internal spikes of t-1
    (``run_mapped`` counts exactly this set). Lets the oracle engine of
    :meth:`repro.core.program.Program.run` report the same stats dict as
    the mapped executors.

    Accepts ``[T, n]`` inputs (returning ``[T]`` counts) or batched
    ``[B, T, n]`` (returning ``[B, T]``): one vectorized count + shift
    along the timestep axis, no per-step loop.
    """
    ext = np.asarray(ext_spikes)
    s = np.asarray(spikes)
    if ext.ndim not in (2, 3) or s.ndim != ext.ndim:
        raise ValueError(f"expected matching [T, n] or [B, T, n] arrays; "
                         f"got {ext.shape} and {s.shape}")
    pkts = np.count_nonzero(ext, axis=-1).astype(np.int64)
    pkts[..., 1:] += np.count_nonzero(s[..., :-1, :], axis=-1)
    return pkts


# ---------------------------------------------------------------------------
# Oracle: dense integer LIF with hardware (delayed) semantics.
# ---------------------------------------------------------------------------

def run_oracle(g: SNNGraph, ext_spikes: np.ndarray
               ) -> tuple[np.ndarray, np.ndarray]:
    """Dense reference simulation.

    ext_spikes: [T, n_inputs] binary.
    Returns (spikes [T, n_internal], v_final [n_internal]) int32.
    """
    spikes, v, _ = run_oracle_state(g, ext_spikes)
    return spikes, v


def run_oracle_state(g: SNNGraph, ext_spikes: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray | None]:
    """:func:`run_oracle` with the final adaptation: ``(spikes, v_final,
    a_final)``, ``a_final`` ``None`` for a scalar-LIF graph. Per-neuron
    parameters step through :func:`~repro.snn.lif.alif_step_int`."""
    t_steps = ext_spikes.shape[0]
    n_int = g.n_internal
    # dense weight matrix [n_neurons, n_internal]
    w = np.zeros((g.n_neurons, n_int), np.int64)
    w[g.pre, g.local(g.post)] = g.weight

    lif = g.scalar_lif
    v = np.zeros(n_int, np.int32)
    a = None if lif is not None else np.zeros(n_int, np.int32)
    s_prev = np.zeros(n_int, np.int32)          # internal spikes at t-1
    out = np.zeros((t_steps, n_int), np.int32)
    for t in range(t_steps):
        s_all = np.concatenate([ext_spikes[t].astype(np.int64),
                                s_prev.astype(np.int64)])
        current = (s_all @ w).astype(np.int32)
        if lif is not None:
            v, s = lif_step_int(v, current, lif)
        else:
            v, a, s = alif_step_int(v, a, current, g.lif)
        out[t] = s
        s_prev = s
    return out, v, a


# ---------------------------------------------------------------------------
# Functional executor of the mapped program.
# ---------------------------------------------------------------------------

class MergeAlignmentError(AssertionError):
    pass


def run_mapped(g: SNNGraph, tables: OpTables, ext_spikes: np.ndarray,
               check_alignment: bool = True,
               routing: np.ndarray | None = None
               ) -> tuple[np.ndarray, np.ndarray, dict]:
    """Execute the scheduled program. Returns (spikes, v_final, stats).

    stats carries per-timestep packet counts for the cycle model.
    ``routing`` takes the precomputed MC-tree bitmap (e.g.
    ``program.lowered.routing``) to skip the O(E log E) re-lowering;
    built here when omitted. Its Neuron Unit is the scalar LIF: a graph
    with per-neuron or adaptive parameters is refused.
    """
    if g.scalar_lif is None:
        raise ValueError(
            "the python engine's Neuron Unit is one scalar LIF; this "
            "program has per-neuron or adaptive parameters: run it with "
            "ExecutionSpec(engine='jax', kernel='fused')")
    m, depth = tables.pre.shape
    t_steps = ext_spikes.shape[0]
    n_int = g.n_internal

    # routing bitstrings: bit[i] of neuron q == SPU i holds a synapse from q
    if routing is None:
        routing = np.zeros((g.n_neurons, m), bool)
        routing[g.pre, tables.assign] = True

    spike_mem = np.zeros((m, g.n_neurons), bool)   # per-SPU bitmap SRAM
    partial = np.zeros((m, n_int), np.int64)       # per-SPU partial currents
    v = np.zeros(n_int, np.int32)
    s_prev = np.zeros(n_int, np.int32)
    out = np.zeros((t_steps, n_int), np.int32)
    pkt_counts = np.zeros(t_steps, np.int64)

    pre_l = tables.pre            # [M, depth]
    post_l = tables.post
    w_l = tables.weight
    pe_l = tables.pre_end
    poe_l = tables.post_end

    for t in range(t_steps):
        # ---- distribution phase: MC packets into Spike Memory ----
        fired = np.flatnonzero(np.concatenate(
            [ext_spikes[t].astype(bool),
             s_prev.astype(bool)]))
        pkt_counts[t] = len(fired)
        for q in fired:
            spike_mem[routing[q], q] = True

        # ---- synaptic phase: execute slots; merge in ME tree ----
        for slot in range(depth):
            valid = pre_l[:, slot] != NOP
            if not valid.any():
                continue
            spus = np.flatnonzero(valid)
            pres = pre_l[spus, slot]
            posts = post_l[spus, slot]
            act = spike_mem[spus, pres]
            loc = posts - g.n_inputs
            partial[spus, loc] += np.where(act, w_l[spus, slot], 0)
            # pre_end: clear spike bit for next timestep
            pe = pe_l[spus, slot]
            if pe.any():
                spike_mem[spus[pe], pres[pe]] = False
            # post_end: inject ME packets; bufferless merge = same slot
            poe = poe_l[spus, slot]
            if poe.any():
                inj_posts = posts[poe]
                if check_alignment and len(set(inj_posts.tolist())) != 1:
                    raise MergeAlignmentError(
                        f"t={t} slot={slot}: misaligned posts {inj_posts}")
                q = int(inj_posts[0])
                lq = q - g.n_inputs
                current = int(partial[spus[poe], lq].sum())
                partial[spus[poe], lq] = 0
                # ---- Neuron Unit: integer LIF on this neuron ----
                v_q, s_q = lif_step_int(v[lq:lq + 1],
                                        np.array([current], np.int32),
                                        g.scalar_lif)
                v[lq] = v_q[0]
                if s_q[0]:
                    out[t, lq] = 1
        s_prev = out[t]

    return out, v, packet_stats(pkt_counts)


# ---------------------------------------------------------------------------
# Cycle-accurate timing + energy model.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PowerModel:
    """FPGA power model with constants fitted to paper Table 2 (DESIGN.md §8).

    P_total = static + dynamic;  dynamic = per-SPU switching cost scaled by
    datapath width, plus fabric (trees + Neuron Unit) cost.
    """
    static_w: float = 0.106                    # XC7Z020 static (Table 2)
    spu_dyn_w_per_bit: float = 0.000355        # per SPU per datapath bit
    fabric_dyn_w: float = 0.015

    def total_w(self, hw: HardwareConfig) -> float:
        bits = hw.weight_bits + hw.potential_bits
        return (self.static_w + self.fabric_dyn_w
                + hw.n_spus * bits * self.spu_dyn_w_per_bit)


@dataclasses.dataclass
class CycleReport:
    cycles_total: int
    cycles_distribution: int
    cycles_synaptic: int
    cycles_overhead: int
    latency_us: float
    power_w: float
    energy_mj: float
    energy_per_synapse_nj: float


class CycleModel:
    """Per-timestep cycle counting (see module docstring).

    distribution:  n_packets + 1 (end pkt) + tree_depth (MC pipeline)
    synaptic:      2 * OT_depth  (single-port Unified Memory, §4.4.3)
    drain:         tree_depth (ME adders) + 4 (NU pipeline) + 1 (end pkt)
    """
    NU_PIPELINE = 4

    def __init__(self, hw: HardwareConfig, power: PowerModel | None = None):
        self.hw = hw
        self.power = power or PowerModel()

    def timestep_cycles(self, n_packets: int, ot_depth: int,
                        n_inter_chip: int = 0) -> tuple[int, int, int]:
        d = self.hw.tree_depth
        dist = n_packets + 1 + d \
            + n_inter_chip * self.hw.inter_chip_hop_cycles
        syn = 2 * ot_depth
        drain = d + self.NU_PIPELINE + 1
        return dist, syn, drain

    def run(self, packet_counts: np.ndarray, ot_depth: int,
            n_synapses_total: int,
            inter_chip_counts: np.ndarray | None = None) -> CycleReport:
        """Aggregate one sample's per-timestep packet counts.

        ``packet_counts`` must be 1-D ``[T]``; the per-timestep phase
        costs are affine in the packet count, so the whole run reduces
        to one sum instead of a Python loop. Batched ``[B, T]`` arrays
        are rejected — aggregate per sample (what
        :meth:`repro.core.program.Program.profile` does) rather than
        silently iterating rows.

        ``inter_chip_counts`` takes the per-timestep forwarded-packet
        counts of a multi-chip mapping (DESIGN.md §11; see
        :func:`repro.core.mapping.hypergraph.inter_chip_packet_counts`),
        each charged ``hw.inter_chip_hop_cycles`` distribution cycles.
        Omitted (or all-zero, the ``n_chips=1`` case) the report is
        bit-identical to the single-chip model.
        """
        pkts = np.asarray(packet_counts)
        if pkts.ndim != 1:
            raise ValueError(
                f"packet_counts must be 1-D [T]; got shape {pkts.shape} — "
                f"profile batched runs per sample (Program.profile "
                f"aggregates them)")
        inter = 0
        if inter_chip_counts is not None:
            ic = np.asarray(inter_chip_counts)
            if ic.shape != pkts.shape:
                raise ValueError(
                    f"inter_chip_counts shape {ic.shape} != packet_counts "
                    f"shape {pkts.shape}")
            inter = int(ic.sum()) * self.hw.inter_chip_hop_cycles
        t_steps = len(pkts)
        d = self.hw.tree_depth
        dist = int(pkts.sum()) + t_steps * (1 + d) + inter
        syn = t_steps * 2 * ot_depth
        over = t_steps * (d + self.NU_PIPELINE + 1)
        total = dist + syn + over
        lat_us = total / self.hw.clock_mhz
        p = self.power.total_w(self.hw)
        e_mj = p * lat_us * 1e-3
        eps_nj = (e_mj * 1e6 / n_synapses_total) if n_synapses_total else 0.0
        return CycleReport(total, dist, syn, over, lat_us, p, e_mj, eps_nj)
