"""The compiled SupraSNN deployment artifact.

:func:`compile` runs the explicit pass pipeline of
:mod:`repro.core.passes` (partition -> schedule -> validate -> lower)
and returns a :class:`Program`: ONE object owning the graph, the
scheduled :class:`~repro.core.schedule.OpTables`, the dense
:class:`~repro.core.schedule.LoweredProgram`, the
:class:`~repro.core.passes.CompileReport`, and the
:class:`~repro.core.partition.PartitionResult`. Everything the rest of
the repo needs hangs off that artifact:

* ``program.run(ext, spec)`` — uniform ``[T, n_inputs]`` /
  ``[B, T, n_inputs]`` input shapes and a uniform
  ``(spikes, v_final, stats)`` return across all executors; ``spec``
  is an :class:`~repro.core.execution.ExecutionSpec` (or an
  engine-name string ``"jax"|"python"|"oracle"``) naming engine,
  kernel tier, interpret mode, mesh, and donation in ONE value. The
  pre-spec kwargs (``engine=, nu_kernel=, interpret=, sharded=,
  mesh=``) survive as deprecated delegating shims;
* ``program.profile(stats)`` — CycleModel latency + energy and the
  FPGA resource report in one :class:`ProfileReport`;
* ``program.init_packets()`` — the MC-tree configuration stream;
* ``program.save(path)`` / ``Program.load(path)`` — a version-stamped
  npz artifact (JSON header + dense arrays) that round-trips
  bit-exactly, so serving processes NEVER re-run the stochastic
  partitioner.

JAX engines are owned, lazily-built members of the artifact, keyed on
the **resolved** :class:`~repro.core.execution.ExecutionSpec` — there
is no module-level engine cache (the old ``id()``-keyed one could
alias recycled ids and duplicated engines for ``interpret=None`` vs
its resolved value). Building an engine turns the persistent XLA cache
on, and ``program.precompile(buckets, T)`` AOT-compiles the serving
shapes (:mod:`repro.core.aot`), so loaded artifacts serve their first
request without paying XLA.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from pathlib import Path

import numpy as np

from repro.core.cost import ResourceReport
from repro.core.engine import (CycleModel, CycleReport, PowerModel,
                               oracle_packet_counts, packet_stats,
                               run_mapped, run_oracle_state)
from repro.core.engine_jax import JaxMappedEngine
from repro.core.execution import (AUTO_MESH, ENGINES, ExecutionSpec, as_spec,
                                  spec_from_legacy_kwargs)
from repro.core.graph import SNNGraph, from_quantized
from repro.core.memory_model import HardwareConfig
from repro.core.mapping.search import SearchConfig, SearchTrace
from repro.core.partition import PartitionResult
from repro.core.passes import (CompileReport, build_report,
                               initialization_packets, lower_pass,
                               neuron_params_pass, partition_pass,
                               schedule_pass, search_pass, validate_pass)
from repro.core.profiling import current_profiler, phase, profiled
from repro.core.scheduling import LoweredProgram, OpTables
from repro.snn.lif import NeuronParams
from repro.snn.quantize import QuantizedSNN

PROGRAM_FORMAT = "suprasnn-program"
PROGRAM_FORMAT_VERSION = 1
# HardwareConfig fields added after format v1 shipped; serialized only at
# non-default values (so old artifacts and new single-chip ones share the
# same header schema, and v1 readers never see them)
_POST_V1_HW_FIELDS = frozenset({"n_chips", "inter_chip_hop_cycles",
                                "mesh_x", "mesh_y"})


@dataclasses.dataclass
class ProfileReport:
    """One-call profile of a run: timing/energy + hardware resources.

    ``per_sample`` holds one :class:`CycleReport` per batch sample;
    ``cycle`` aggregates them (mean over the batch; equal to
    ``per_sample[0]`` for unbatched runs). The scalar properties
    delegate to the aggregate.
    """
    cycle: CycleReport
    resources: ResourceReport
    per_sample: list[CycleReport]

    @property
    def latency_us(self) -> float:
        return self.cycle.latency_us

    @property
    def power_w(self) -> float:
        return self.cycle.power_w

    @property
    def energy_mj(self) -> float:
        return self.cycle.energy_mj

    @property
    def energy_per_synapse_nj(self) -> float:
        return self.cycle.energy_per_synapse_nj


def _aggregate_cycles(reports: list[CycleReport]) -> CycleReport:
    if len(reports) == 1:
        return reports[0]

    def mean(f):
        return float(np.mean([getattr(r, f) for r in reports]))

    return CycleReport(
        cycles_total=int(round(mean("cycles_total"))),
        cycles_distribution=int(round(mean("cycles_distribution"))),
        cycles_synaptic=int(round(mean("cycles_synaptic"))),
        cycles_overhead=int(round(mean("cycles_overhead"))),
        latency_us=mean("latency_us"), power_w=reports[0].power_w,
        energy_mj=mean("energy_mj"),
        energy_per_synapse_nj=mean("energy_per_synapse_nj"))


@dataclasses.dataclass
class Program:
    """A compiled, runnable, persistable SupraSNN deployment artifact."""
    graph: SNNGraph
    hw: HardwareConfig
    tables: OpTables
    lowered: LoweredProgram
    report: CompileReport
    part: PartitionResult
    default_engine: str = "jax"
    _engines: dict = dataclasses.field(default_factory=dict, repr=False,
                                       compare=False)

    # -- summary properties -------------------------------------------------

    @property
    def feasible(self) -> bool:
        return self.report.feasible

    @property
    def ot_depth(self) -> int:
        return self.tables.depth

    @property
    def n_inputs(self) -> int:
        return self.graph.n_inputs

    @property
    def n_synapses(self) -> int:
        return self.graph.n_synapses

    # -- engines ------------------------------------------------------------

    def engine(self, spec: ExecutionSpec | None = None, *,
               nu_kernel: bool | None = None,
               interpret: bool | None = None) -> JaxMappedEngine:
        """The owned compiled single-device executor for ``spec``.

        The spec is resolved (platform defaults folded in) BEFORE
        keying, so an explicit value and the default it resolves to
        share one engine. Engines build lazily from the
        already-lowered program and live as long as the artifact.
        ``nu_kernel=``/``interpret=`` are the deprecated pre-spec
        kwargs.
        """
        if nu_kernel is not None or interpret is not None:
            if spec is not None:
                raise TypeError("pass spec= OR the deprecated nu_kernel=/"
                                "interpret= kwargs, not both")
            spec = spec_from_legacy_kwargs(
                nu_kernel=nu_kernel, interpret=interpret,
                where="Program.engine", stacklevel=3)
        spec = as_spec(spec).resolve().single_device()
        if spec.engine != "jax":
            raise ValueError(f"Program.engine builds the jax engine; got "
                             f"engine={spec.engine!r}")
        eng = self._engines.get(spec)
        if eng is None:
            from repro.core.aot import enable_persistent_cache
            enable_persistent_cache()       # before this engine compiles
            eng = JaxMappedEngine(self.graph, self.lowered, spec)
            self._engines[spec] = eng
        return eng

    def sharded_runner(self, spec=None, *, nu_kernel: bool | None = None,
                       interpret: bool | None = None):
        """The owned multi-device runner for ``spec``.

        ``spec`` may be an :class:`ExecutionSpec` (``mesh=None`` means
        the default serving mesh here), a bare jax ``Mesh``, or
        ``None`` (default mesh). Wraps the owned engine in
        ``shard_map`` — see :mod:`repro.serve.sharded`. Runners are
        cached like engines: same resolved spec -> same object.
        ``nu_kernel=``/``interpret=`` are the deprecated pre-spec
        kwargs.
        """
        from repro.serve.sharded import ShardedRunner
        mesh = None
        if spec is not None and not isinstance(spec, ExecutionSpec):
            mesh, spec = spec, None         # bare-Mesh convenience form
        if nu_kernel is not None or interpret is not None:
            if spec is not None:
                raise TypeError("pass spec= OR the deprecated nu_kernel=/"
                                "interpret= kwargs, not both")
            spec = spec_from_legacy_kwargs(
                sharded=True, mesh=mesh, nu_kernel=nu_kernel,
                interpret=interpret, where="Program.sharded_runner",
                stacklevel=3)
        elif spec is None:
            spec = ExecutionSpec(mesh=mesh if mesh is not None else AUTO_MESH)
        if spec.mesh is None:
            spec = dataclasses.replace(spec, mesh=AUTO_MESH)
        spec = spec.resolve()
        runner = self._engines.get(spec)
        if runner is None:
            runner = ShardedRunner(self, spec=spec)
            self._engines[spec] = runner
        return runner

    # -- AOT ----------------------------------------------------------------

    def precompile(self, batch_sizes, timesteps: int,
                   spec: ExecutionSpec | None = None) -> list:
        """AOT-compile the jax engine for every serving shape NOW.

        ``batch_sizes`` is a :class:`~repro.serve.batcher.BatchPolicy`
        or an iterable of batch sizes (the padded buckets serving can
        dispatch); ``timesteps`` fixes the T axis. The engine turns the
        persistent XLA cache on (:mod:`repro.core.aot`), so restarted
        processes reuse these compilations from disk. Returns the
        shapes compiled by this call; idempotent per engine.
        """
        from repro.core.aot import normalize_buckets
        spec = as_spec(spec).resolve()
        if spec.engine != "jax":
            raise ValueError(f"precompile targets the jax engine; got "
                             f"engine={spec.engine!r}")
        target = (self.sharded_runner(spec) if spec.sharded
                  else self.engine(spec))
        return target.precompile(normalize_buckets(batch_sizes), timesteps)

    def content_hash(self) -> str:
        """SHA-256 over the lowered program + LIF params — the stable
        identity of the compiled computation (:mod:`repro.core.aot`)."""
        from repro.core.aot import content_hash
        return content_hash(self)

    # -- execution ----------------------------------------------------------

    def run(self, ext_spikes: np.ndarray,
            spec: "ExecutionSpec | str | None" = None, *,
            engine: str | None = None, nu_kernel: bool | None = None,
            interpret: bool | None = None, sharded: bool | None = None,
            mesh=None) -> tuple[np.ndarray, np.ndarray, dict]:
        """Execute the program on a spike train (batch).

        ext_spikes: binary ``[T, n_inputs]`` or ``[B, T, n_inputs]``.
        spec: an :class:`~repro.core.execution.ExecutionSpec`, an
        engine-name string (``"jax"`` compiled batched, ``"python"``
        per-op reference executor, ``"oracle"`` dense integer LIF), or
        ``None`` for ``self.default_engine``. All engines and kernel
        tiers return ``(spikes, v_final, stats)`` with matching shapes
        — ``[T, n_internal]`` / ``[n_internal]`` / packet_counts
        ``[T]``, batched with a leading ``B`` — and identical bits.

        ``ExecutionSpec(mesh=...)`` data-parallelizes the batch axis
        over a jax mesh through the owned
        :class:`~repro.serve.sharded.ShardedRunner` — jax engine only,
        outputs bit-exact vs the single-device run (ragged batches
        pad-and-mask; tiny batches fall back to one device).

        ``engine=/nu_kernel=/interpret=/sharded=/mesh=`` are the
        deprecated pre-spec kwargs and delegate with a
        ``DeprecationWarning`` (see README, 'Migration to
        ExecutionSpec').
        """
        if (engine is not None or nu_kernel is not None
                or interpret is not None or sharded is not None
                or mesh is not None):
            if spec is not None:
                raise TypeError("pass spec OR the deprecated engine=/"
                                "nu_kernel=/interpret=/sharded=/mesh= "
                                "kwargs, not both")
            spec = spec_from_legacy_kwargs(
                engine=engine, nu_kernel=nu_kernel, interpret=interpret,
                sharded=sharded, mesh=mesh,
                default_engine=self.default_engine)
        spec = as_spec(spec, self.default_engine)
        if spec.engine == "jax":
            if spec.mesh is not None:
                return self.sharded_runner(spec).run(ext_spikes)
            return self.engine(spec).run(ext_spikes)

        ext = np.asarray(ext_spikes)
        squeeze = ext.ndim == 2
        if squeeze:
            ext = ext[None]
        if ext.ndim != 3 or ext.shape[2] != self.graph.n_inputs:
            raise ValueError(f"ext_spikes shape {np.shape(ext_spikes)} != "
                             f"[B, T, {self.graph.n_inputs}] or "
                             f"[T, {self.graph.n_inputs}]")

        spikes, vs, pkts, adapt = [], [], [], []
        for b in range(ext.shape[0]):
            e = ext[b].astype(np.int32)
            if spec.engine == "python":
                s, v, st = run_mapped(self.graph, self.tables, e,
                                      routing=self.lowered.routing)
                p = st["packet_counts"]
            else:
                s, v, a = run_oracle_state(self.graph, e)
                p = oracle_packet_counts(e, s)
                adapt.append(a)
            spikes.append(s)
            vs.append(v)
            pkts.append(p)
        s_all = np.stack(spikes)
        v_all = np.stack(vs)
        p_all = np.stack(pkts)
        a_all = (np.stack(adapt) if adapt and adapt[0] is not None
                 else None)
        if squeeze:
            s_all, v_all, p_all = s_all[0], v_all[0], p_all[0]
            a_all = None if a_all is None else a_all[0]
        stats = packet_stats(p_all)
        if a_all is not None:
            stats["adaptation"] = a_all
        return s_all, v_all, stats

    # -- profiling ----------------------------------------------------------

    def profile(self, stats: dict | np.ndarray, *,
                n_synapses: int | None = None,
                power: PowerModel | None = None,
                inter_chip_counts: np.ndarray | None = None
                ) -> ProfileReport:
        """CycleModel timing/energy + resource report in one call.

        ``stats`` is the dict returned by :meth:`run` (or a raw
        packet-counts array, ``[T]`` or ``[B, T]``). ``n_synapses``
        overrides the energy-per-synapse denominator (e.g. the
        pre-pruning synapse count of a quantized model); defaults to
        the mapped graph's nonzero synapses. On a multi-chip target
        pass ``inter_chip_counts`` (same shape as the packet counts;
        see :meth:`inter_chip_counts`) to charge the forwarded packets
        their hop cost — omitted, the profile is the single-chip model.
        """
        pkts = stats["packet_counts"] if isinstance(stats, dict) else stats
        pkts = np.atleast_2d(np.asarray(pkts))
        if inter_chip_counts is None:
            ics = [None] * pkts.shape[0]
        else:
            ic = np.atleast_2d(np.asarray(inter_chip_counts))
            if ic.shape != pkts.shape:
                raise ValueError(f"inter_chip_counts shape {ic.shape} != "
                                 f"packet_counts shape {pkts.shape}")
            ics = list(ic)
        n_syn = self.graph.n_synapses if n_synapses is None else n_synapses
        cm = CycleModel(self.hw, power)
        per = [cm.run(row, self.tables.depth, n_syn, inter_chip_counts=i)
               for row, i in zip(pkts, ics)]
        return ProfileReport(cycle=_aggregate_cycles(per),
                             resources=self.report.resources,
                             per_sample=per)

    # -- multi-chip accounting (DESIGN.md §11) --------------------------------

    def chip_span(self) -> np.ndarray:
        """[n_neurons] distinct chips each neuron's fan-out spans under
        this program's mapping (all-ones/zeros on a single-chip hw)."""
        from repro.core.mapping.hypergraph import chip_span
        return chip_span(self.graph, self.tables.assign, self.hw)

    def mesh_hops(self) -> np.ndarray:
        """[n_neurons] 2D-mesh hop cost of each neuron's multicast under
        this program's mapping (DESIGN.md §12; all zeros on a
        single-chip hw)."""
        from repro.core.mapping.hypergraph import mesh_hops
        return mesh_hops(self.graph, self.tables.assign, self.hw)

    def inter_chip_counts(self, ext_spikes: np.ndarray,
                          spikes: np.ndarray) -> np.ndarray:
        """Per-timestep inter-chip MESH HOPS of a run — the companion of
        the ``packet_counts`` stat, for :meth:`profile`'s
        ``inter_chip_counts=``. Each firing neuron charges the XY-mesh
        bounding-box hop count of its multicast (:meth:`mesh_hops`), so
        the cycle model's ``inter_chip_hop_cycles`` term scales with
        actual mesh distance (DESIGN.md §12; on a two-chip chain this
        is exactly the §11 ``span - 1`` forward count). ``ext_spikes``
        and ``spikes`` are the run's input and output spike trains
        (``[T, n]`` or ``[B, T, n]``). All zeros when ``n_chips == 1``.
        """
        from repro.core.mapping.hypergraph import inter_chip_hop_counts
        return inter_chip_hop_counts(ext_spikes, spikes, self.mesh_hops())

    # -- static verification (DESIGN.md §13) ----------------------------------

    def verify(self, checkers: "list[str] | None" = None):
        """Statically verify the artifact WITHOUT executing any engine.

        Runs the registered analysis checkers of
        :mod:`repro.analysis` — schedule hazards, integer range
        analysis, Eq. 9/11 memory audit — and returns their
        :class:`~repro.analysis.diagnostics.VerifyReport`
        (``report.ok`` iff no ERROR diagnostic). The CLI form is
        ``python -m repro.analysis.verify artifact.npz``.
        """
        from repro.analysis import verify as _verify
        return _verify(self, checkers=checkers)

    # -- initialization stream ----------------------------------------------

    def init_packets(self) -> list[tuple[int, int]]:
        """The MC-tree (ctrl, payload) configuration stream (§4.3)."""
        return initialization_packets(self.graph, self.tables, self.hw,
                                      routing=self.lowered.routing)

    # -- persistence --------------------------------------------------------

    def save(self, path: str | Path) -> Path:
        """Persist the artifact as npz (JSON header + dense arrays).

        Returns the actual file path (``.npz`` appended if missing).
        ``Program.load(path)`` round-trips bit-exactly — the lowered
        program is re-derived deterministically; the partitioner is
        NOT re-run.
        """
        path = Path(path)
        if path.suffix != ".npz":
            path = path.with_name(path.name + ".npz")
        g, hw, rep, part = self.graph, self.hw, self.report, self.part
        res = rep.resources
        header = {
            "format": PROGRAM_FORMAT,
            "version": PROGRAM_FORMAT_VERSION,
            "default_engine": self.default_engine,
            "graph": {
                "n_inputs": int(g.n_inputs),
                "n_neurons": int(g.n_neurons),
                "output_slice": [int(g.output_slice[0]),
                                 int(g.output_slice[1])],
                # a scalar LIF keeps the v1 header bytes; per-neuron
                # parameters are the g_neurons array, one row a field
                "lif": ({"per_neuron": list(NeuronParams._fields)}
                        if isinstance(g.lif, NeuronParams) else
                        {"leak_shift": int(g.lif.leak_shift),
                         "v_threshold": int(g.lif.v_threshold),
                         "v_reset": int(g.lif.v_reset)}),
            },
            # post-v1 HardwareConfig fields are elided at their defaults so
            # single-chip artifacts keep the exact v1 header bytes
            # (tests/test_serving.py golden roundtrip); Program.load fills
            # absent keys from the dataclass defaults
            "hw": {f.name: getattr(hw, f.name)
                   for f in dataclasses.fields(hw)
                   if f.name not in _POST_V1_HW_FIELDS
                   or getattr(hw, f.name) != f.default},
            "report": {
                "method": rep.method,
                "feasible": bool(rep.feasible),
                "iterations": int(rep.iterations),
                "perturbations": int(rep.perturbations),
                "ot_depth": int(rep.ot_depth),
                "n_init_packets": int(rep.n_init_packets),
                "compile_seconds": float(rep.compile_seconds),
                "resources": {"luts": int(res.luts), "ffs": int(res.ffs),
                              "brams": float(res.brams),
                              "memory_kb": float(res.memory_kb)},
                "search": rep.search.to_json() if rep.search else None,
                "candidates_tried": int(rep.candidates_tried),
                "schedule_method": rep.schedule_method,
                "schedule_depths": ({k: int(v) for k, v
                                     in rep.schedule_depths.items()}
                                    if rep.schedule_depths else None),
                # phase profile keys are elided when absent so pre-§12
                # artifacts keep their exact v1 header (golden roundtrip)
                **({"phase_seconds": {k: float(v) for k, v
                                      in rep.phase_seconds.items()}}
                   if rep.phase_seconds else {}),
                **({"phase_alloc_mb": {k: float(v) for k, v
                                       in rep.phase_alloc_mb.items()}}
                   if rep.phase_alloc_mb else {}),
            },
            "part": {
                "feasible": bool(part.feasible),
                "iterations": int(part.iterations),
                "perturbations": int(part.perturbations),
            },
        }
        np.savez_compressed(
            path,
            header=np.asarray(json.dumps(header)),
            g_pre=g.pre, g_post=g.post, g_weight=g.weight,
            t_pre=self.tables.pre, t_post=self.tables.post,
            t_weight=self.tables.weight, t_pre_end=self.tables.pre_end,
            t_post_end=self.tables.post_end, t_assign=self.tables.assign,
            part_assign=part.assign, part_scores=part.scores,
            part_history=np.asarray(part.score_history, np.float64),
            rep_scores=rep.scores,
            rep_spu_synapse_counts=rep.spu_synapse_counts,
            rep_spu_post_counts=rep.spu_post_counts,
            rep_spu_weight_counts=rep.spu_weight_counts,
            **({"g_neurons": np.stack(g.lif)}
               if isinstance(g.lif, NeuronParams) else {}))
        return path

    @classmethod
    def load(cls, path: str | Path, *, precompile=None,
             timesteps: int | None = None,
             spec: ExecutionSpec | None = None) -> "Program":
        """Load a saved artifact; rejects unknown formats/versions.

        ``precompile=`` (a :class:`~repro.serve.batcher.BatchPolicy`
        or iterable of batch buckets, with ``timesteps=`` fixing the T
        axis) AOT-compiles the jax engine for every serving shape at
        load time — see :meth:`precompile` — so the artifact is warm
        before its first request.
        """
        with np.load(path) as z:
            if "header" not in z.files:
                raise ValueError(f"{path}: not a {PROGRAM_FORMAT} artifact")
            header = json.loads(str(z["header"][()]))
            if header.get("format") != PROGRAM_FORMAT:
                raise ValueError(
                    f"{path}: format {header.get('format')!r} != "
                    f"{PROGRAM_FORMAT!r}")
            if header.get("version") != PROGRAM_FORMAT_VERSION:
                raise ValueError(
                    f"{path}: format version {header.get('version')} "
                    f"unsupported (have {PROGRAM_FORMAT_VERSION})")
            arrays = {k: z[k] for k in z.files if k != "header"}

        from repro.snn.lif import LIFIntParams
        gh = header["graph"]
        if "per_neuron" in gh["lif"]:
            lif = NeuronParams(**dict(zip(gh["lif"]["per_neuron"],
                                          arrays["g_neurons"])))
        else:
            lif = LIFIntParams(**gh["lif"])
        g = SNNGraph(
            n_inputs=gh["n_inputs"], n_neurons=gh["n_neurons"],
            pre=arrays["g_pre"], post=arrays["g_post"],
            weight=arrays["g_weight"], lif=lif,
            output_slice=tuple(gh["output_slice"]))
        hw = HardwareConfig(**header["hw"])
        tables = OpTables.from_dense(
            arrays["t_pre"], arrays["t_post"], arrays["t_weight"],
            arrays["t_pre_end"], arrays["t_post_end"], arrays["t_assign"])
        ph = header["part"]
        part = PartitionResult(
            assign=arrays["part_assign"], scores=arrays["part_scores"],
            feasible=ph["feasible"], iterations=ph["iterations"],
            perturbations=ph["perturbations"],
            score_history=arrays["part_history"].tolist())
        rh = header["report"]
        report = CompileReport(
            method=rh["method"], feasible=rh["feasible"],
            iterations=rh["iterations"], perturbations=rh["perturbations"],
            ot_depth=rh["ot_depth"], scores=arrays["rep_scores"],
            spu_synapse_counts=arrays["rep_spu_synapse_counts"],
            spu_post_counts=arrays["rep_spu_post_counts"],
            spu_weight_counts=arrays["rep_spu_weight_counts"],
            resources=ResourceReport(**rh["resources"]),
            n_init_packets=rh["n_init_packets"],
            compile_seconds=rh["compile_seconds"],
            search=(SearchTrace.from_json(rh["search"])
                    if rh.get("search") else None),
            candidates_tried=rh.get("candidates_tried", 1),
            schedule_method=rh.get("schedule_method", "slack"),
            schedule_depths=rh.get("schedule_depths"),
            phase_seconds=rh.get("phase_seconds"),
            phase_alloc_mb=rh.get("phase_alloc_mb"))
        # re-lower (pure, deterministic) — never re-partition
        lowered = lower_pass(g, tables)
        prog = cls(g, hw, tables, lowered, report, part,
                   default_engine=header.get("default_engine", "jax"))
        if precompile is not None:
            if timesteps is None:
                raise ValueError("Program.load(precompile=...) needs "
                                 "timesteps= to fix the T axis of the AOT "
                                 "shapes")
            prog.precompile(precompile, timesteps, spec)
        return prog


# ---------------------------------------------------------------------------
# The compile entry point.
# ---------------------------------------------------------------------------

def compile(g_or_qsnn: SNNGraph | QuantizedSNN, hw: HardwareConfig, *,
            method: str = "framework", engine: str = "jax", seed: int = 0,
            validate: bool = True, max_iters: int = 20000,
            restarts: int = 1, workers: int = 1,
            schedule_method: str = "slack",
            search: SearchConfig | None = None,
            n_chips: int | None = None,
            profile_phases: bool = True) -> Program:
    """Compile an SNN (graph or quantized model) into a :class:`Program`.

    Runs the explicit pipeline neuron_params -> partition -> schedule
    -> [validate] -> lower (see :mod:`repro.core.passes`) and wraps
    every product in the artifact. ``neuron_params`` checks the Neuron
    Unit's parameters and, for per-neuron ones, proves its int32 state
    cannot overflow, refusing the graph before any mapping where it
    could. ``engine`` picks the default executor of
    :meth:`Program.run`; ``method``/``seed``/``max_iters``/``restarts``/
    ``workers`` parameterize the partitioning pass, and
    ``schedule_method`` names the registered
    :class:`~repro.core.scheduling.ScheduleStrategy` ordering the post
    transmissions (``'slack'`` is the original scheduler).

    ``n_chips=N`` scales the target out to N virtual devices
    (DESIGN.md §11): ``hw`` describes ONE chip and is replicated —
    ``n_spus`` becomes ``hw.n_spus * N`` over the flattened virtual
    tree every pass already understands, and the memory/cycle models
    pick up the per-chip structures and inter-chip hop costs. The
    mapped program's chip traffic is exposed by
    :meth:`Program.chip_span` / :meth:`Program.inter_chip_counts`.

    Passing ``search=SearchConfig(...)`` replaces the single partition
    pass with the joint portfolio search (framework restarts raced
    against every baseline, each feasible mapping scheduled under every
    registered schedule strategy; best (mapping, strategy) pair by OT
    depth and memory wins). The per-candidate trace lands on
    ``program.report.search``, the winning strategy on
    ``program.report.schedule_method``, and both survive
    ``save``/``load``.

    ``profile_phases=True`` (the default) records a per-phase wall-time
    breakdown of the pipeline onto ``report.phase_seconds`` (DESIGN.md
    §12); wrap the call in ``profiled(PhaseProfiler(alloc=True))`` to
    also capture per-phase allocation on ``report.phase_alloc_mb``.
    """
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; use one of {ENGINES}")
    t0 = time.time()
    if n_chips is not None and n_chips != 1:
        if hw.n_chips != 1:
            raise ValueError(
                f"compile(n_chips={n_chips}) replicates a SINGLE-chip "
                f"HardwareConfig; hw already has n_chips={hw.n_chips}")
        hw = dataclasses.replace(hw, n_spus=hw.n_spus * n_chips,
                                 n_chips=n_chips)
    g = (from_quantized(g_or_qsnn) if isinstance(g_or_qsnn, QuantizedSNN)
         else g_or_qsnn)
    trace = None
    tables = None
    schedule_depths = None
    # phase profiler (DESIGN.md §12): reuse a caller-installed profiler
    # (``with profiled(PhaseProfiler(alloc=True)):``) so nested compiles
    # accumulate into it; otherwise install a wall-clock-only one unless
    # profiling is disabled.
    prof = current_profiler()
    ctx = (contextlib.nullcontext(prof)
           if (prof is not None or not profile_phases) else profiled())
    with ctx as prof:
        with phase("neuron_params"):
            neuron_params_pass(g)
        if search is not None:
            if (method, seed, max_iters, restarts, workers,
                    schedule_method) != \
                    ("framework", 0, 20000, 1, 1, "slack"):
                raise ValueError(
                    "search= runs the joint portfolio and takes its "
                    "parameters from the SearchConfig; pass "
                    "seed/max_iters/restarts/workers there instead of as "
                    "compile() arguments (the portfolio explores every "
                    "registered schedule strategy, so schedule_method= "
                    "does not apply)")
            with phase("partition"):
                part, trace, tables = search_pass(g, hw, search)
            method = "portfolio"
            if tables is not None:
                sel = trace.selected
                schedule_method = sel.schedule_method or "slack"
                schedule_depths = sel.schedule_depths
            else:
                schedule_method = "slack"  # infeasible winner: default
        else:
            with phase("partition"):
                part = partition_pass(g, hw, method=method, seed=seed,
                                      max_iters=max_iters,
                                      restarts=restarts, workers=workers)
        if tables is None:
            with phase("schedule"):
                tables = schedule_pass(g, part, hw, method=schedule_method)
        if validate:
            with phase("validate"):
                validate_pass(g, tables)
        with phase("lower"):
            lowered = lower_pass(g, tables)
        with phase("report"):
            report = build_report(g, hw, tables, part, method=method,
                                  compile_seconds=time.time() - t0,
                                  routing=lowered.routing, search=trace,
                                  schedule_method=schedule_method,
                                  schedule_depths=schedule_depths)
    if prof is not None:
        report.phase_seconds = {k: float(v) for k, v in prof.seconds.items()}
        if prof.alloc:
            report.phase_alloc_mb = {k: float(v)
                                     for k, v in prof.alloc_mb.items()}
    return Program(g, hw, tables, lowered, report, part,
                   default_engine=engine)
