"""Compiled, batched executor of mapped OpTables programs.

``engine.run_mapped`` is the *reference* executor: a Python triple loop
over timesteps x OT slots x SPUs that mirrors the hardware datapath
structure op by op. That fidelity costs ~0.5 s per MNIST image — fine for
verification, useless for serving. This module lowers a scheduled program
ONCE into dense arrays (:func:`repro.core.schedule.lower_tables`) and
executes it with ``jax.lax.scan`` over timesteps, with a leading batch
dimension pushing many samples through one mapped program. The body of
the scan is one of three **kernel tiers**, selected by
:class:`~repro.core.execution.ExecutionSpec`:

* ``"fused"`` (platform default) — the whole timestep in ONE Pallas
  launch: multicast routing + per-SPU accumulation as a packed dense
  int contraction, Neuron-Unit update as the in-register epilogue,
  packet counts for free (:mod:`repro.kernels.fused_step`);
* ``"lif"`` — the split pipeline: vectorized segment-sum over all
  (SPU, slot) ops + the small Pallas Neuron-Unit kernel
  (:func:`repro.kernels.lif_update.lif_update_int`);
* ``"reference"`` — segment-sum + pure-jnp ``lif_step_int``.

A program whose neurons do not all share one non-adaptive LIF
(:attr:`~repro.core.graph.SNNGraph.scalar_lif` is ``None``: per-neuron
leaks, adaptive thresholds, subtractive reset, leaky readouts) carries
the adaptation ``a`` as a third scan state, ``(v, a, s)``, and returns
it after the spikes, potentials and packets. The fused tier runs it
through :func:`~repro.kernels.fused_step.fused_step_alif` and the
reference tier through :func:`~repro.snn.lif.alif_step_int`; the
``"lif"`` tier's Neuron Unit kernel is scalar and refuses such a
program.

Why this is still the SAME program, bit for bit (deterministic-commit
property, paper §4.2):

* every non-NOP op contributes ``weight * spike_bit(pre)`` to its post
  neuron exactly once per timestep — Spike Memory bits are set at
  distribution and cleared by Pre-End only after the last reference, so
  within a timestep an op is active iff its pre fired (external spike at
  t, or internal spike at t-1);
* the ME-tree merge and the per-SPU partial sums are plain int32
  additions, which are associative and exact — any summation order
  (segment_sum here, slot-major commit in the reference) yields the
  identical int32 current;
* the Neuron Unit applies the same int32 shift-leak LIF step to every
  post neuron once per timestep.

Outputs therefore match ``run_oracle``/``run_mapped`` bit-exactly, and
the emitted per-timestep MC packet counts equal ``run_mapped``'s stats,
so ``CycleModel`` latency/energy reports are unchanged.

Engines are owned by the :class:`repro.core.program.Program` artifact
(``program.run(ext)`` / ``program.engine(spec)``), which builds them
lazily from its already-lowered program, keyed on the **resolved**
spec, and reuses them across calls; construct :class:`JaxMappedEngine`
directly only when driving a bare ``OpTables`` outside the artifact
API. :meth:`JaxMappedEngine.precompile` AOT-compiles the scan for the
serving buckets so the first real request never traces (see
:mod:`repro.core.aot`).
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import packet_stats
from repro.core.execution import (_NU_KERNEL_TIER, ExecutionSpec, as_spec,
                                  spec_from_legacy_kwargs)
from repro.core.graph import SNNGraph
from repro.core.profiling import call_scope, span
from repro.core.scheduling import LoweredProgram, OpTables, lower_tables
from repro.kernels.fused_step import (fused_step, fused_step_alif, pack_dense,
                                      step_block, step_grid)
from repro.kernels.lif_update import lif_update_int
from repro.snn.lif import LIFIntParams, alif_step_int, lif_step_int


def normalize_ext_spikes(ext_spikes, n_inputs: int
                         ) -> tuple[np.ndarray, bool]:
    """Validate a spike train (batch) into int8 ``[B, T, n_inputs]`` form.

    Returns ``(ext, squeeze)`` where ``squeeze`` records that a 2-D
    ``[T, n_inputs]`` input was promoted and the outputs should drop
    the batch dim again: :func:`batched_ext_spikes`, then
    :func:`binary_int8`. Shared by the single-device engine and the
    sharded runner so validation cannot drift between them.
    """
    ext, squeeze = batched_ext_spikes(ext_spikes, n_inputs)
    return binary_int8(ext), squeeze


def batched_ext_spikes(ext_spikes, n_inputs: int
                       ) -> tuple[np.ndarray, bool]:
    """The shape half of :func:`normalize_ext_spikes`: ``[B, T,
    n_inputs]`` (a 2-D ``[T, n_inputs]`` train promoted, ``squeeze``
    set), values not yet read."""
    ext = np.asarray(ext_spikes)
    squeeze = ext.ndim == 2
    if squeeze:
        ext = ext[None]
    if ext.ndim != 3 or ext.shape[2] != n_inputs:
        raise ValueError(f"ext_spikes shape {np.shape(ext_spikes)} != "
                         f"[B, T, {n_inputs}] or [T, {n_inputs}]")
    return ext, squeeze


# elements of a train checked and narrowed at a time: the narrowing
# reads each chunk from cache, right after the check read it
_CHUNK = 1 << 18


def binary_int8(ext: np.ndarray) -> np.ndarray:
    """``ext`` as int8, one byte per spike, after checking that every
    entry is 0 or 1.

    The fused tier's MXU contraction is proven exact only for binary
    spikes (:func:`~repro.analysis.ranges.mxu_operand_dtype`). The check
    reads the caller's values, before the narrowing, so a 256 or a -255
    is refused rather than wrapped to 0 or 1. An integer train is read
    as unsigned of its own width, where a negative entry is past 1 too,
    so one max per chunk is the whole check."""
    if ext.dtype == np.bool_:
        return ext.view(np.int8)
    if ext.dtype.kind not in "iu" or not ext.dtype.isnative:
        if ext.min(initial=0) < 0 or ext.max(initial=0) > 1:
            _refuse(ext)
        return ext.astype(np.int8)
    flat = np.ascontiguousarray(ext).reshape(-1)
    bits = flat.view(f"u{flat.itemsize}")
    out = np.empty(flat.shape, np.int8)
    for i in range(0, flat.size, _CHUNK):
        if bits[i:i + _CHUNK].max() > 1:
            _refuse(ext)
        np.copyto(out[i:i + _CHUNK], flat[i:i + _CHUNK], casting="unsafe")
    return out.reshape(ext.shape)


def _refuse(ext: np.ndarray):
    raise ValueError(f"ext_spikes must be 0/1, got values in "
                     f"[{ext.min()}, {ext.max()}]")


def finalize_outputs(spikes, v, pkts, squeeze: bool, a=None
                     ) -> tuple[np.ndarray, np.ndarray, dict]:
    """Device arrays -> the uniform ``(spikes, v_final, stats)`` tuple;
    a per-neuron program's final adaptation ``a`` goes to
    ``stats["adaptation"]``."""
    spikes = np.asarray(spikes, np.int32)
    v = np.asarray(v, np.int32)
    pkts = np.asarray(pkts, np.int64)
    if a is not None:
        a = np.asarray(a, np.int32)
    if squeeze:
        spikes, v, pkts = spikes[0], v[0], pkts[0]
        a = None if a is None else a[0]
    stats = packet_stats(pkts)
    if a is not None:
        stats["adaptation"] = a
    return spikes, v, stats


def fetch_outputs(outs: list, squeeze: bool, rows: int | None = None
                  ) -> tuple[np.ndarray, np.ndarray, dict]:
    """Copy the executable's ``[spikes, v, pkts]`` (``+ [a]`` for a
    per-neuron program) to the host (the first ``rows`` rows, where the
    batch was padded) and :func:`finalize_outputs` them.

    The host copies block as they always did; no sync is added. The
    first copy, of the small ``pkts``, is where the host waits for the
    device, so it is span ``repro.engine.wait``; the copies of
    ``spikes``, ``v`` (and ``a``) and the finalize are
    ``repro.engine.download``. Empties ``outs``, so each device buffer
    is released inside its span, not at a later return outside every
    span."""
    spikes, v, pkts, *state = outs
    outs.clear()
    with span("repro.engine.wait", nbytes=int(pkts.nbytes)):
        pkts = np.asarray(pkts)[:rows]
    with span("repro.engine.download",
              nbytes=int(spikes.nbytes) + int(v.nbytes)
              + sum(int(x.nbytes) for x in state)):
        spikes = np.asarray(spikes)[:rows]
        v = np.asarray(v)[:rows]
        a = np.asarray(state.pop())[:rows] if state else None
        return finalize_outputs(spikes, v, pkts, squeeze, a)


def state_fills(shape: tuple[int, int], n_state: int) -> list[jax.Array]:
    """Zero initial states for one call: ``v0, s0`` or ``v0, a0, s0``,
    distinct buffers (under donation no two may alias)."""
    return [jnp.zeros(shape, jnp.int32) for _ in range(n_state)]


def neuron_state_nbytes(shape: tuple[int, int], n_state: int) -> int:
    """Bytes of the Neuron Unit state a call puts on the device: ``v``,
    plus ``a`` for a per-neuron program (the carried spikes ``s0`` are
    the spike plane, not Neuron Unit state)."""
    return (n_state - 1) * shape[0] * shape[1] * 4


class JaxMappedEngine:
    """A mapped program compiled for batched execution.

    Construction lowers the tables and jit-compiles the scan; ``run``
    then serves any batch of spike trains through the same program.
    Reuse one engine across calls — compilation is cached per engine,
    per (batch, timesteps) shape.
    """

    def __init__(self, g: SNNGraph, tables: OpTables | LoweredProgram,
                 spec: ExecutionSpec | None = None, *,
                 nu_kernel: bool | None = None,
                 interpret: bool | None = None):
        """``spec`` selects the kernel tier / interpret mode / donation
        (:class:`~repro.core.execution.ExecutionSpec`); ``None`` is the
        platform default (fused tier, interpret off-TPU).
        ``nu_kernel=``/``interpret=`` are the deprecated pre-spec
        kwargs and delegate with a ``DeprecationWarning``."""
        if nu_kernel is not None or interpret is not None:
            if spec is not None:
                raise TypeError("pass spec= OR the deprecated nu_kernel=/"
                                "interpret= kwargs, not both")
            spec = spec_from_legacy_kwargs(
                nu_kernel=nu_kernel, interpret=interpret,
                where="JaxMappedEngine", stacklevel=3)
        spec = as_spec(spec).resolve()
        if spec.engine != "jax" or spec.mesh is not None:
            raise ValueError(
                f"JaxMappedEngine is the single-device jax engine; got "
                f"{spec} (meshes go through repro.serve.sharded)")
        self.spec = spec
        self.lowered = (tables if isinstance(tables, LoweredProgram)
                        else lower_tables(g, tables))
        self.lif: LIFIntParams | None = g.scalar_lif
        self.neurons = g.lif if self.lif is None else None
        # scan state: (v, s), or (v, a, s) with the per-neuron Neuron Unit
        self.n_state = 2 if self.lif is not None else 3
        self._operand = None            # the fused plane's MXU operand type
        self._fn = self._build()
        # donate the Neuron Unit state (v0 -> v_final, a0 -> a_final
        # storage); s0 has no same-shaped output and would just warn
        self._run = jax.jit(self._fn, donate_argnums=(
            tuple(range(1, self.n_state)) if spec.donate else ()))
        self._aot: dict[tuple[int, int], object] = {}

    @property
    def step_fn(self):
        """The uncompiled ``(ext [B,T,in] int8, v0, [a0,] s0) -> (spikes,
        v, pkts[, a])`` program — :mod:`repro.serve.sharded` wraps it in
        ``shard_map`` over a device mesh before jitting, so the sharded
        executor runs the byte-identical computation per shard."""
        return self._fn

    # -- compiled program ---------------------------------------------------

    def _build(self):
        lw, lif = self.lowered, self.lif
        tier, interp = self.spec.kernel, self.spec.interpret
        if lif is None and tier == "lif":
            raise ValueError(
                "the 'lif' tier's Neuron Unit kernel is one scalar LIF; this "
                "program has per-neuron or adaptive parameters: use "
                "kernel='fused' (or 'reference')")
        if tier == "fused":
            # whole timestep in one Pallas launch over the packed
            # dense plane — bit-exact vs the split pipeline (int32
            # addition is associative; deterministic-commit, §4.2)
            w = pack_dense(lw).operand()
            self._operand = w.dtype
            if lif is None:
                params = jnp.asarray(self.neurons.packed())

                def step(carry, ext_t):
                    v, a, s_prev = carry
                    s_all = jnp.concatenate([ext_t, s_prev], axis=1)
                    v_next, a_next, s, pkt = fused_step_alif(
                        s_all, v, a, w, params, interpret=interp)
                    return (v_next, a_next, s), (s, pkt)

                return self._scan(step)

            def step(carry, ext_t):
                v, s_prev = carry
                s_all = jnp.concatenate([ext_t, s_prev], axis=1)
                v_next, s, pkt = fused_step(s_all, v, w, lif,
                                            interpret=interp)
                return (v_next, s), (s, pkt)

            return self._scan(step)

        op_pre = jnp.asarray(lw.op_pre)
        op_w = jnp.asarray(lw.op_weight, jnp.int32)
        accum = functools.partial(jax.ops.segment_sum,
                                  segment_ids=jnp.asarray(lw.op_post_local),
                                  num_segments=lw.n_internal)
        if lif is None:
            p = type(self.neurons)(*(jnp.asarray(x) for x in self.neurons))

            def nu(v, a, current):
                return alif_step_int(v, a, current, p)
        elif tier == "lif":
            nu = functools.partial(lif_update_int, p=lif, interpret=interp)
        else:
            nu = functools.partial(lif_step_int, p=lif)

        def step(carry, ext_t):
            *state, s_prev = carry
            # distribution phase: one MC packet per fired neuron
            s_all = jnp.concatenate([ext_t, s_prev], axis=1)
            pkt = jnp.sum(s_all != 0, axis=1)
            # synaptic phase: every op gated by its pre's spike bit,
            # merged per post neuron (exact int32 sum == ME tree)
            act = jnp.take(s_all, op_pre, axis=1)
            current = jax.vmap(accum)(act * op_w)
            # Neuron Unit: fused leak/integrate/fire/reset
            *state, s = nu(*state, current)
            s = s.astype(jnp.int32)
            return (*state, s), (s, pkt)

        return self._scan(step)

    @staticmethod
    def _scan(step):

        def run(ext, *init):
            # ext [B, T, n_inputs] int8 -> scan is time-major; each
            # step widens its input spikes to the state dtype before
            # they join the internal spikes of t-1
            def widened(carry, ext_t):
                return step(carry, ext_t.astype(init[-1].dtype))

            with jax.named_scope("engine_scan"):
                (v, *adapt, _), (spikes, pkts) = jax.lax.scan(
                    widened, init, jnp.swapaxes(ext, 0, 1))
            return (jnp.swapaxes(spikes, 0, 1), v,
                    jnp.swapaxes(pkts, 0, 1), *adapt)

        return run

    # -- AOT ----------------------------------------------------------------

    def precompile(self, batch_sizes, timesteps: int) -> list[tuple[int, int]]:
        """AOT-compile the scan for each ``(batch, timesteps)`` shape.

        Lowers + compiles via ``jit(...).lower(shapes).compile()`` and
        stores the executables; :meth:`run` dispatches to a stored
        executable when the incoming shape matches, so a precompiled
        shape's first real request skips XLA tracing entirely. Returns
        the shapes compiled by THIS call (already-warm shapes skip).
        Idempotent; serving passes ``BatchPolicy.buckets`` here.
        """
        lw = self.lowered
        compiled = []
        for b in batch_sizes:
            key = (int(b), int(timesteps))
            if key in self._aot:
                continue
            ext = jax.ShapeDtypeStruct((*key, lw.n_inputs), jnp.int8)
            st = jax.ShapeDtypeStruct((key[0], lw.n_internal), jnp.int32)
            exe = self._run.lower(ext, *[st] * self.n_state).compile()
            # execute once on zeros: warms the one-time dispatch costs
            # that live outside the executable (the jnp.zeros fills for
            # these state shapes, host<->device transfer setup), so the
            # first real request runs at steady-state latency
            jax.block_until_ready(exe(jnp.zeros(ext.shape, ext.dtype),
                                      *state_fills(st.shape, self.n_state)))
            self._aot[key] = exe
            compiled.append(key)
        return compiled

    def kernel_grid(self, batch: int) -> tuple[int, int, int] | None:
        """Grid steps per timestep ``(nb, nj, nk)`` of the fused kernel
        at ``batch`` rows: batch, post and pre blocks of the block
        :func:`~repro.kernels.fused_step.step_block` resolves for this
        program's plane, the quantity that sets the kernel's time.
        ``None`` off the fused tier."""
        if self._operand is None:
            return None
        lw = self.lowered
        n_all = lw.n_inputs + lw.n_internal
        block = step_block(batch, n_all, lw.n_internal, self.n_state - 1,
                           self._operand, interpret=self.spec.interpret)
        return step_grid(batch, n_all, lw.n_internal, block)

    def executable(self, batch: int, timesteps: int):
        """The AOT executable :meth:`precompile` stored for this shape
        (``KeyError`` if that shape was never precompiled)."""
        return self._aot[(int(batch), int(timesteps))]

    # -- public API ---------------------------------------------------------

    def run(self, ext_spikes: np.ndarray
            ) -> tuple[np.ndarray, np.ndarray, dict]:
        """Execute the program on ``ext_spikes``.

        ext_spikes: [T, n_inputs] or batched [B, T, n_inputs], binary.
        Returns (spikes, v_final, stats) shaped like ``run_mapped`` for
        2-D input ([T, n_int] / [n_int] / packet_counts [T]); with a
        batch dimension the leading B is kept ([B, T, n_int] / [B, n_int]
        / [B, T]).
        """
        with call_scope(), span("repro.engine.run"):
            with span("repro.engine.prepare"):
                ext, squeeze = normalize_ext_spikes(ext_spikes,
                                                    self.lowered.n_inputs)
            return self.run_prepared(ext, squeeze)

    def run_prepared(self, ext: np.ndarray, squeeze: bool
                     ) -> tuple[np.ndarray, np.ndarray, dict]:
        """:meth:`run` after its input preparation: upload the
        validated int8 ``[B, T, n_inputs]`` batch (one byte per spike,
        as :func:`normalize_ext_spikes` gives it), launch, wait and
        download (the sharded runner's fallback enters here)."""
        with span("repro.engine.upload", nbytes=ext.nbytes):
            x = jnp.asarray(ext)
        shape = (ext.shape[0], self.lowered.n_internal)
        with span("repro.engine.launch",
                  nbytes=neuron_state_nbytes(shape, self.n_state)):
            fn = self._aot.get((ext.shape[0], ext.shape[1]), self._run)
            outs = list(fn(x, *state_fills(shape, self.n_state)))
            del x                          # released once enqueued
        return fetch_outputs(outs, squeeze)


# -- deprecated convenience entry point -------------------------------------

def run_mapped_batched(g: SNNGraph, tables: OpTables, ext_spikes: np.ndarray,
                       *, nu_kernel: bool = True,
                       interpret: bool | None = None
                       ) -> tuple[np.ndarray, np.ndarray, dict]:
    """Deprecated: use ``Program.run`` (:mod:`repro.core.program`).

    Batched counterpart of ``engine.run_mapped``. Builds a fresh
    :class:`JaxMappedEngine` on every call — the former module-level
    ``id()``-keyed cache is gone (recycled ids could alias dead
    programs, and ``interpret=None`` vs its resolved value duplicated
    engines). Compiled engines are now owned by the ``Program``
    artifact, which keys them on resolved build options and reuses
    them across calls; construct one via ``repro.core.compile`` to
    avoid per-call recompilation.
    """
    warnings.warn(
        "run_mapped_batched is deprecated and recompiles per call; use "
        "repro.core.compile(...).run(ext)",
        DeprecationWarning, stacklevel=2)
    eng = JaxMappedEngine(
        g, tables,
        ExecutionSpec(kernel=_NU_KERNEL_TIER[bool(nu_kernel)],
                      interpret=interpret))
    return eng.run(ext_spikes)
