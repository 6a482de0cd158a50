"""Multi-device data-parallel execution of a compiled ``Program``.

The compiled batched executor (:class:`repro.core.engine_jax
.JaxMappedEngine`) is embarrassingly parallel over the batch axis —
every sample runs the same lowered program on its own spike train, all
in exact int32 arithmetic. :class:`ShardedRunner` exploits that: it
takes the engine's uncompiled step function and wraps it in
``shard_map`` over a jax mesh, sharding the leading batch axis across
the mesh's ``data`` axis (``PartitionSpec('data')`` in and out) and
replicating the lowered program's constant arrays.

Why the result is bit-exact vs the single-device engine:

* each device executes the byte-identical scan on its batch shard —
  there is no cross-sample communication, reduction, or reordering;
* all arithmetic is int32 (deterministic-commit property, paper §4.2),
  so shard boundaries cannot perturb any value;
* ragged batches are handled by **pad-and-mask**: the batch is padded
  with all-zero samples up to the next multiple of the shard count,
  and the pad rows are sliced away (masked) from spikes, potentials,
  and packet counts before stats are computed — zero-input pad samples
  never touch the real rows.

Tiny batches don't shard well: below ``n_shards * min_shard`` real
samples, per-device dispatch overhead exceeds the parallel win (the
``serve.sharded.dispatch_us`` benchmark row measures it), so
:meth:`ShardedRunner.run` routes such batches through the program's
owned single-device engine — bit-exact by the argument above, just
cheaper. ``min_shard=0`` disables the fallback (conformance tests use
it to force the true shard path at every size).

On CPU, CI forces >= 8 virtual devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (see the
``serving`` lane); with a single device the mesh degenerates to one
shard and the runner is still exact, so the same tests run everywhere.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.engine_jax import (batched_ext_spikes, binary_int8,
                                   fetch_outputs, neuron_state_nbytes,
                                   state_fills)
from repro.core.execution import (AUTO_MESH, ExecutionSpec,
                                  spec_from_legacy_kwargs)
from repro.core.profiling import call_scope, span


class ShardedRunner:
    """A ``Program`` compiled for data-parallel execution over a mesh.

    Construction wraps the program's owned engine step function in
    ``shard_map`` + ``jit``; :meth:`run` then serves any batch —
    including ragged ones that do not divide the shard count — with
    outputs bit-exact vs ``program.run(ext)`` on one device.

    ``spec`` is an :class:`~repro.core.execution.ExecutionSpec`
    (``mesh=None`` means the default serving mesh here); the bare
    ``mesh`` positional and the ``nu_kernel=``/``interpret=`` kwargs
    are the deprecated pre-spec surface.
    """

    def __init__(self, program, mesh=None, *,
                 spec: ExecutionSpec | None = None,
                 nu_kernel: bool | None = None,
                 interpret: bool | None = None, min_shard: int = 1):
        if nu_kernel is not None or interpret is not None:
            if spec is not None:
                raise TypeError("pass spec= OR the deprecated nu_kernel=/"
                                "interpret= kwargs, not both")
            spec = spec_from_legacy_kwargs(
                sharded=True, mesh=mesh, nu_kernel=nu_kernel,
                interpret=interpret, where="ShardedRunner", stacklevel=3)
        elif spec is None:
            spec = ExecutionSpec(mesh=mesh if mesh is not None else AUTO_MESH)
        elif mesh is not None:
            raise TypeError("pass the mesh inside spec=, not alongside it")
        if spec.mesh is None:
            spec = dataclasses.replace(spec, mesh=AUTO_MESH)
        spec = spec.resolve()
        mesh = spec.mesh
        if "data" not in mesh.axis_names:
            raise ValueError(f"mesh axes {mesh.axis_names} lack 'data'; "
                             "the batch axis shards over 'data' "
                             "(launch.mesh.make_serving_mesh)")
        self.spec = spec
        self.mesh = mesh
        self.n_shards = int(mesh.shape["data"])
        self.min_shard = int(min_shard)
        # the per-device engine IS the program's owned single-device
        # engine for this spec — the fallback and the shard path share
        # one compiled scan body
        self._engine = program.engine(spec.single_device())
        self._n_inputs = self._engine.lowered.n_inputs
        self._n_internal = self._engine.lowered.n_internal
        # (v0, s0), or (v0, a0, s0) for a per-neuron program; the outputs
        # are (spikes, v, pkts), plus a
        self._n_state = n_state = self._engine.n_state
        pspec = P("data")
        # check_vma=False: the Pallas kernels have no varying-axes rule;
        # every output is batch-sharded anyway, nothing is replicated.
        shard_step = jax.shard_map(self._engine.step_fn, mesh=mesh,
                                   in_specs=(pspec,) * (1 + n_state),
                                   out_specs=(pspec,) * (1 + n_state),
                                   check_vma=False)

        def sharded_step(ext, *state):
            with jax.named_scope("sharded_step"):
                return shard_step(ext, *state)

        self._run = jax.jit(sharded_step, donate_argnums=(
            tuple(range(1, n_state)) if spec.donate else ()))
        # the int8 train, each chip's rows on that chip
        self._ext_sharding = NamedSharding(mesh, pspec)
        self._aot: dict[tuple[int, int], object] = {}

    def padded_size(self, b: int) -> int:
        """Next multiple of the shard count (the pad-and-mask bucket)."""
        d = self.n_shards
        return ((b + d - 1) // d) * d

    def _use_fallback(self, b: int) -> bool:
        """True when ``b`` real samples go single-device (see module
        docstring): fewer than ``min_shard`` samples per shard."""
        return b < self.n_shards * self.min_shard

    def kernel_grid(self, batch: int) -> tuple[int, int, int] | None:
        """The fused kernel's grid per timestep on each chip for a batch
        of ``batch`` rows: the engine's at one chip's rows of the padded
        batch, or at the whole batch where it falls back to one chip."""
        b = int(batch)
        if not self._use_fallback(b):
            b = self.padded_size(b) // self.n_shards
        return self._engine.kernel_grid(b)

    # -- AOT ----------------------------------------------------------------

    def precompile(self, batch_sizes, timesteps: int
                   ) -> list[tuple[int, int]]:
        """AOT-compile every serving shape, mirroring :meth:`run`'s
        routing: fallback-sized buckets warm the single-device engine,
        the rest warm the sharded scan at their PADDED size (so two
        buckets padding to the same multiple compile once). Returns
        the shapes compiled by this call.
        """
        compiled = []
        for b in batch_sizes:
            b = int(b)
            if self._use_fallback(b):
                compiled.extend(self._engine.precompile([b], timesteps))
                continue
            key = (self.padded_size(b), int(timesteps))
            if key in self._aot:
                continue
            ext = jax.ShapeDtypeStruct((*key, self._n_inputs), jnp.int8,
                                       sharding=self._ext_sharding)
            st = jax.ShapeDtypeStruct((key[0], self._n_internal), jnp.int32)
            exe = self._run.lower(ext, *[st] * self._n_state).compile()
            # one throwaway zero-batch execution warms the dispatch
            # costs outside the executable (state-buffer fills, device
            # placement) — first real request then runs steady-state
            jax.block_until_ready(exe(jnp.zeros(ext.shape, ext.dtype),
                                      *state_fills(st.shape,
                                                   self._n_state)))
            self._aot[key] = exe
            compiled.append(key)
        return compiled

    # -- public API ---------------------------------------------------------

    def run(self, ext_spikes: np.ndarray
            ) -> tuple[np.ndarray, np.ndarray, dict]:
        """Execute the program on ``ext_spikes`` across the mesh.

        ext_spikes: binary ``[T, n_inputs]`` or ``[B, T, n_inputs]``;
        returns ``(spikes, v_final, stats)`` shaped exactly like the
        single-device engine (pad rows are sliced away before stats).
        """
        with call_scope(), span("repro.engine.run"):
            with span("repro.engine.prepare"):
                ext, squeeze = batched_ext_spikes(ext_spikes,
                                                  self._n_inputs)
            b = ext.shape[0]
            if self._use_fallback(b):
                with span("repro.engine.prepare"):
                    ext = binary_int8(ext)
                return self._engine.run_prepared(ext, squeeze)
            # mask: drop the pad rows before any stats are derived
            return fetch_outputs(list(self.shard_outputs(ext)), squeeze,
                                 rows=b)

    def shard_outputs(self, ext: np.ndarray) -> tuple[jax.Array, ...]:
        """A 0/1 ``[B, T, n_inputs]`` batch through the shard path (never
        the fallback): the device arrays ``(spikes, v, pkts[, a])``,
        batch-sharded over the mesh, pad rows included — what
        :meth:`run` masks and copies back.

        Each chip's rows are checked and narrowed to int8 apart (the
        engine's :func:`~repro.core.engine_jax.binary_int8`) and cross,
        one byte per spike, straight to that chip, not through one chip
        and a split inside the jit."""
        b, t = ext.shape[0], ext.shape[1]
        full = self.padded_size(b)
        rows = full // self.n_shards
        with span("repro.engine.prepare"):
            parts = []
            for lo in range(0, full, rows):
                part = binary_int8(ext[lo:lo + rows])
                if len(part) < rows:           # pad: all-zero samples
                    part = np.concatenate([part, np.zeros(
                        (rows - len(part), t, self._n_inputs), np.int8)])
                parts.append(part)
        with span("repro.engine.upload",
                  nbytes=sum(p.nbytes for p in parts)):
            x = jax.make_array_from_callback(
                (full, t, self._n_inputs), self._ext_sharding,
                lambda idx: parts[(idx[0].start or 0) // rows])
        shape = (full, self._n_internal)
        with span("repro.engine.launch",
                  nbytes=neuron_state_nbytes(shape, self._n_state)):
            fn = self._aot.get((full, t), self._run)
            return fn(x, *state_fills(shape, self._n_state))


def sharded_runner(program, mesh=None, *, spec: ExecutionSpec | None = None,
                   nu_kernel: bool | None = None,
                   interpret: bool | None = None,
                   min_shard: int = 1) -> ShardedRunner:
    """Build a :class:`ShardedRunner` for ``program`` (default mesh:
    every device on the ``data`` axis)."""
    return ShardedRunner(program, mesh, spec=spec, nu_kernel=nu_kernel,
                         interpret=interpret, min_shard=min_shard)
