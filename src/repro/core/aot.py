"""Ahead-of-time compilation + the persistent XLA cache (cold start).

Cold-start compilation dominates first-request serving latency: the
first batch through a freshly-loaded :class:`~repro.core.program
.Program` pays the full XLA trace+compile of the timestep scan — tens
of times the steady-state service time. Two layers kill it:

* **AOT bucket precompile** — ``Program.precompile(buckets, T)`` (and
  the ``precompile=`` hooks on ``Program.load`` / registry insert)
  walks every padded batch shape the serving policy can dispatch
  (:class:`~repro.serve.batcher.BatchPolicy.buckets`) and compiles the
  engine's jitted scan for it NOW, via ``jit(...).lower(shapes)
  .compile()``; ``run()`` dispatches straight to the stored executable,
  so the first real request never traces;
* **persistent compilation cache** — :func:`enable_persistent_cache`
  (called by ``Program.engine`` before the first compile of a serving
  engine) turns jax's on-disk cache on, so a *restarted* process skips
  XLA for shapes any previous process compiled. The directory is
  ``JAX_COMPILATION_CACHE_DIR`` when that is set (jax reads it itself;
  no other directory is set in code), else the fixed ``.jax-cache`` at
  the root of the checkout. The path is part of what a later run must
  find again, so it is never built from a temp name, a pid or the
  time. The cache is keyed by the serialized HLO, and the lowered
  program's constants (op tables / dense weight plane) are baked into
  that HLO — distinct Programs therefore key distinct entries with no
  extra salt. :func:`content_hash` exposes the salt CI uses to version
  its cached directory (actions/cache key = jax version + program
  hash).

Both layers are warm-path-only optimizations: they never change what
executes, only when it compiles.
"""
from __future__ import annotations

import hashlib
import os
from pathlib import Path

import numpy as np

from repro.snn.lif import NeuronParams

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax-cache"

_enabled = False


def cache_dir() -> str:
    """The persistent-cache directory: ``JAX_COMPILATION_CACHE_DIR`` if
    set, else the checkout's fixed ``.jax-cache``. Pure — touches no
    jax state."""
    return os.environ.get(ENV_CACHE_DIR) or str(DEFAULT_CACHE_DIR)


def enable_persistent_cache() -> str:
    """Turn jax's on-disk compilation cache on; returns its directory.

    Sets ``jax_compilation_cache_dir`` only when
    ``JAX_COMPILATION_CACHE_DIR`` is unset (jax already took the
    variable's value), and opens the thresholds (min size / compile
    time -> 0) so even the small SNN scans persist. Idempotent.
    """
    global _enabled
    import jax
    if not _enabled:
        if not os.environ.get(ENV_CACHE_DIR):
            jax.config.update("jax_compilation_cache_dir", cache_dir())
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        _enabled = True
    return jax.config.jax_compilation_cache_dir


def normalize_buckets(buckets) -> tuple[int, ...]:
    """Coerce a ``BatchPolicy`` or iterable of batch sizes to sorted
    unique positive ints — the shapes AOT precompile walks."""
    buckets = getattr(buckets, "buckets", buckets)
    if isinstance(buckets, (int, np.integer)):
        buckets = (buckets,)
    out = tuple(sorted({int(b) for b in buckets}))
    if not out or out[0] < 1:
        raise ValueError(f"precompile buckets must be positive batch "
                         f"sizes, got {buckets}")
    return out


def content_hash(program) -> str:
    """SHA-256 of everything that determines the compiled computation.

    Covers the lowered op stream (the constants baked into the HLO),
    the routing matrix, the Neuron Unit parameters (the scalar LIF, or
    every per-neuron vector), and the problem dims —
    NOT the search/report metadata, so re-compiling the same mapping
    hashes identically. Used as the CI cache-key salt.
    """
    lw = program.lowered
    h = hashlib.sha256()
    for name in ("op_spu", "op_slot", "op_pre", "op_post_local",
                 "op_weight", "op_pre_end", "op_post_end", "routing"):
        a = np.ascontiguousarray(getattr(lw, name))
        h.update(f"{name}:{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    lif = program.graph.lif
    if isinstance(lif, NeuronParams):
        for name, a in zip(lif._fields, lif):
            h.update(f"neuron.{name}:{a.shape}".encode())
            h.update(np.ascontiguousarray(a, np.int32).tobytes())
        head = "neurons"
    else:
        head = f"lif:{lif.leak_shift}:{lif.v_threshold}:{lif.v_reset}"
    h.update(f"{head}:dims:{lw.n_inputs}:{lw.n_neurons}:{lw.n_internal}"
             f":{lw.n_spus}:{lw.depth}".encode())
    return h.hexdigest()
