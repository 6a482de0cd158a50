"""WKV-6 (RWKV "Finch") recurrence as a Pallas TPU kernel.

WHY (roofline-driven, EXPERIMENTS.md §Perf rwkv6 iterations): the pure-JAX
chunked WKV materializes the intra-chunk decay-ratio tensor
[C, C, H, N] in HBM every chunk — at train_4k scale that one intermediate
makes rwkv6-3b the WORST roofline cell of the whole grid (memory term
~100x the compute term). The kernel keeps the running state S [N, N], the
chunk inputs, and every intermediate in VMEM: HBM traffic drops to
read r/k/v/w once + write y once — the arithmetic-intensity profile the
paper's Unified-Memory/SPU-local design achieves for synaptic sums.

Mapping (DESIGN.md §3/§4): the per-head state S is "neuronal" (small,
stateful, sequential — lives in VMEM scratch like membrane potentials in
the Neuron Unit); the r/k/v/w streams are "synaptic" (big, streamed).

Grid: (B, H, S/C) with the chunk axis minormost — TPU grids execute
sequentially, so VMEM scratch carries S across chunks of one (b, h) and
re-initializes when the chunk index wraps (same pattern as spike_accum's
accumulator).

Inside a chunk the recurrence is stepped token-by-token with rank-1
updates (fori_loop over C): O(C N^2) VPU work per head-chunk with ZERO
HBM intermediates. The matrix-form intra-chunk path (two MXU matmuls)
requires an exp(+cumsum) ratio factorization that overflows for long
chunks; the sequential form is unconditionally stable, and with every
operand VMEM-resident the kernel is bandwidth- not compute-bound anyway.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
            y_ref, s_out_ref, state, *, chunk: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                    # [N]

    def step(t, st):
        r = r_ref[0, 0, t].astype(jnp.float32)          # [N]
        k = k_ref[0, 0, t].astype(jnp.float32)
        v = v_ref[0, 0, t].astype(jnp.float32)
        w = w_ref[0, 0, t].astype(jnp.float32)          # log-decay <= 0
        # y_t = r . (S + (u*k) v^T)   (current-token bonus included)
        bonus = jnp.sum(r * u * k)
        y = r @ st + bonus * v
        y_ref[0, 0, t] = y.astype(y_ref.dtype)
        # S' = diag(exp(w)) S + k v^T
        return jnp.exp(w)[:, None] * st + k[:, None] * v[None, :]

    state[...] = jax.lax.fori_loop(0, chunk, step, state[...])

    @pl.when(c == pl.num_programs(2) - 1)
    def _flush():
        s_out_ref[0, 0] = state[...].astype(s_out_ref.dtype)


def wkv6_pallas(r, k, v, w_log, u, state0, *, chunk: int = DEFAULT_CHUNK,
                interpret: bool):
    """r/k/v/w_log [B, S, H, N]; u [H, N]; state0 [B, H, N, N] f32.

    Returns (y [B, S, H, N], state [B, H, N, N]). S is padded to a chunk
    multiple (padded slots have k = v = 0 and exp(0) = 1 decay: the state
    passes through unchanged, so results are pad-invariant).
    """
    b, s, h, n = r.shape
    pad = -s % chunk
    if pad:
        zp = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v, w_log = zp(r), zp(k), zp(v), zp(w_log)
    sp = s + pad

    # [B, S, H, N] -> [B, H, S, N]: the streamed tile is (tokens, features)
    tr = lambda x: x.transpose(0, 2, 1, 3)
    r, k, v, w_log = tr(r), tr(k), tr(v), tr(w_log)

    seq_spec = pl.BlockSpec((1, 1, chunk, n),
                            lambda bb, hh, cc: (bb, hh, cc, 0))
    state_spec = pl.BlockSpec((1, 1, n, n), lambda bb, hh, cc: (bb, hh, 0, 0))
    grid = (b, h, sp // chunk)
    y, s_out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, n), lambda bb, hh, cc: (hh, 0)),
                  state_spec],
        out_specs=[seq_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, sp, n), r.dtype),
                   jax.ShapeDtypeStruct((b, h, n, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w_log, u, state0)
    y = y.transpose(0, 2, 1, 3)[:, :s]
    return y, s_out
