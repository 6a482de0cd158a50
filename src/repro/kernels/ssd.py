"""Mamba-2 SSD recurrence as a Pallas TPU kernel — zamba2's state-space
half, same design as kernels/wkv6.py (and the same roofline motivation:
the chunked einsum form materializes O(C^2 H) decay-ratio tensors in HBM;
zamba2 train_4k sits at 0.02-0.03 of roofline, memory-bound).

The per-head SSM state S [P, N] lives in VMEM scratch across the
sequential chunk grid; tokens update it rank-1:

    S_t = exp(-exp(a_log_h) * dt_t) * S_{t-1} + dt_t * x_t b_t^T
    y_t = S_t c_t

HBM traffic = stream x/dt/b/c once + write y once. Grid (B, H, S/C),
chunk axis minormost (sequential on TPU), state re-initialized from the
carried input when the chunk index wraps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _kernel(x_ref, dt_ref, b_ref, c_ref, alog_ref, s0_ref,
            y_ref, s_out_ref, state, *, chunk: int):
    cc = pl.program_id(2)

    @pl.when(cc == 0)
    def _init():
        state[...] = s0_ref[0, 0].astype(jnp.float32)

    neg_a = jnp.exp(alog_ref[0, 0].astype(jnp.float32))   # -A > 0, scalar

    def step(t, st):
        x = x_ref[0, 0, t].astype(jnp.float32)            # [P]
        dt = dt_ref[0, 0, t].astype(jnp.float32)          # scalar
        b = b_ref[0, t].astype(jnp.float32)               # [N]
        c = c_ref[0, t].astype(jnp.float32)               # [N]
        decay = jnp.exp(-neg_a * dt)
        st = decay * st + dt * x[:, None] * b[None, :]
        y_ref[0, 0, t] = (st @ c).astype(y_ref.dtype)     # y_t = S_t c_t
        return st

    state[...] = jax.lax.fori_loop(0, chunk, step, state[...])

    @pl.when(cc == pl.num_programs(2) - 1)
    def _flush():
        s_out_ref[0, 0] = state[...].astype(s_out_ref.dtype)


def ssd_pallas(x, dt, a_log, b, c, state0, *, chunk: int = DEFAULT_CHUNK,
               interpret: bool):
    """x [B, S, H, P]; dt [B, S, H] (softplus'd, >= 0); a_log [H];
    b/c [B, S, N]; state0 [B, H, P, N] f32.

    Returns (y [B, S, H, P], state [B, H, P, N]). Matches
    ``repro.models.mamba2.ssd_chunked`` / ``ssd_step`` (the D-skip and
    gating stay outside, as in the model). Padding is harmless: dt pad =
    0 -> decay 1 and zero state update.
    """
    bsz, s, h, p_dim = x.shape
    n = b.shape[-1]
    pad = -s % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad

    xh = x.transpose(0, 2, 1, 3)                   # [B, H, S, P]
    dth = dt.transpose(0, 2, 1)                    # [B, H, S]

    grid = (bsz, h, sp // chunk)
    y, s_out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p_dim),
                         lambda bb, hh, cc: (bb, hh, cc, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bb, hh, cc: (bb, hh, cc)),
            pl.BlockSpec((1, chunk, n), lambda bb, hh, cc: (bb, cc, 0)),
            pl.BlockSpec((1, chunk, n), lambda bb, hh, cc: (bb, cc, 0)),
            pl.BlockSpec((1, 1), lambda bb, hh, cc: (0, hh)),
            pl.BlockSpec((1, 1, p_dim, n),
                         lambda bb, hh, cc: (bb, hh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p_dim),
                         lambda bb, hh, cc: (bb, hh, cc, 0)),
            pl.BlockSpec((1, 1, p_dim, n),
                         lambda bb, hh, cc: (bb, hh, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bsz, h, sp, p_dim), x.dtype),
                   jax.ShapeDtypeStruct((bsz, h, p_dim, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((p_dim, n), jnp.float32)],
        interpret=interpret,
    )(xh, dth, b, c, a_log[None, :], state0)
    return y.transpose(0, 2, 1, 3)[:, :s], s_out
