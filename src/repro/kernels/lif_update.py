"""Fused LIF membrane-update Pallas kernels — the centralized Neuron Unit.

Leak, integrate, threshold, and reset (paper Eqs. 2/4/5, Fig. 7 pipeline)
fused into one element-wise VMEM pass: one HBM read + one write per state
element instead of the four separate passes a naive implementation costs.

Two variants share the same tiling:

* ``lif_update``     — float path (training-side inference);
* ``lif_update_int`` — int32 path with the hardware's shift-based leak
  ``V - (V >> shift)``, bit-exact with :func:`repro.snn.lif.lif_step_int`.
  This is the Neuron Unit of the compiled mapped executor
  (:mod:`repro.core.engine_jax`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.snn.lif import LIFIntParams, leak_int


DEFAULT_BLOCK = (8, 128)


def _pad_call(kernel, v, current, block, interpret):
    """Shared pad-to-block / grid / unpad wrapper for both LIF variants."""
    squeeze = v.ndim == 1
    if squeeze:
        v, current = v[None, :], current[None, :]
    b, n = v.shape
    bb, bn = block
    pb, pn = -b % bb, -n % bn
    vp = jnp.pad(v, ((0, pb), (0, pn)))
    ip = jnp.pad(current, ((0, pb), (0, pn)))

    grid = (vp.shape[0] // bb, vp.shape[1] // bn)
    v_next, spikes = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
                  pl.BlockSpec((bb, bn), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
                   pl.BlockSpec((bb, bn), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct(vp.shape, v.dtype),
                   jax.ShapeDtypeStruct(vp.shape, v.dtype)],
        interpret=interpret,
    )(vp, ip)
    v_next, spikes = v_next[:b, :n], spikes[:b, :n]
    if squeeze:
        v_next, spikes = v_next[0], spikes[0]
    return v_next, spikes


def _kernel(v_ref, i_ref, v_out_ref, s_ref, *, alpha, v_th, v_reset):
    v = v_ref[...]
    v_upd = (1.0 - alpha) * v + i_ref[...]
    spike = v_upd >= v_th
    v_out_ref[...] = jnp.where(spike, jnp.asarray(v_reset, v.dtype), v_upd)
    s_ref[...] = spike.astype(v.dtype)


def lif_update(v: jax.Array, current: jax.Array, *, alpha: float,
               v_th: float = 1.0, v_reset: float = 0.0,
               block: tuple[int, int] = DEFAULT_BLOCK,
               interpret: bool) -> tuple[jax.Array, jax.Array]:
    """Fused LIF step on [B, N] (or [N], auto-promoted) state tensors."""
    kernel = functools.partial(_kernel, alpha=alpha, v_th=v_th,
                               v_reset=v_reset)
    return _pad_call(kernel, v, current, block, interpret)


def _kernel_int(v_ref, i_ref, v_out_ref, s_ref, *, leak_shift, v_th, v_reset):
    v = v_ref[...]
    v_upd = leak_int(v, leak_shift) + i_ref[...]
    spike = v_upd >= v_th
    v_out_ref[...] = jnp.where(spike, jnp.asarray(v_reset, v.dtype), v_upd)
    s_ref[...] = spike.astype(v.dtype)


def lif_update_int(v: jax.Array, current: jax.Array, p: LIFIntParams, *,
                   block: tuple[int, int] = DEFAULT_BLOCK,
                   interpret: bool) -> tuple[jax.Array, jax.Array]:
    """Fused int32 LIF step, bit-exact with ``lif_step_int``.

    Pad lanes hold v == 0, current == 0; they are sliced off before
    return, so a non-positive threshold spiking the padding is harmless.
    """
    kernel = functools.partial(_kernel_int, leak_shift=p.leak_shift,
                               v_th=p.v_threshold, v_reset=p.v_reset)
    return _pad_call(kernel, v, current, block, interpret)
