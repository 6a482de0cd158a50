"""jit'd public wrappers around the Pallas kernels.

``interpret`` is a required keyword: the kernels TARGET TPU (BlockSpec
VMEM tiling, MXU-aligned tiles) and are validated in interpret mode on
CPU (see tests/test_kernels.py), and a caller must say which one it
wants — nothing falls back to the interpreter on its own.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.lif_update import lif_update as _lif_update
from repro.kernels.spike_accum import spike_accum as _spike_accum


@functools.partial(jax.jit, static_argnames=("block_b", "block_pre",
                                             "block_post", "interpret"))
def spike_accum(spikes, weights, *, block_b=8, block_pre=128, block_post=128,
                interpret):
    return _spike_accum(spikes, weights, block_b=block_b,
                        block_pre=block_pre, block_post=block_post,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("alpha", "v_th", "v_reset",
                                             "block", "interpret"))
def lif_update(v, current, *, alpha, v_th=1.0, v_reset=0.0, block=(8, 128),
               interpret):
    return _lif_update(v, current, alpha=alpha, v_th=v_th, v_reset=v_reset,
                       block=block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("p", "block", "interpret"))
def lif_update_int(v, current, p, *, block=(8, 128), interpret):
    from repro.kernels.lif_update import lif_update_int as _lif_update_int
    return _lif_update_int(v, current, p, block=block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w_log, u, state0, *, chunk=64, interpret):
    from repro.kernels.wkv6 import wkv6_pallas
    return wkv6_pallas(r, k, v, w_log, u, state0, chunk=chunk,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, a_log, b, c, state0, *, chunk=64, interpret):
    from repro.kernels.ssd import ssd_pallas
    return ssd_pallas(x, dt, a_log, b, c, state0, chunk=chunk,
                      interpret=interpret)
