"""Block-sparse spike-accumulation Pallas kernel.

TPU-native adaptation of SupraSNN's synapse-level parallelism (DESIGN.md §3):

* the paper's per-event skip (operation tables only hold nonzero synapses,
  SPUs idle on non-spiking pres) becomes a per-BLOCK skip — the MXU is a
  dense 128x128 systolic array, so the profitable granularity of
  event-sparsity on TPU is a VMEM tile, not a scalar;
* the MC-tree routing bitstring becomes the block-occupancy predicate
  (`any spike in this pre-tile?`) evaluated inside the kernel; a dead tile
  skips the weight MAC entirely;
* the ME-tree deterministic merge is the sequential accumulation over the
  minormost grid dimension — a fixed-order reduction, bit-identical run
  to run, exactly the paper's deterministic-commit guarantee.

Grid: (batch_blocks, post_blocks, pre_blocks); pre is minormost so each
(i, j) output tile accumulates its pre-tiles in a fixed sequential order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_B = 8
DEFAULT_BLOCK_PRE = 128
DEFAULT_BLOCK_POST = 128


def _kernel(s_ref, w_ref, o_ref, *, acc_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    s = s_ref[...]
    # MC-tree analogue: OR-reduce the spike tile; skip dead weight tiles.
    any_spike = jnp.any(s != 0)

    @pl.when(any_spike)
    def _mac():
        o_ref[...] += jnp.dot(s.astype(acc_dtype),
                              w_ref[...].astype(acc_dtype),
                              preferred_element_type=acc_dtype)


def spike_accum(spikes: jax.Array, weights: jax.Array, *,
                block_b: int = DEFAULT_BLOCK_B,
                block_pre: int = DEFAULT_BLOCK_PRE,
                block_post: int = DEFAULT_BLOCK_POST,
                interpret: bool) -> jax.Array:
    """I = S @ W with block-level spike sparsity skipping.

    spikes [B, N_pre], weights [N_pre, N_post] -> [B, N_post].
    Inputs are padded to block multiples; output unpadded. f32/bf16 inputs
    accumulate in f32; integer inputs accumulate in int32 (bit-exact with
    the quantized-hardware oracle).
    """
    b, n_pre = spikes.shape
    n_pre_w, n_post = weights.shape
    assert n_pre == n_pre_w, (spikes.shape, weights.shape)

    integer = jnp.issubdtype(weights.dtype, jnp.integer)
    acc_dtype = jnp.int32 if integer else jnp.float32

    pb = -b % block_b
    pk = -n_pre % block_pre
    pn = -n_post % block_post
    s = jnp.pad(spikes, ((0, pb), (0, pk)))
    w = jnp.pad(weights, ((0, pk), (0, pn)))

    grid = (s.shape[0] // block_b, w.shape[1] // block_post,
            s.shape[1] // block_pre)
    out = pl.pallas_call(
        functools.partial(_kernel, acc_dtype=acc_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_pre), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_pre, block_post), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_post), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((s.shape[0], w.shape[1]), acc_dtype),
        interpret=interpret,
    )(s, w)
    return out[:b, :n_post]
