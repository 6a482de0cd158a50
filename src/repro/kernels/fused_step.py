"""Fused per-timestep step megakernel — route + accumulate + Neuron Unit.

The ``"lif"`` engine tier executes every timestep as three
XLA-fused-but-distinct ops: a gather over the lowered op stream
(multicast routing), a segment-sum (per-SPU weight accumulation merged
by the ME tree), and the small Pallas LIF kernel (the centralized
Neuron Unit) — round-tripping the spike plane and synaptic currents
through HBM between each. This module collapses the whole timestep
into ONE ``pallas_call``, mirroring the decoupled-SPU / unified-NU
dataflow SupraSNN implements in hardware (Fig. 7): spikes stream in,
currents accumulate on-chip, membrane state updates in place.

Memory layout (DESIGN.md §10):

* the lowered op stream is **densified** once per engine into a weight
  plane ``W[n_neurons, n_internal]`` with ``W[q, p] = Σ weight`` over
  all (q -> p) synapses, packed to the narrowest signed dtype that
  holds every entry (int8 for the paper's 4-bit MNIST net, int16 for
  the 9-bit SHD net). The synaptic phase is then the exact contraction
  ``current = s_all @ W`` on the MXU's native operand types: int8 x
  int8 with int32 accumulation for an int8 plane, bf16 x bf16 with f32
  accumulation for a plane whose entries and column sums the range
  analysis proves exact in those types. Each tile's sum is cast to
  int32 before it joins the accumulator — identical bits to the
  segment-sum (int32 addition is associative; deterministic-commit
  property, paper §4.2);
* the grid is ``(batch blocks, post blocks, pre blocks)`` with the pre
  (reduction) axis innermost; spike and weight tiles stream through
  VMEM under Pallas's pipelined BlockSpec DMA (each next tile is
  fetched while the current one multiplies — the double-buffered spike
  plane of the hardware's distribution phase). The block is resolved
  from the shapes (:func:`resolve_block`): the whole batch up to 128
  rows, 128 post neurons, and the whole pre axis while the
  double-buffered working set fits :data:`VMEM_BUDGET`, so a timestep
  is a handful of grid steps (7 on the 1535 x 835 SSC plane at B=128);
* partial currents live in an int32 VMEM scratch accumulator; on the
  LAST pre block the Neuron Unit epilogue runs in-register: shift-leak,
  integrate, threshold, reset — one HBM read and one write per state
  element for the whole timestep;
* the membrane-state input is aliased onto the ``v_next`` output
  (``input_output_aliases``), so the donated state buffer is updated
  in place rather than reallocated every step;
* MC packet counts (one packet per fired neuron, the distribution
  phase of the cycle model) are counted from the same streamed spike
  tiles at ``j == 0`` — the fused step emits them for free.

Two Neuron Unit epilogues share that body. A program whose neurons all
share one non-adaptive LIF (:attr:`~repro.core.graph.SNNGraph
.scalar_lif`) runs :func:`fused_step`, with leak, threshold and reset
baked in as constants. Any other program — per-neuron leaks, adaptive
thresholds, subtractive reset, leaky readouts — runs
:func:`fused_step_alif` (``fused_step_alif`` in HLO and the trace): it
takes the :class:`~repro.snn.lif.NeuronParams` rows as one ``[8, bn]``
int32 block per post tile, carries the adaptation ``a`` as a second
state aliased in place like ``v``, and runs
:func:`~repro.snn.lif.alif_step_int` on the last pre tile.

Bit-exactness (spikes, potentials AND packet counts) vs the unfused
tiers is pinned by ``tests/test_fused_kernel.py`` over feedforward +
recurrent graphs, ragged batch sizes, random quantized nets
(hypothesis) and the golden artifact.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.ranges import (dense_column_abs_bound, dense_plane_bounds,
                                   min_safe_dtype, mxu_operand_dtype)
from repro.snn.lif import LIFIntParams, NeuronParams

# The TPU block's working set is held under half of v5e's 16 MiB default
# scoped VMEM, which leaves the compiler room for its own temporaries.
VMEM_BUDGET = 8 * 1024 * 1024
_LANE, _SUBLANE = 128, 8
_MAX_BATCH_BLOCK = 128                  # the MXU's rows: one pass per block

# MXU operand dtype -> the dtype the contraction accumulates in
_ACCUMULATOR = {jnp.dtype(jnp.int8): jnp.int32,
                jnp.dtype(jnp.bfloat16): jnp.float32}

# Densifying the op stream costs n_neurons * n_internal entries; past
# this many bytes the fused tier refuses and the caller should stay on
# the streaming "lif" tier (override via env for big-memory hosts).
MAX_DENSE_BYTES = int(os.environ.get("SUPRASNN_FUSED_MAX_BYTES",
                                     256 * 1024 * 1024))


@dataclasses.dataclass(frozen=True)
class DenseSynapses:
    """The lowered op stream as a packed dense weight plane.

    ``value_min``/``value_max`` are the PROVEN bounds of the folded
    plane (min/max after summing duplicate (pre, post) ops) — the
    facts the range analyzer (:mod:`repro.analysis.ranges`) consumes
    directly instead of re-scanning the dense array. ``operand_dtype``
    is the MXU operand type the analyzer proved exact for this plane;
    :meth:`operand` is the plane in that type, as :func:`fused_step`
    takes it.
    """
    weight: np.ndarray                  # [n_neurons, n_internal], int8/16
    n_neurons: int
    n_internal: int
    value_min: int = 0                  # exact folded-plane bounds
    value_max: int = 0
    operand_dtype: str = "int8"         # "int8" | "bfloat16"

    @property
    def dtype(self) -> np.dtype:
        return self.weight.dtype

    def operand(self) -> jax.Array:
        return jnp.asarray(self.weight, self.operand_dtype)


def pack_dense(lowered) -> DenseSynapses:
    """Densify a :class:`~repro.core.scheduling.LoweredProgram`.

    Sums duplicate (pre, post) ops exactly (int32), then packs to the
    narrowest signed dtype holding every SUMMED entry — the packing
    check runs on the dense plane, not the raw weights, so two int8
    synapses folding into a >int8 entry still pack correctly wider.
    The folded bounds (and the dtype choice they imply) are computed
    by the static range analyzer BEFORE any densification, so the
    size-guard message can already name the dtype the plane would use.
    A plane with no proven exact MXU form
    (:func:`~repro.analysis.ranges.mxu_operand_dtype`) is refused: the
    caller picks ``kernel='lif'``, nothing switches tiers on its own.
    """
    n, m = lowered.n_neurons, lowered.n_internal
    lo, hi = dense_plane_bounds(lowered.op_pre, lowered.op_post_local,
                                lowered.op_weight, n, m)
    col_abs = dense_column_abs_bound(lowered.op_post_local,
                                     lowered.op_weight, m)
    operand = mxu_operand_dtype(lo, hi, col_abs)
    if operand is None:
        raise ValueError(
            f"fused kernel tier has no exact MXU form for this plane "
            f"(values in [{lo}, {hi}], column |sum| up to {col_abs}; "
            f"int8 needs [-128, 127], bf16 needs [-256, 256] and "
            f"|sum| <= 2**24); use kernel='lif' for this graph")
    if n * m * 4 > MAX_DENSE_BYTES:
        raise ValueError(
            f"fused kernel tier would densify {n}x{m} weights "
            f"(> {MAX_DENSE_BYTES} bytes; plane values in [{lo}, {hi}], "
            f"minimal safe dtype {min_safe_dtype(lo, hi)}); use "
            f"kernel='lif' for this graph or raise "
            f"SUPRASNN_FUSED_MAX_BYTES")
    w = np.zeros((n, m), np.int32)
    np.add.at(w, (lowered.op_pre, lowered.op_post_local), lowered.op_weight)
    return DenseSynapses(weight=w.astype(min_safe_dtype(lo, hi)),
                         n_neurons=n, n_internal=m, value_min=lo,
                         value_max=hi, operand_dtype=operand)


# ---------------------------------------------------------------------------
# The kernel body.
# ---------------------------------------------------------------------------

def _synaptic_phase(s_ref, w_ref, acc_ref, pkt_acc_ref, j, k):
    """Accumulate one (pre tile x post tile) contraction and, on the
    first post tile, the pre tile's packets."""
    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((k == 0) & (j == 0))
    def _init_pkt():
        pkt_acc_ref[...] = jnp.zeros_like(pkt_acc_ref)

    # synaptic phase: the 0/1 spike tile times the packed weight tile
    # in the plane's MXU operand type, exact by pack_dense's proof; the
    # tile sum joins the int32 accumulator (== segment-sum == ME tree)
    s_blk = s_ref[...]
    w_blk = w_ref[...]
    part = jax.lax.dot_general(
        s_blk.astype(w_blk.dtype), w_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=_ACCUMULATOR[w_blk.dtype])
    acc_ref[...] += part.astype(jnp.int32)

    # distribution phase: one MC packet per fired neuron; count once
    # per pre tile (j == 0 — the count is independent of the post tile)
    @pl.when(j == 0)
    def _count_packets():
        pkt_acc_ref[...] += jnp.sum((s_blk != 0).astype(jnp.int32),
                                    axis=1, keepdims=True)


def _emit_packets(pkt_ref, pkt_acc_ref, j, k, nk):
    @pl.when((j == 0) & (k == nk - 1))
    def _emit():
        pkt_ref[...] = pkt_acc_ref[...]


def _kernel(s_ref, w_ref, v_ref, v_out_ref, s_out_ref, pkt_ref,
            acc_ref, pkt_acc_ref, *, leak_shift, v_th, v_reset, nk):
    j, k = pl.program_id(1), pl.program_id(2)
    _synaptic_phase(s_ref, w_ref, acc_ref, pkt_acc_ref, j, k)

    # Neuron Unit epilogue on the last pre tile: shift-leak, integrate,
    # threshold, reset — in-register, one state read + one write
    @pl.when(k == nk - 1)
    def _neuron_unit():
        v = v_ref[...]
        v_upd = (v - jax.lax.shift_right_arithmetic(
            v, jnp.int32(leak_shift))) + acc_ref[...]
        spike = v_upd >= v_th
        v_out_ref[...] = jnp.where(spike, jnp.asarray(v_reset, v.dtype),
                                   v_upd)
        s_out_ref[...] = spike.astype(jnp.int32)

    _emit_packets(pkt_ref, pkt_acc_ref, j, k, nk)


def _kernel_alif(s_ref, w_ref, v_ref, a_ref, p_ref, v_out_ref, a_out_ref,
                 s_out_ref, pkt_ref, acc_ref, pkt_acc_ref, *, nk):
    j, k = pl.program_id(1), pl.program_id(2)
    _synaptic_phase(s_ref, w_ref, acc_ref, pkt_acc_ref, j, k)

    # per-neuron Neuron Unit epilogue on the last pre tile
    # (alif_step_int): each parameter row broadcast over the batch rows
    @pl.when(k == nk - 1)
    def _neuron_unit():
        v, a = v_ref[...], a_ref[...]
        row = lambda f: jnp.broadcast_to(
            p_ref[pl.ds(_ROW[f], 1), :], v.shape)
        u = v - jax.lax.shift_right_arithmetic(v, row("leak_shift")) \
            + acc_ref[...]
        th = row("v_threshold") + a
        spike = u >= th
        v_out_ref[...] = jnp.where(
            spike, jnp.where(row("subtractive") != 0, u - th,
                             row("v_reset")), u)
        a_out_ref[...] = a - jax.lax.shift_right_arithmetic(
            a, row("adapt_shift")) + jnp.where(spike, row("adapt_inc"), 0)
        s_out_ref[...] = spike.astype(jnp.int32)

    _emit_packets(pkt_ref, pkt_acc_ref, j, k, nk)


# NeuronParams field -> its row in the packed [8, n] parameter block
_ROW = {f: i for i, f in enumerate(NeuronParams._fields)}


def _check_plane(weight: jax.Array):
    if weight.dtype not in _ACCUMULATOR:
        raise TypeError(
            f"fused_step contracts an int8 or bfloat16 weight plane on "
            f"the MXU, got {weight.dtype}; pass pack_dense(...).operand() "
            f"or use kernel='lif'")


# ---------------------------------------------------------------------------
# The block.
# ---------------------------------------------------------------------------

def _cdiv(n: int, d: int) -> int:
    return -(-n // d)


def _split(n: int, cap: int, align: int) -> int:
    """The block, a multiple of ``align`` no wider than ``cap`` (itself a
    multiple of ``align``), that covers ``n`` in the fewest blocks with
    the least padding."""
    return _cdiv(_cdiv(n, _cdiv(n, cap)), align) * align


def vmem_bytes(block: tuple[int, int, int], n_state: int, operand) -> int:
    """VMEM the kernel takes at ``block = (bb, bn, bk)`` with ``n_state``
    state arrays (``v``, or ``v`` and ``a``) and the plane in ``operand``.

    Pallas double-buffers every pipelined block: the spike and weight
    blocks, the states in, the states and spikes out, the ``[8, bn]``
    parameter rows (the per-neuron kernel's; counted for both) and the
    packet column, which fills a whole lane row. The two scratch
    accumulators are single."""
    bb, bn, bk = block
    i32 = 4
    pipelined = (bb * bk * i32 + bk * bn * jnp.dtype(operand).itemsize
                 + (2 * n_state + 1) * bb * bn * i32 + 8 * bn * i32
                 + bb * _LANE * i32)
    return 2 * pipelined + bb * bn * i32 + bb * _LANE * i32


def resolve_block(batch: int, n_all: int, n_state: int,
                  operand) -> tuple[int, int, int]:
    """The ``(bb, bn, bk)`` block of the kernel on the TPU, from shapes.

    ``bb`` covers the batch in the fewest blocks of at most 128 rows (a
    multiple of 8); ``bn`` is 128 whatever the post axis, so the next
    post tile's weight slab is fetched while the current one multiplies,
    and the spike block, whose index does not change with the post tile,
    stays resident (on a v5e, post blocks up to the whole axis timed
    within a few percent of it);
    ``bk`` is the whole pre axis (padded to 128) while
    :func:`vmem_bytes` stays under :data:`VMEM_BUDGET`, so the
    reduction, the epilogue and the state DMA run once per (batch,
    post) block. A plane too wide for that splits its pre axis into the
    fewest blocks that fit."""
    bb = _split(batch, _MAX_BATCH_BLOCK, _SUBLANE)
    bn = _LANE
    fixed = vmem_bytes((bb, bn, 0), n_state, operand)
    per_lane = vmem_bytes((bb, bn, _LANE), n_state, operand) - fixed
    widest = max(1, (VMEM_BUDGET - fixed) // per_lane) * _LANE
    return bb, bn, _split(n_all, widest, _LANE)


def step_block(batch: int, n_all: int, n_int: int, n_state: int, operand,
               *, interpret: bool) -> tuple[int, int, int]:
    """The block ``block=None`` resolves to: :func:`resolve_block` on the
    TPU; ONE full-array tile under interpret mode, where the interpreter
    walks the grid in Python, so on CPU the kernel lowers to one XLA dot
    and epilogue instead of hundreds of emulated DMA steps."""
    if interpret:
        return batch, n_int, n_all
    return resolve_block(batch, n_all, n_state, operand)


def step_grid(batch: int, n_all: int, n_int: int,
              block: tuple[int, int, int]) -> tuple[int, int, int]:
    """Grid steps per timestep ``(nb, nj, nk)``: batch, post and pre
    blocks, the shapes padded up to ``block``."""
    bb, bn, bk = block
    return _cdiv(batch, bb), _cdiv(n_int, bn), _cdiv(n_all, bk)


def _padded(s_all, weight, states, block, interpret):
    """The resolved block, the spike plane, states and weights padded to
    it, and the grid ``(nb, nj, nk)``."""
    b, n_all = s_all.shape
    n_int = states[0].shape[1]
    if block is None:
        block = step_block(b, n_all, n_int, len(states), weight.dtype,
                           interpret=interpret)
    bb, bn, bk = block
    sp = jnp.pad(s_all, ((0, -b % bb), (0, -n_all % bk)))
    xs = [jnp.pad(x, ((0, -b % bb), (0, -n_int % bn))) for x in states]
    wp = jnp.pad(weight, ((0, -n_all % bk), (0, -n_int % bn)))
    return block, sp, xs, wp, step_grid(b, n_all, n_int, block)


def fused_step(s_all: jax.Array, v: jax.Array, weight: jax.Array,
               p: LIFIntParams, *,
               block: tuple[int, int, int] | None = None,
               interpret: bool
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One fused timestep: ``(v_next, spikes, packet_counts)``.

    s_all:  [B, n_neurons] int32 spike plane (external ‖ internal t-1).
    v:      [B, n_internal] int32 membrane state — aliased onto the
            ``v_next`` output, so pass a donated/owned buffer.
    weight: [n_neurons, n_internal] dense plane in its MXU operand
            type, int8 or bfloat16 (``pack_dense(...).operand()``,
            which proves the contraction exact in that type).
    interpret: run the Pallas interpreter (CPU tests) instead of
            compiling for the TPU; required, so no caller on the chip
            interprets by leaving it out.

    ``block=None`` resolves per backend (:func:`step_block`): on the
    TPU the block :func:`resolve_block` sizes from the batch, the plane
    and the VMEM budget (the whole batch up to 128 rows, 128 post
    neurons, the whole pre axis while it fits); under interpret mode
    ONE full-array tile (grid ``(1, 1, 1)``). An explicit ``block``
    wins. Tiling only changes the visit order of an associative int32
    reduction, so every block choice is bit-exact (pinned in
    tests/test_fused_kernel.py).

    Pad lanes are all-zero spikes / zero weights / zero potentials:
    they contribute nothing to real currents and are sliced off before
    return, so a non-positive threshold spiking the padding is
    harmless (same rule as ``lif_update_int``).
    """
    _check_plane(weight)
    b, n_int = v.shape
    block, sp, (vp,), wp, grid = _padded(s_all, weight, [v], block,
                                         interpret)
    bb, bn, bk = block
    kernel = functools.partial(_kernel, leak_shift=p.leak_shift,
                               v_th=p.v_threshold, v_reset=p.v_reset,
                               nk=grid[2])
    v_next, s_out, pkt = pl.pallas_call(
        kernel,
        grid=grid,                      # pre (reduction) axis innermost
        in_specs=[pl.BlockSpec((bb, bk), lambda i, j, k: (i, k)),
                  pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
                  pl.BlockSpec((bb, bn), lambda i, j, k: (i, j))],
        out_specs=[pl.BlockSpec((bb, bn), lambda i, j, k: (i, j)),
                   pl.BlockSpec((bb, bn), lambda i, j, k: (i, j)),
                   pl.BlockSpec((bb, 1), lambda i, j, k: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(vp.shape, jnp.int32),
                   jax.ShapeDtypeStruct(vp.shape, jnp.int32),
                   jax.ShapeDtypeStruct((sp.shape[0], 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.int32),
                        pltpu.VMEM((bb, 1), jnp.int32)],
        input_output_aliases={2: 0},    # v updates in place (donation)
        interpret=interpret,
        name="fused_step",              # the kernel's name in HLO/traces
    )(sp, wp, vp)
    return v_next[:b, :n_int], s_out[:b, :n_int], pkt[:b, 0]


def fused_step_alif(s_all: jax.Array, v: jax.Array, a: jax.Array,
                    weight: jax.Array, params: jax.Array, *,
                    block: tuple[int, int, int] | None = None,
                    interpret: bool
                    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One fused timestep with the per-neuron Neuron Unit:
    ``(v_next, a_next, spikes, packet_counts)``.

    As :func:`fused_step`, plus ``a`` ([B, n_internal] int32 adaptation,
    aliased onto ``a_next`` like ``v`` onto ``v_next``) and ``params``,
    the ``[8, n_internal]`` int32 :meth:`~repro.snn.lif.NeuronParams
    .packed` rows, read as one ``[8, bn]`` block per post tile. The
    epilogue is :func:`~repro.snn.lif.alif_step_int`, bit for bit. Pad
    lanes carry zero parameters and zero state and are sliced off.
    """
    _check_plane(weight)
    b, n_int = v.shape
    block, sp, (vp, ap), wp, grid = _padded(s_all, weight, [v, a], block,
                                            interpret)
    bb, bn, bk = block
    pp = jnp.pad(params, ((0, 0), (0, -n_int % bn)))
    state = pl.BlockSpec((bb, bn), lambda i, j, k: (i, j))
    v_next, a_next, s_out, pkt = pl.pallas_call(
        functools.partial(_kernel_alif, nk=grid[2]),
        grid=grid,                      # pre (reduction) axis innermost
        in_specs=[pl.BlockSpec((bb, bk), lambda i, j, k: (i, k)),
                  pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
                  state, state,
                  pl.BlockSpec((8, bn), lambda i, j, k: (0, j))],
        out_specs=[state, state, state,
                   pl.BlockSpec((bb, 1), lambda i, j, k: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(vp.shape, jnp.int32),
                   jax.ShapeDtypeStruct(vp.shape, jnp.int32),
                   jax.ShapeDtypeStruct(vp.shape, jnp.int32),
                   jax.ShapeDtypeStruct((sp.shape[0], 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.int32),
                        pltpu.VMEM((bb, 1), jnp.int32)],
        input_output_aliases={2: 0, 3: 1},   # v and a update in place
        interpret=interpret,
        name="fused_step_alif",         # the kernel's name in HLO/traces
    )(sp, wp, vp, ap, pp)
    return v_next[:b, :n_int], a_next[:b, :n_int], s_out[:b, :n_int], \
        pkt[:b, 0]
