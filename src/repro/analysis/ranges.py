"""Integer range analysis over the scheduled op stream.

Computes, WITHOUT executing any engine, sound worst-case intervals for
every integer quantity the execution tiers manipulate:

* per-synapse weights vs the signed ``weight_bits`` Unified-Memory
  field (RANGE001);
* the folded dense weight plane ``W[q, p] = Σ weight`` that
  :func:`repro.kernels.fused_step.pack_dense` builds — proving the
  int8/int16 dtype choice (the paper's 4-bit MNIST / 9-bit SHD nets)
  before any densification happens, and the MXU operand form (int8,
  or bf16 with f32 accumulation) in which the fused kernel's
  contraction over that plane is exact;
* the per-post synaptic accumulator and membrane potential of the
  integer LIF (``v' = leak(v) + I``, spike iff ``v' >= th`` then
  reset), and with per-neuron parameters the adaptation ``a`` and the
  threshold ``v_th + a`` of the adaptive LIF (:func:`neuron_bounds`),
  proving the int32 accumulation in every engine and in the fused
  megakernel cannot overflow — or naming the offending neuron and the
  minimal safe width (RANGE002).

The membrane bounds are a closed-form fixpoint of the reset dynamics
(DESIGN.md §13 derives both):

* upper: the carried (post-commit) state never exceeds
  ``carried_hi = max(v_reset, 0, v_threshold - 1)`` — a spiking step
  resets, a non-spiking one leaves ``v' <= th - 1``, and the initial
  state is 0 — so the pre-threshold peak is bounded by
  ``leak(carried_hi) + pos[p]`` with ``pos[p] = Σ max(w, 0)`` over
  ``p``'s in-synapses (all pres firing at once);
* lower: ``lo[p] = min(0, v_reset, neg[p] * 2**leak_shift)`` is an
  inductive invariant — the arithmetic-shift leak contracts a negative
  state by at least ``2**-leak_shift`` of itself, so
  ``leak(lo) + neg >= lo`` exactly when ``lo <= neg * 2**leak_shift``.
  At ``leak_shift = 0`` the leak zeroes the state and both collapse to
  one-step sums.

:func:`neuron_bounds` applies both per neuron, with its own shifts,
and extends them to adaptation and subtractive reset.

Extremes are finished in exact Python ints (numpy int64 only carries
the per-post partial sums, which are safe for any graph the pipeline
can represent). This module imports ONLY numpy at runtime —
``kernels/fused_step.py`` imports :func:`min_safe_dtype` from here for
its guard message, so this must stay below the jax layer.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, Any

import numpy as np
import numpy.typing as npt

from repro.analysis.diagnostics import (Diagnostic, Location, Severity,
                                        register_code)

if TYPE_CHECKING:
    from repro.core.graph import SNNGraph
    from repro.core.memory_model import HardwareConfig
    from repro.core.scheduling.tables import OpTables
    from repro.snn.lif import NeuronParams

NOP = -1

RANGE001 = register_code(
    "RANGE001", "weight outside the signed weight_bits field")
RANGE002 = register_code(
    "RANGE002", "accumulator interval exceeds the int32 engine width")

INT32_LO, INT32_HI = -(2 ** 31), 2 ** 31 - 1


def signed_bits(lo: int, hi: int) -> int:
    """Smallest signed bit-width holding every value in [lo, hi]."""
    b = 1
    while not (-(1 << (b - 1)) <= lo and hi <= (1 << (b - 1)) - 1):
        b += 1
    return b


def min_safe_dtype(lo: int, hi: int) -> str:
    """Narrowest signed numpy dtype name holding [lo, hi] (the
    ``pack_dense`` ladder: int8 -> int16 -> int32 -> int64)."""
    b = signed_bits(int(lo), int(hi))
    for width in (8, 16, 32, 64):
        if b <= width:
            return f"int{width}"
    return f"int{b}"                     # unrepresentable in numpy; name it


# every integer of magnitude <= 2**8 is a bfloat16 value, and an f32 sum
# of integers is exact while every partial sum stays within 2**24
BF16_EXACT_INT = 2 ** 8
F32_EXACT_INT = 2 ** 24


def mxu_operand_dtype(lo: int, hi: int, col_abs_max: int) -> str | None:
    """Operand dtype in which the fused kernel's ``spikes @ W`` is exact.

    ``[lo, hi]`` bounds the folded plane and ``col_abs_max`` bounds
    ``Σ_q |W[q, p]|`` over every column. Spikes are 0/1, so:

    * ``"int8"`` — the plane fits int8; the MXU multiplies int8 x int8
      and accumulates in int32;
    * ``"bfloat16"`` — every entry lies in ``[-2**8, 2**8]`` (exact bf16
      values) and no f32 partial sum can pass ``col_abs_max <= 2**24``,
      whatever the tiling or the MXU's summation order;
    * ``None`` — no exact MXU form is proven for this plane.
    """
    if min_safe_dtype(lo, hi) == "int8":
        return "int8"
    if (-BF16_EXACT_INT <= lo and hi <= BF16_EXACT_INT
            and col_abs_max <= F32_EXACT_INT):
        return "bfloat16"
    return None


def dense_column_abs_bound(op_post_local: npt.NDArray[Any],
                           op_weight: npt.NDArray[Any],
                           n_internal: int) -> int:
    """Upper bound on ``Σ_q |W[q, p]|`` over the columns of the folded
    plane: the largest per-post sum of ``|w|`` over its in-synapses."""
    if not len(op_weight):
        return 0
    col = np.zeros(int(n_internal), np.int64)
    np.add.at(col, np.asarray(op_post_local, np.int64),
              np.abs(np.asarray(op_weight, np.int64)))
    return int(col.max())


def dense_plane_bounds(op_pre: npt.NDArray[Any], op_post_local: npt.NDArray[Any],
                       op_weight: npt.NDArray[Any], n_neurons: int,
                       n_internal: int) -> tuple[int, int]:
    """Exact (min, max) of the folded dense plane ``W[q, p] = Σ w``.

    Group-sums the op stream by (pre, post) WITHOUT allocating the
    ``n_neurons x n_internal`` plane, so the bound is computable for
    graphs far past ``SUPRASNN_FUSED_MAX_BYTES``. Cells with no
    synapse hold an implicit 0, included whenever the plane is not
    fully dense.
    """
    w = np.asarray(op_weight, np.int64)
    n_cells = int(n_neurons) * int(n_internal)
    if not len(w):
        return (0, 0)
    key = (np.asarray(op_pre, np.int64) * n_internal
           + np.asarray(op_post_local, np.int64))
    order = np.argsort(key, kind="stable")
    ks = key[order]
    starts = np.flatnonzero(np.r_[True, ks[1:] != ks[:-1]])
    sums = np.add.reduceat(w[order], starts)
    lo, hi = int(sums.min()), int(sums.max())
    if len(starts) < n_cells:            # implicit zero cells exist
        lo, hi = min(lo, 0), max(hi, 0)
    return lo, hi


def post_current_bounds(post_local: npt.NDArray[Any],
                        weight: npt.NDArray[Any], n_internal: int
                        ) -> tuple[npt.NDArray[np.int64], npt.NDArray[np.int64]]:
    """Per-post one-step current interval ``[neg, pos]``: the sums of the
    negative and of the positive weights into each internal neuron (all
    its pres firing at once)."""
    pos = np.zeros(int(n_internal), np.int64)
    neg = np.zeros(int(n_internal), np.int64)
    pl = np.asarray(post_local, np.int64)
    w = np.asarray(weight, np.int64)
    np.add.at(pos, pl, np.maximum(w, 0))
    np.add.at(neg, pl, np.minimum(w, 0))
    return pos, neg


def neuron_bounds(pos: npt.NDArray[Any], neg: npt.NDArray[Any],
                  params: "NeuronParams") -> dict[str, Any]:
    """Proven intervals of the Neuron Unit's state under ``params``
    (:func:`repro.snn.lif.alif_step_int`), from the per-post currents.

    Per neuron, with ``A = adapt_inc << adapt_shift``: the adaptation
    stays in ``[0, A]`` (``leak(A) + adapt_inc <= A``) and the threshold
    in ``[v_th, v_th + A]``. The carried potential is at most ``C``:

    * ``pos << leak_shift`` where that is below ``v_th``: no reset can
      happen, and the leaky integrator's fixpoint never reaches the
      threshold (a readout at ``NEVER_FIRES``);
    * else ``max(0, v_reset, v_th + A - 1)`` for a value reset;
    * else ``max(0, v_th + A - 1, (pos - v_th) << leak_shift)`` for a
      subtractive one (``u - th <= leak(C) + pos - v_th <= C``).

    The pre-threshold peak is ``leak(C) + pos``; the floor is the module
    docstring's ``min(0, v_reset, neg << leak_shift)`` (a subtractive
    reset leaves ``u - th >= 0``, so drops ``v_reset``). Exact Python
    ints throughout. With every neuron alike the bounds are monotone in
    ``pos`` and ``neg``, so only their extreme neurons are evaluated.
    """
    n = len(pos)
    out = {"current_lo": 0, "current_hi": 0, "membrane_lo": 0,
           "membrane_hi": 0, "adapt_hi": 0, "threshold_lo": 0,
           "threshold_hi": 0, "post_hi": 0, "post_lo": 0}
    if not n:
        return out
    if all((x == x[0]).all() for x in params):
        cand = sorted({int(np.argmax(pos)), int(np.argmin(neg))})
    else:
        cand = range(n)
    hi = lo = th_hi = th_lo = a_hi = None
    for j in cand:
        ls, th, rst, ash, inc, sub = (int(x[j]) for x in params)
        p, q = int(pos[j]), int(neg[j])
        a_max = inc << ash
        quiet = max(0, p << ls)
        if quiet < th:
            c = quiet
        elif sub:
            c = max(0, th + a_max - 1, (p - th) << ls)
        else:
            c = max(0, rst, th + a_max - 1)
        u_hi = c - (c >> ls) + p
        v_lo = min(0, q << ls) if sub else min(0, rst, q << ls)
        if hi is None or u_hi > hi[0]:
            hi = (u_hi, j)
        if lo is None or v_lo < lo[0]:
            lo = (v_lo, j)
        th_hi = max(th + a_max, th_hi if th_hi is not None else th + a_max)
        th_lo = min(th, th_lo if th_lo is not None else th)
        a_hi = max(a_max, a_hi if a_hi is not None else a_max)
    out.update(current_lo=int(neg.min()), current_hi=int(pos.max()),
               membrane_lo=lo[0], membrane_hi=hi[0], adapt_hi=a_hi,
               threshold_lo=th_lo, threshold_hi=th_hi, post_hi=hi[1],
               post_lo=lo[1])
    return out


def int32_violation(b: dict[str, Any]) -> tuple[str, int] | None:
    """The first quantity of :func:`neuron_bounds`' result ``b`` (with
    ``acc_lo``/``acc_hi``) that leaves int32, as ``(what, local post)``;
    ``None`` when every one fits."""
    if b["acc_lo"] < INT32_LO or b["acc_hi"] > INT32_HI:
        return (f"accumulator interval [{b['acc_lo']}, {b['acc_hi']}]",
                b["post_hi"] if b["acc_hi"] > INT32_HI else b["post_lo"])
    if b["threshold_lo"] < INT32_LO or b["threshold_hi"] > INT32_HI:
        return (f"adaptive threshold interval [{b['threshold_lo']}, "
                f"{b['threshold_hi']}]", b["post_hi"])
    return None


def neuron_state_facts(post_local: npt.NDArray[Any],
                       weight: npt.NDArray[Any], params: "NeuronParams"
                       ) -> dict[str, Any]:
    """:func:`neuron_bounds` of a synapse list, with the accumulator
    interval ``acc_lo``/``acc_hi`` (the potential and one step's
    current)."""
    pos, neg = post_current_bounds(post_local, weight, params.n)
    b = neuron_bounds(pos, neg, params)
    b["acc_lo"] = min(b["membrane_lo"], b["current_lo"])
    b["acc_hi"] = max(b["membrane_hi"], b["current_hi"])
    return b


def prove_neuron_state(post_local: npt.NDArray[Any],
                       weight: npt.NDArray[Any], params: "NeuronParams"
                       ) -> dict[str, Any]:
    """:func:`neuron_state_facts`, refusing with ``ValueError`` (naming
    the neuron) where the int32 Neuron Unit could overflow: ``compile``
    calls this for per-neuron programs, whose kernel takes the proof as
    its precondition."""
    b = neuron_state_facts(post_local, weight, params)
    bad = int32_violation(b)
    if bad is not None:
        raise ValueError(
            f"{bad[0]} of internal neuron {bad[1]} exceeds int32: the "
            f"Neuron Unit's state could overflow; shrink the weights, "
            f"thresholds or adaptation (adapt_inc << adapt_shift)")
    return b


def check_ranges(g: "SNNGraph", hw: "HardwareConfig", tables: "OpTables"
                 ) -> tuple[list[Diagnostic], dict[str, Any]]:
    """RANGE diagnostics + the proven interval facts for (g, hw, tables).

    Folds from the TABLES (not the lowered program), so hand-edited
    artifacts are analyzed as they would execute after re-lowering.
    """
    out: list[Diagnostic] = []
    n, n_int = int(g.n_neurons), int(g.n_internal)
    valid = tables.pre != NOP
    spu_i, slot_i = np.nonzero(valid)
    pre_v = tables.pre[spu_i, slot_i].astype(np.int64)
    post_v = tables.post[spu_i, slot_i].astype(np.int64)
    w_v = tables.weight[spu_i, slot_i].astype(np.int64)
    in_range = ((pre_v >= 0) & (pre_v < n)
                & (post_v >= g.n_inputs) & (post_v < n))
    pre_v, post_v, w_v = pre_v[in_range], post_v[in_range], w_v[in_range]
    idx = np.flatnonzero(valid.ravel())[in_range]

    # -- RANGE001: every weight representable in the signed UM field --------
    ww = int(hw.weight_bits)
    w_lo, w_hi = -(1 << (ww - 1)), (1 << (ww - 1)) - 1
    bad = (w_v < w_lo) | (w_v > w_hi)
    if bad.any():
        i = int(np.argmax(bad))
        s, t = divmod(int(idx[i]), tables.pre.shape[1])
        out.append(Diagnostic(
            code=RANGE001, severity=Severity.ERROR,
            message=(f"weight {int(w_v[i])} of synapse "
                     f"({int(pre_v[i])} -> {int(post_v[i])}) outside the "
                     f"signed {ww}-bit range [{w_lo}, {w_hi}]; needs "
                     f"{signed_bits(int(w_v.min()), int(w_v.max()))} bits"),
            location=Location(spu=s, slot=t, pre=int(pre_v[i]),
                              post=int(post_v[i]), field="hw.weight_bits"),
            hint="raise HardwareConfig.weight_bits or requantize",
            count=int(bad.sum())))

    # -- per-post one-step current interval and the Neuron Unit's state ---
    pl = (post_v - g.n_inputs).astype(np.int64)
    b = neuron_state_facts(pl, w_v, g.neurons)
    acc_lo, acc_hi = b["acc_lo"], b["acc_hi"]
    acc_bits = signed_bits(acc_lo, acc_hi)
    overflow = int32_violation(b)
    if overflow is not None:
        out.append(Diagnostic(
            code=RANGE002, severity=Severity.ERROR,
            message=(f"{overflow[0]} of post {overflow[1] + g.n_inputs} "
                     f"exceeds int32; minimal safe width is {acc_bits} "
                     f"bits ({min_safe_dtype(acc_lo, acc_hi)})"),
            location=Location(post=overflow[1] + g.n_inputs),
            hint="shrink weights/fan-in or widen the engine accumulator",
            count=1))

    # -- dense-plane dtype proof (the pack_dense choice) --------------------
    d_lo, d_hi = dense_plane_bounds(pre_v, pl, w_v, n, n_int)
    col_abs = dense_column_abs_bound(pl, w_v, n_int)
    stats: dict[str, Any] = {
        "weight_lo": int(w_v.min()) if len(w_v) else 0,
        "weight_hi": int(w_v.max()) if len(w_v) else 0,
        "weight_bits_needed": (signed_bits(int(w_v.min()), int(w_v.max()))
                               if len(w_v) else 1),
        "dense_lo": d_lo, "dense_hi": d_hi,
        "dense_dtype": min_safe_dtype(d_lo, d_hi),
        "mxu_operand": mxu_operand_dtype(d_lo, d_hi, col_abs),
        "current_lo": b["current_lo"], "current_hi": b["current_hi"],
        "membrane_lo": b["membrane_lo"], "membrane_hi": b["membrane_hi"],
        "adapt_hi": b["adapt_hi"], "threshold_hi": b["threshold_hi"],
        "acc_lo": acc_lo, "acc_hi": acc_hi, "acc_bits": acc_bits,
        "int32_safe": overflow is None,
    }
    return out, stats
