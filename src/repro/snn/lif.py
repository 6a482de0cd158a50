"""Discrete-time LIF neuron dynamics (paper Eqs. (2)-(5)) with surrogate gradients.

The float path is used for BPTT training (snnTorch-equivalent); the integer
path (`lif_step_int`) is the bit-exact oracle the SupraSNN engine must match
(deterministic-commit property).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class LIFParams(NamedTuple):
    """Neuron-model constants (paper Table 2)."""
    alpha: float = 0.25        # leak factor; (1 - alpha) V + I
    v_threshold: float = 1.0
    v_reset: float = 0.0


# ---------------------------------------------------------------------------
# Surrogate-gradient spike functions (paper Table 2: ReLU for MNIST, Sigmoid
# for SHD).  Forward is the hard Heaviside of Eq. (4); backward replaces the
# Dirac delta with a smooth/piecewise surrogate.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def spike_fn(v_minus_th: jax.Array, surrogate: str = "relu") -> jax.Array:
    return (v_minus_th >= 0.0).astype(v_minus_th.dtype)


def _spike_fwd(v_minus_th, surrogate):
    return spike_fn(v_minus_th, surrogate), v_minus_th


def _spike_bwd(surrogate, v_minus_th, g):
    if surrogate == "relu":
        # Triangle ("ReLU of 1-|x|") surrogate.
        surr = jnp.maximum(0.0, 1.0 - jnp.abs(v_minus_th))
    elif surrogate == "sigmoid":
        k = 4.0
        s = jax.nn.sigmoid(k * v_minus_th)
        surr = k * s * (1.0 - s)
    elif surrogate == "fast_sigmoid":
        k = 10.0
        surr = 1.0 / (1.0 + k * jnp.abs(v_minus_th)) ** 2
    else:  # pragma: no cover - guarded by config validation
        raise ValueError(f"unknown surrogate {surrogate!r}")
    return (g * surr,)


spike_fn.defvjp(_spike_fwd, _spike_bwd)


def lif_step(v: jax.Array, current: jax.Array, p: LIFParams,
             surrogate: str = "relu") -> tuple[jax.Array, jax.Array]:
    """One LIF timestep. Returns (v_next, spikes).

    Eq. (2): V_upd = (1 - alpha) V + I
    Eq. (4): S = [V_upd >= V_th]
    Eq. (5): V_next = V_reset if S else V_upd
    """
    v_upd = (1.0 - p.alpha) * v + current
    s = spike_fn(v_upd - p.v_threshold, surrogate)
    v_next = jnp.where(s > 0, p.v_reset, v_upd)
    return v_next, s


# ---------------------------------------------------------------------------
# Integer (quantized-hardware) oracle. SupraSNN implements the leak with a
# programmable right shift: (1 - alpha) V  ==  V - (V >> shift).
# All arithmetic is int32; this is the reference the cycle engine and the
# mapped executor must reproduce BIT-EXACTLY.
# ---------------------------------------------------------------------------

class LIFIntParams(NamedTuple):
    leak_shift: int            # alpha approximated as 2**-leak_shift
    v_threshold: int
    v_reset: int


def leak_int(v: np.ndarray | jax.Array, shift: int):
    """V - (V >> shift), arithmetic shift (matches RTL two's-complement)."""
    if isinstance(v, np.ndarray):
        return v - (v >> shift)
    return v - jax.lax.shift_right_arithmetic(v, jnp.int32(shift))


def lif_step_int(v, current, p: LIFIntParams):
    """Integer LIF step. Works for both numpy and jnp int32 arrays."""
    xp = np if isinstance(v, np.ndarray) else jnp
    v_upd = leak_int(v, p.leak_shift) + current
    s = (v_upd >= p.v_threshold)
    v_next = xp.where(s, xp.asarray(p.v_reset, dtype=v_upd.dtype), v_upd)
    return v_next, s.astype(xp.int32)


def alpha_to_shift(alpha: float) -> int:
    """Nearest power-of-two approximation of the leak factor (paper §5)."""
    return int(round(-np.log2(alpha)))


# ---------------------------------------------------------------------------
# Per-neuron Neuron Unit parameters and the integer adaptive-LIF step
# (Bellec et al., NeurIPS 2018; the ALIF SRNN of Yin et al., 2021). Every
# internal neuron j carries its own shifts, threshold, reset and
# adaptation; the scalar LIFIntParams is the uniform, non-adaptive case.
# ---------------------------------------------------------------------------

#: the threshold of a neuron that never fires (a leaky readout): the
#: range proof shows its potential stays below it
NEVER_FIRES = 2 ** 31 - 1

_SHIFT_FIELDS = ("leak_shift", "adapt_shift")


class NeuronParams(NamedTuple):
    """int32 vectors over the internal neurons, one entry per neuron.

    The step (:func:`alif_step_int`) carries ``v`` and the adaptation
    ``a``: ``u = v - (v >> leak_shift) + I``, threshold ``v_threshold +
    a``, spike iff ``u`` reaches it, then ``v = u - threshold`` where
    ``subtractive`` else ``v_reset``, and ``a`` decays by its own shift
    and grows by ``adapt_inc`` on a spike. :meth:`packed` is the
    ``[8, n]`` device form, one row per field (two zero rows pad it to
    one int32 sublane tile).
    """
    leak_shift: np.ndarray
    v_threshold: np.ndarray
    v_reset: np.ndarray
    adapt_shift: np.ndarray
    adapt_inc: np.ndarray
    subtractive: np.ndarray        # 1: reset by subtracting the threshold

    @classmethod
    def make(cls, n: int, *, leak_shift, v_threshold, v_reset=0,
             adapt_shift=0, adapt_inc=0, subtractive=0) -> "NeuronParams":
        """Each field a scalar (every neuron alike) or an ``[n]`` vector;
        checked by :meth:`validate`."""
        vals = dict(leak_shift=leak_shift, v_threshold=v_threshold,
                    v_reset=v_reset, adapt_shift=adapt_shift,
                    adapt_inc=adapt_inc, subtractive=subtractive)
        p = cls(**{k: np.broadcast_to(np.asarray(x, np.int64), (n,))
                   .astype(np.int32) for k, x in vals.items()})
        for k, x in vals.items():
            if not np.array_equal(getattr(p, k), np.broadcast_to(x, (n,))):
                raise ValueError(f"NeuronParams.{k} outside int32")
        return p.validate()

    @classmethod
    def uniform(cls, p: LIFIntParams, n: int) -> "NeuronParams":
        """The scalar LIF ``p`` on each of ``n`` neurons."""
        return cls.make(n, leak_shift=p.leak_shift,
                        v_threshold=p.v_threshold, v_reset=p.v_reset)

    @property
    def n(self) -> int:
        return int(self.leak_shift.shape[0])

    @property
    def adaptive(self) -> bool:
        """Some neuron adapts its threshold or resets by subtraction."""
        return bool(self.adapt_inc.any() or self.subtractive.any())

    def scalar(self) -> LIFIntParams | None:
        """The one scalar LIF every neuron shares, or ``None`` where the
        neurons differ or adapt: picks the scalar Neuron Unit."""
        if self.adaptive or self.n == 0:
            return None
        first = [int(getattr(self, k)[0]) for k in
                 ("leak_shift", "v_threshold", "v_reset")]
        if any((getattr(self, k) != f).any() for k, f in
               zip(("leak_shift", "v_threshold", "v_reset"), first)):
            return None
        return LIFIntParams(*first)

    def validate(self) -> "NeuronParams":
        shapes = {x.shape for x in self}
        if len(shapes) != 1 or len(next(iter(shapes))) != 1:
            raise ValueError(f"NeuronParams fields must be [n] vectors of "
                             f"one length; got shapes {sorted(shapes)}")
        for k in _SHIFT_FIELDS:
            x = getattr(self, k)
            if ((x < 0) | (x > 31)).any():
                raise ValueError(f"NeuronParams.{k} must lie in [0, 31]")
        if (self.adapt_inc < 0).any():
            raise ValueError("NeuronParams.adapt_inc must be >= 0")
        if ((self.subtractive != 0) & (self.subtractive != 1)).any():
            raise ValueError("NeuronParams.subtractive must be 0 or 1")
        return self

    def packed(self) -> np.ndarray:
        """``[8, n]`` int32: the fields as rows, in field order."""
        out = np.zeros((8, self.n), np.int32)
        out[:len(self)] = np.stack(self)
        return out


def alif_step_int(v, a, current, p: NeuronParams):
    """Integer adaptive-LIF step over per-neuron parameters, for numpy
    or jnp int32 arrays; ``p``'s vectors broadcast over the last axis.
    Returns ``(v_next, a_next, spikes)``.

    With ``a`` the adaptation carried out of the previous step::

        u  = v - (v >> leak_shift) + I
        th = v_threshold + a
        s  = u >= th
        v' = (u - th if subtractive else v_reset) where s, else u
        a' = a - (a >> adapt_shift) + adapt_inc * s

    This is the source's ``a[t] = a[t-1] - (a[t-1] >> k) + inc *
    s[t-1]`` with the threshold ``v_th + a[t]``: the carried ``a'`` is
    the next step's ``a[t+1]``. A readout (``v_threshold ==
    NEVER_FIRES``, ``adapt_inc == 0``) is a leaky integrator. With
    ``adapt_inc == 0`` and value resets it is :func:`lif_step_int` per
    neuron, bit for bit. Where no spike occurs, ``u - th`` is computed
    and discarded; it may wrap, as int32 arithmetic does everywhere.
    """
    xp = np if isinstance(v, np.ndarray) else jnp
    u = v - (v >> p.leak_shift) + current
    th = p.v_threshold + a
    s = u >= th
    v_next = xp.where(s, xp.where(p.subtractive != 0, u - th, p.v_reset), u)
    a_next = a - (a >> p.adapt_shift) + xp.where(s, p.adapt_inc, 0)
    return v_next.astype(v.dtype), a_next.astype(a.dtype), s.astype(xp.int32)
