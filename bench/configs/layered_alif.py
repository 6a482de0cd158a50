"""Plain reference of a layered integer adaptive-LIF network (ALIF SRNN).

Used by the configurations whose ``"model"`` is ``"layered_alif"``. It
imports nothing of the system under test and takes nothing the system
made: the benchmark draws the weights and every neuron's shifts here
from the seed, hands the same integers to the program, and checks the
program against :func:`reference`.

Source: the adaptive spiking recurrent network of Yin, Corradi & Bohte,
"Accurate and efficient time-domain classification with adaptive
spiking recurrent neural networks", Nature Machine Intelligence 3
(2021), arXiv:2103.12593 (its Spiking Speech Commands network:
700 inputs, two recurrent layers of 400 ALIF neurons, 35 leaky
readouts), with the ALIF neuron of Bellec et al., NeurIPS 2018,
arXiv:1803.09574. Layer 0 is the input. A hidden layer ``l`` holds
integer ALIF neurons, each ``j`` with its own shifts::

    I_l[t]   = x_{l-1}[t] @ W_l + s_l[t-1] @ R_l
    a_l[t]   = a_l[t-1] - (a_l[t-1] >> adapt_shift_j) + adapt_inc * s_l[t-1]
    u_l[t]   = v_l[t-1] - (v_l[t-1] >> leak_shift_j) + I_l[t]
    th_l[t]  = v_th + a_l[t]
    s_l[t]   = u_l[t] >= th_l[t]
    v_l[t]   = u_l[t] - th_l[t]  where s_l[t], else u_l[t]

and the last layer is a readout of leaky integrators that never fire:
``v[t] = v[t-1] - (v[t-1] >> leak_shift_j) + x_{L-1}[t] @ W_L``. As in
the SupraSNN hardware, ``x_0[t]`` is the external spikes at ``t`` and
``x_{l-1}[t] = s_{l-1}[t-1]`` for ``l >= 2``: every synapse between
neurons carries the spike of the previous step, and the packets of
step ``t`` are the external spikes of ``t`` plus every neuron spike of
``t - 1``. Hidden layers have no self-loops.

Departures from the source, each a hardware form of it:

* the learned per-neuron time constants tau_m and tau_adp become
  integer shifts, ``exp(-dt/tau) ~ 1 - 2**-shift``, drawn per neuron
  from the seed within the configuration's ranges (adaptation slower
  than the membrane), not trained;
* the weights are random, one symmetric scale for all of them; the
  threshold ``v_th`` and the adaptation step ``adapt_inc`` (the
  source's ``beta * (1 - rho)``) are in the same fixed-point units;
* the readout's arithmetic is the same integer shift-leak, with no
  threshold and no softmax; classification reads the final ``v``;
* the input is Bernoulli spikes at ``input_spike_rate``, not SSC audio.

Outputs follow the network's neuron order, layers 1..L concatenated:
spikes ``[B, T, n_neurons]``, final potentials ``[B, n_neurons]``,
packets ``[B, T]``.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Network:
    """Integer weights and per-neuron constants of one seeded network."""
    layer_sizes: tuple[int, ...]
    weights: tuple[np.ndarray, ...]          # W_l, int [fan_in, fan_out]
    rec_weights: tuple[np.ndarray | None, ...]   # R_l per layer, or None
    scale: float                             # float weight = int * scale
    leak_shift: tuple[np.ndarray, ...]       # per layer, one per neuron
    adapt_shift: tuple[np.ndarray, ...]      # per layer (readout: unused)
    v_threshold: int
    adapt_inc: int
    weight_bits: int

    @property
    def n_inputs(self) -> int:
        return self.layer_sizes[0]

    @property
    def n_neurons(self) -> int:
        return int(sum(self.layer_sizes[1:]))

    @property
    def n_synapses(self) -> int:
        return int(sum(np.count_nonzero(w) for w in self.weights)
                   + sum(np.count_nonzero(r) for r in self.rec_weights
                         if r is not None))


def float_weights(cfg: dict, rng: np.random.Generator
                  ) -> tuple[list[np.ndarray], list[np.ndarray | None]]:
    """Float weights: ``N(0, 1/fan_in) * weight_gain`` feed-forward,
    ``N(0, 1/fan_out) * recurrent_gain`` recurrent in the hidden layers
    with no self-loops, each kept where a uniform draw is
    ``>= sparsity``."""
    sizes = cfg["layer_sizes"]
    sparsity = cfg["sparsity"]
    ws, rs = [], []
    for i in range(len(sizes) - 1):
        fan_in, fan_out = sizes[i], sizes[i + 1]
        w = rng.standard_normal((fan_in, fan_out)) / np.sqrt(fan_in)
        w *= cfg["weight_gain"]
        ws.append(w * (rng.random((fan_in, fan_out)) >= sparsity))
        if cfg["recurrent"] and i < len(sizes) - 2:
            r = rng.standard_normal((fan_out, fan_out)) / np.sqrt(fan_out)
            r *= cfg["recurrent_gain"]
            keep = rng.random((fan_out, fan_out)) >= sparsity
            np.fill_diagonal(keep, False)
            rs.append(r * keep)
        else:
            rs.append(None)
    return ws, rs


def make_network(cfg: dict, seed: int, weight_bits: int | None = None
                 ) -> Network:
    """The seeded network: weights quantized symmetrically to
    ``weight_bits`` (the configuration's own by default) with one scale,
    ``v_th`` and ``adapt_inc`` in the same units, and each neuron's
    shifts drawn uniformly from the configuration's inclusive ranges.
    The shifts are drawn after the weights from the same stream, so a
    coarser ``weight_bits`` changes nothing else."""
    bits = cfg["weight_bits"] if weight_bits is None else weight_bits
    rng = np.random.default_rng(seed)
    ws, rs = float_weights(cfg, rng)
    absmax = max(float(np.abs(w).max()) for w in ws + [r for r in rs
                                                       if r is not None])
    qmax = 2 ** (bits - 1) - 1
    scale = absmax / qmax if absmax > 0 else 1.0

    def quantize(w):
        return np.clip(np.round(w / scale), -qmax - 1, qmax).astype(np.int64)

    (ll, lh), (al, ah) = cfg["leak_shift_range"], cfg["adapt_shift_range"]
    sizes = cfg["layer_sizes"][1:]
    leak = tuple(rng.integers(ll, lh + 1, n) for n in sizes)
    adapt = tuple(rng.integers(al, ah + 1, n) for n in sizes)
    return Network(
        layer_sizes=tuple(cfg["layer_sizes"]),
        weights=tuple(quantize(w) for w in ws),
        rec_weights=tuple(None if r is None else quantize(r) for r in rs),
        scale=scale, leak_shift=leak, adapt_shift=adapt,
        v_threshold=max(int(round(cfg["v_threshold"] / scale)), 1),
        adapt_inc=int(round(cfg["adapt_inc"] / scale)),
        weight_bits=bits)


def reference(net: Network, ext: np.ndarray
              ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Run ``net`` on binary spike trains ``ext [B, T, n_inputs]``.

    Returns ``(spikes [B, T, n_neurons], v [B, n_neurons], packets
    [B, T])`` as int64. The products are taken in float64, which is
    exact here: every current is an integer far below 2**53.
    """
    ext = np.asarray(ext)
    b, t_steps, _ = ext.shape
    sizes = net.layer_sizes[1:]
    readout = len(sizes) - 1
    w = [x.astype(np.float64) for x in net.weights]
    r = [None if x is None else x.astype(np.float64)
         for x in net.rec_weights]
    v = [np.zeros((b, n), np.int64) for n in sizes]
    a = [np.zeros((b, n), np.int64) for n in sizes]
    s_prev = [np.zeros((b, n), np.int64) for n in sizes]
    spikes = np.zeros((b, t_steps, net.n_neurons), np.int64)
    packets = np.zeros((b, t_steps), np.int64)
    for t in range(t_steps):
        x_t = ext[:, t].astype(np.float64)
        packets[:, t] = np.count_nonzero(ext[:, t], axis=1) + sum(
            np.count_nonzero(s, axis=1) for s in s_prev)
        s_now = []
        for i in range(len(sizes)):
            src = x_t if i == 0 else s_prev[i - 1].astype(np.float64)
            cur = src @ w[i]
            if r[i] is not None:
                cur = cur + s_prev[i].astype(np.float64) @ r[i]
            u = v[i] - (v[i] >> net.leak_shift[i]) + np.rint(cur).astype(
                np.int64)
            if i == readout:
                v[i] = u
                s_now.append(np.zeros_like(u))
                continue
            a[i] = a[i] - (a[i] >> net.adapt_shift[i]) \
                + net.adapt_inc * s_prev[i]
            th = net.v_threshold + a[i]
            fired = u >= th
            v[i] = np.where(fired, u - th, u)
            s_now.append(fired.astype(np.int64))
        spikes[:, t] = np.concatenate(s_now, axis=1)
        s_prev = s_now
    return spikes, np.concatenate(v, axis=1), packets
