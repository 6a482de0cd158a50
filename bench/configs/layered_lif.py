"""Plain reference of a layered integer LIF network (the SupraSNN model).

Used by the configurations whose ``"model"`` is ``"layered_lif"``. It
imports nothing of the system under test and takes nothing the system
made: the benchmark draws the weights here from the seed, hands the same
integers to the program, and checks the program against
:func:`reference`.

Model (SupraSNN section 4.2, hardware semantics). Layer 0 is the input.
Every layer ``l >= 1`` holds integer LIF neurons::

    I_l[t]   = x_{l-1}[t] @ W_l  (+ s_l[t-1] @ R_l  when recurrent)
    u_l[t]   = v_l[t-1] - (v_l[t-1] >> leak_shift) + I_l[t]
    s_l[t]   = u_l[t] >= v_th
    v_l[t]   = v_reset where s_l[t] else u_l[t]

with ``x_0[t]`` the external spikes at ``t`` and ``x_{l-1}[t] =
s_{l-1}[t-1]`` for ``l >= 2``: every synapse between neurons carries
the spike of the previous step. The hidden layers recur when
``recurrent`` is set; the output layer never does. A neuron's multicast
packet is sent in the step after it fires, so the packets of step ``t``
are the external spikes of ``t`` plus every neuron spike of ``t - 1``.

Outputs follow the network's neuron order, layers 1..L concatenated:
spikes ``[B, T, n_neurons]``, final potentials ``[B, n_neurons]``,
packets ``[B, T]``.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Network:
    """Integer weights and neuron constants of one seeded network."""
    layer_sizes: tuple[int, ...]
    weights: tuple[np.ndarray, ...]          # W_l, int [fan_in, fan_out]
    rec_weights: tuple[np.ndarray | None, ...]   # R_l per layer, or None
    scale: float                             # float weight = int * scale
    leak_shift: int
    v_threshold: int
    v_reset: int
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


def float_weights(cfg: dict, seed: int
                  ) -> tuple[list[np.ndarray], list[np.ndarray | None]]:
    """Pruned float weights from the seed: ``N(0, 1/fan_in) * gain``
    feed-forward, ``N(0, 1/fan_out) * recurrent_gain`` recurrent with
    no self-loops, each kept where a uniform draw is ``>= sparsity``."""
    rng = np.random.default_rng(seed)
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
    """The seeded network, quantized symmetrically to ``weight_bits``
    (the configuration's own by default) with one scale for all
    weights; threshold and reset in the same fixed-point units, the
    leak as the nearest power-of-two shift."""
    bits = cfg["weight_bits"] if weight_bits is None else weight_bits
    ws, rs = float_weights(cfg, seed)
    absmax = max(float(np.abs(w).max()) for w in ws + [r for r in rs
                                                       if r is not None])
    qmax = 2 ** (bits - 1) - 1
    scale = absmax / qmax if absmax > 0 else 1.0

    def quantize(w):
        return np.clip(np.round(w / scale), -qmax - 1, qmax).astype(np.int64)

    return Network(
        layer_sizes=tuple(cfg["layer_sizes"]),
        weights=tuple(quantize(w) for w in ws),
        rec_weights=tuple(None if r is None else quantize(r) for r in rs),
        scale=scale,
        leak_shift=int(round(-np.log2(cfg["leak_alpha"]))),
        v_threshold=max(int(round(cfg["v_threshold"] / scale)), 1),
        v_reset=int(round(cfg["v_reset"] / scale)),
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
    w = [x.astype(np.float64) for x in net.weights]
    r = [None if x is None else x.astype(np.float64)
         for x in net.rec_weights]
    v = [np.zeros((b, n), np.int64) for n in sizes]
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
            u = v[i] - (v[i] >> net.leak_shift) + np.rint(cur).astype(np.int64)
            fired = u >= net.v_threshold
            v[i] = np.where(fired, net.v_reset, u)
            s_now.append(fired.astype(np.int64))
        spikes[:, t] = np.concatenate(s_now, axis=1)
        s_prev = s_now
    return spikes, np.concatenate(v, axis=1), packets
