"""SNN-as-graph representation (paper Eq. (6)): G = (V, E, W).

Neurons are globally indexed. Indices [0, n_inputs) are input neurons
(off-chip spike sources, no on-chip state); [n_inputs, n_neurons) are
internal neurons whose state lives in the Neuron Unit. Internal neurons
also carry a *local* index (global - n_inputs), which is what SPU
operation tables and the Neuron Unit use (paper §4.4.3).

Synapses are stored as flat arrays (pre, post, weight) over the NONZERO
connections only — the operation-based execution model simply omits
zero-weight synapses (paper §4.4.2 advantage 1).

The Neuron Unit's parameters (``lif``) are either one scalar
:class:`~repro.snn.lif.LIFIntParams` shared by every internal neuron,
or per-neuron :class:`~repro.snn.lif.NeuronParams` vectors in local
index order (per-neuron leaks, adaptive thresholds, subtractive reset).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.snn.lif import LIFIntParams, NeuronParams
from repro.snn.quantize import QuantizedSNN


@dataclasses.dataclass
class SNNGraph:
    n_inputs: int
    n_neurons: int             # inputs + internal
    pre: np.ndarray            # [E] int32 global pre index
    post: np.ndarray           # [E] int32 global post index (always internal)
    weight: np.ndarray         # [E] int32 quantized weight (nonzero)
    lif: LIFIntParams | NeuronParams
    output_slice: tuple[int, int] = (0, 0)   # global [start, stop) of outputs

    def __post_init__(self):
        assert self.pre.shape == self.post.shape == self.weight.shape
        assert (self.weight != 0).all(), "zero-weight synapses must be dropped"
        assert (self.post >= self.n_inputs).all(), \
            "post-synaptic neurons must be internal"
        if isinstance(self.lif, NeuronParams):
            self.lif.validate()
            if self.lif.n != self.n_internal:
                raise ValueError(f"NeuronParams over {self.lif.n} neurons; "
                                 f"the graph has {self.n_internal} internal")

    @property
    def neurons(self) -> NeuronParams:
        """Per-neuron parameters over the internal neurons (a scalar
        ``lif`` repeated)."""
        if isinstance(self.lif, NeuronParams):
            return self.lif
        return NeuronParams.uniform(self.lif, self.n_internal)

    @property
    def scalar_lif(self) -> LIFIntParams | None:
        """The one non-adaptive LIF every neuron shares, or ``None``:
        ``None`` selects the per-neuron Neuron Unit in every engine."""
        if isinstance(self.lif, LIFIntParams):
            return self.lif
        return self.lif.scalar()

    @property
    def n_internal(self) -> int:
        return self.n_neurons - self.n_inputs

    @property
    def n_synapses(self) -> int:
        return int(self.pre.shape[0])

    def local(self, global_idx: np.ndarray) -> np.ndarray:
        return global_idx - self.n_inputs

    def validate(self):
        assert (self.pre >= 0).all() and (self.pre < self.n_neurons).all()
        assert (self.post >= self.n_inputs).all() and \
               (self.post < self.n_neurons).all()
        # no duplicate synapses
        key = self.pre.astype(np.int64) * self.n_neurons + self.post
        assert len(np.unique(key)) == len(key), "duplicate synapses"


def from_quantized(qsnn: QuantizedSNN) -> SNNGraph:
    """Flatten a layered quantized SNN into the global graph."""
    sizes = qsnn.layer_sizes
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    pres, posts, ws = [], [], []
    for i, w in enumerate(qsnn.weights):
        r, c = np.nonzero(w)
        pres.append(r + offsets[i])
        posts.append(c + offsets[i + 1])
        ws.append(w[r, c])
    for i, wr in enumerate(qsnn.rec_weights):
        if wr is None:
            continue
        r, c = np.nonzero(wr)
        pres.append(r + offsets[i + 1])
        posts.append(c + offsets[i + 1])
        ws.append(wr[r, c])
    g = SNNGraph(
        n_inputs=sizes[0], n_neurons=int(offsets[-1]),
        pre=np.concatenate(pres).astype(np.int32),
        post=np.concatenate(posts).astype(np.int32),
        weight=np.concatenate(ws).astype(np.int32),
        lif=qsnn.lif,
        output_slice=(int(offsets[-2]), int(offsets[-1])))
    g.validate()
    return g


def random_graph(n_inputs: int, n_internal: int, n_synapses: int,
                 seed: int = 0, weight_lo: int = -7, weight_hi: int = 7,
                 lif: LIFIntParams | None = None) -> SNNGraph:
    """Random irregular graph (for property tests — paper Fig. 2b style)."""
    rng = np.random.default_rng(seed)
    n = n_inputs + n_internal
    # sample unique (pre, post) pairs; post must be internal
    max_e = n * n_internal
    n_synapses = min(n_synapses, max_e)
    flat = rng.choice(max_e, size=n_synapses, replace=False)
    pre = (flat // n_internal).astype(np.int32)
    post = (flat % n_internal + n_inputs).astype(np.int32)
    w = np.zeros(n_synapses, np.int32)
    while (w == 0).any():  # nonzero weights only
        m = w == 0
        w[m] = rng.integers(weight_lo, weight_hi + 1, m.sum())
    g = SNNGraph(n_inputs, n, pre, post, w,
                 lif or LIFIntParams(leak_shift=2, v_threshold=15, v_reset=0),
                 output_slice=(n - min(4, n_internal), n))
    g.validate()
    return g
