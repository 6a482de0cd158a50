"""Build the system under test for a ``layered_alif`` configuration.

The benchmark's integer network (drawn by the reference module
``bench/configs/layered_alif.py``) goes through the program's own path:
``QuantizedSNN`` with per-neuron ``NeuronParams`` -> ``from_quantized``
-> ``compile`` -> ``Program``. Hidden neurons adapt their threshold and
reset by subtraction; readout neurons never fire (``NEVER_FIRES``) and
do not adapt.
"""
from __future__ import annotations

import numpy as np


def neuron_params(net):
    """The reference's per-neuron constants as the program's
    ``NeuronParams``, in neuron order (layers 1..L)."""
    from repro.snn.lif import NEVER_FIRES, NeuronParams
    sizes = net.layer_sizes[1:]
    readout = np.concatenate([np.full(n, i == len(sizes) - 1)
                              for i, n in enumerate(sizes)])
    return NeuronParams.make(
        int(sum(sizes)), leak_shift=np.concatenate(net.leak_shift),
        v_threshold=np.where(readout, NEVER_FIRES, net.v_threshold),
        adapt_shift=np.where(readout, 0, np.concatenate(net.adapt_shift)),
        adapt_inc=np.where(readout, 0, net.adapt_inc),
        subtractive=~readout)


def quantized_snn(cfg: dict, net):
    """The reference's integer network as the program's ``QuantizedSNN``."""
    from repro.snn.quantize import QuantizedSNN
    return QuantizedSNN(
        layer_sizes=tuple(net.layer_sizes),
        weights=[w.astype(np.int32) for w in net.weights],
        rec_weights=[None if r is None else r.astype(np.int32)
                     for r in net.rec_weights],
        scale=net.scale, lif=neuron_params(net),
        recurrent=bool(cfg["recurrent"]))


def build(cfg: dict, net, compile_seed: int):
    """The compiled ``Program`` of ``net`` on the configuration's
    hardware, mapped by the configuration's partitioner."""
    from repro.core import HardwareConfig, compile, from_quantized
    part = cfg["partitioner"]
    return compile(from_quantized(quantized_snn(cfg, net)),
                   HardwareConfig(**cfg["hardware"]), seed=compile_seed,
                   method=part["method"], max_iters=part["max_iters"])
