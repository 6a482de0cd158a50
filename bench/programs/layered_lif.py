"""Build the system under test for a ``layered_lif`` configuration.

The benchmark's integer network (drawn by the reference module
``bench/configs/layered_lif.py``) goes through the program's own path:
``QuantizedSNN`` -> ``from_quantized`` -> ``compile`` -> ``Program``.
A configuration whose ``"model"`` names another network type brings
its reference and its build as two files of that name.
"""
from __future__ import annotations

import numpy as np


def quantized_snn(cfg: dict, net):
    """The reference's integer network as the program's ``QuantizedSNN``."""
    from repro.snn.lif import LIFIntParams
    from repro.snn.quantize import QuantizedSNN
    return QuantizedSNN(
        layer_sizes=tuple(net.layer_sizes),
        weights=[w.astype(np.int32) for w in net.weights],
        rec_weights=[None if r is None else r.astype(np.int32)
                     for r in net.rec_weights],
        scale=net.scale,
        lif=LIFIntParams(leak_shift=net.leak_shift,
                         v_threshold=net.v_threshold, v_reset=net.v_reset),
        recurrent=bool(cfg["recurrent"]))


def build(cfg: dict, net, compile_seed: int):
    """The compiled ``Program`` of ``net`` on the configuration's
    hardware."""
    from repro.core import HardwareConfig, compile, from_quantized
    return compile(from_quantized(quantized_snn(cfg, net)),
                   HardwareConfig(**cfg["hardware"]), seed=compile_seed,
                   max_iters=cfg["partitioner"]["max_iters"])
