from repro.snn.lif import (NEVER_FIRES, LIFParams, LIFIntParams,
                           NeuronParams, alif_step_int, lif_step,
                           lif_step_int, alpha_to_shift, spike_fn)
from repro.snn.models import (SNNConfig, MNIST_CONFIG, SHD_CONFIG,
                              init_params, masked_weights, forward)
from repro.snn.quantize import QuantConfig, QuantizedSNN, quantize

__all__ = ["NEVER_FIRES", "LIFParams", "LIFIntParams", "NeuronParams",
           "alif_step_int", "lif_step", "lif_step_int",
           "alpha_to_shift", "spike_fn", "SNNConfig", "MNIST_CONFIG",
           "SHD_CONFIG", "init_params", "masked_weights", "forward",
           "QuantConfig", "QuantizedSNN", "quantize"]
