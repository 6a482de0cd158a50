"""The inputs every traffic mix draws from.

Inputs are binary spike trains at the configuration's
``input_spike_rate``, drawn from the seed into a pool that the requests
pick from. How requests are sent is the traffic file's ``generator``
(``bench/generators/<name>.py``), and, for a generator that sends on a
schedule, its ``arrivals`` (``bench/arrivals/<name>.py``).
"""
from __future__ import annotations

import numpy as np


def spike_pool(rows: int, timesteps: int, n_inputs: int, rate: float,
               seed: int) -> np.ndarray:
    """``[rows, timesteps, n_inputs]`` int32 Bernoulli(``rate``) spikes."""
    rng = np.random.default_rng(seed)
    draw = rng.random((rows, timesteps, n_inputs), dtype=np.float32)
    return (draw < rate).astype(np.int32)
