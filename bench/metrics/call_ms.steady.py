"""Executor (``core/engine_jax.py`` through the registry runner): ms per
engine call, as the front end measures it.

Mean of ``pad_us + compute_us`` per engine call; every member of a call
of ``n`` requests carries that call's service time and a weight ``1/n``.
"""
import numpy as np


def read(run):
    req = getattr(run, "requests", None)
    if req is None:
        return None
    st = req.stages[~np.isnan(req.stages[:, 0])]
    if not len(st):
        return None
    w = 1.0 / st[:, 5]
    return float(np.sum((st[:, 2] + st[:, 3]) * w) / np.sum(w) / 1e3)
