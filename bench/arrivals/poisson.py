"""Poisson arrivals at ``rate_rps`` requests per second.

Every seed gets the same multiset of gaps, the exponential
distribution's mid-point quantiles scaled to fill the window, in an
order drawn from the seed: the same work in another order, so seeds
differ only as much as orderings do.
"""
from __future__ import annotations

import numpy as np

PARAMS = ("rate_rps",)


def offsets(params: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times in ``[0, seconds)``, in seconds from the window's start:
    ``round(rate_rps * seconds)`` requests."""
    n = max(1, int(round(float(params["rate_rps"]) * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng(seed).permutation(gaps)
    return np.cumsum(gaps) - gaps[0]
