"""The comparison that decides ``correct``.

The program is an integer network: every output is exact, so every
number compared is a count of disagreements with the plain reference
and every limit is 0. The readings these limits were set from, and the
control that fails them, are in ``PERF.md``.
"""
from __future__ import annotations

import numpy as np

# number compared -> its limit (all exact comparisons)
LIMITS = {
    "requests_failed": 0,      # sampled or not: a request that never came
    "rows_wrong": 0,           # checked rows with any output that differs
    "spikes_wrong": 0,         # spike entries that differ
    "v_wrong": 0,              # final membrane potentials that differ
    "packets_wrong": 0,        # per-step multicast packet counts that differ
}


def compare(got: tuple[np.ndarray, np.ndarray, np.ndarray],
            want: tuple[np.ndarray, np.ndarray, np.ndarray]) -> dict:
    """Disagreements of ``got`` with ``want``, both ``(spikes [R, T, n],
    v [R, n], packets [R, T])``."""
    gs, gv, gp = (np.asarray(x, np.int64) for x in got)
    ws, wv, wp = (np.asarray(x, np.int64) for x in want)
    if gs.shape != ws.shape or gv.shape != wv.shape or gp.shape != wp.shape:
        n = max(len(ws), 1)
        return {"rows_wrong": n, "spikes_wrong": int(ws.size),
                "v_wrong": int(wv.size), "packets_wrong": int(wp.size)}
    bad_s, bad_v, bad_p = gs != ws, gv != wv, gp != wp
    rows = bad_s.any(axis=(1, 2)) | bad_v.any(axis=1) | bad_p.any(axis=1)
    return {"rows_wrong": int(rows.sum()), "spikes_wrong": int(bad_s.sum()),
            "v_wrong": int(bad_v.sum()), "packets_wrong": int(bad_p.sum())}


def verdict(numbers: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over :data:`LIMITS`."""
    table = {k: {"value": int(numbers[k]), "limit": lim}
             for k, lim in LIMITS.items()}
    return all(v["value"] <= v["limit"] for v in table.values()), table
