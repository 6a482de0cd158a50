"""Front end (batch buckets): pad rows over bucket rows, in %.

From each ``CompletedRequest``'s ``bucket`` and ``batch_size``: a call
of ``n`` requests in bucket ``b`` has ``n`` members, so each member
carries ``1/n`` of the call's ``b - n`` pad rows and ``b`` bucket rows.
"""
import numpy as np


def read(run):
    req = getattr(run, "requests", None)
    if req is None:
        return None
    st = req.stages[~np.isnan(req.stages[:, 0])]
    if not len(st):
        return None
    bucket, n = st[:, 4], st[:, 5]
    return float(100.0 * np.sum((bucket - n) / n) / np.sum(bucket / n))
