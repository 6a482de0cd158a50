"""Load generator (``bench/``): how late the generator sent, in ms.

The 95th percentile of send time minus due time over the requests of
the window, on the harness clock. A starved generator shows here, not
as a fast server.
"""
import numpy as np


def read(run):
    req = getattr(run, "requests", None)
    if req is None:
        return None
    late = (req.sent - req.due) * 1e3
    late = late[~np.isnan(late)]
    return float(np.percentile(late, 95)) if late.size else None
