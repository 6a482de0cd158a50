"""Front end (``serve/async_server.py``): time a request waited, in ms.

Mean of ``queue_wait_us + fill_wait_us`` over the ``CompletedRequest``s
of the window: from the server's enqueue stamp to the dispatch of the
request's batch.
"""
import numpy as np


def read(run):
    req = getattr(run, "requests", None)
    if req is None:
        return None
    st = req.stages[~np.isnan(req.stages[:, 0])]
    return float(np.mean(st[:, 0] + st[:, 1]) / 1e3) if len(st) else None
