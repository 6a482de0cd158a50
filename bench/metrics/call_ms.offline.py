"""Executor (``Program.run``; ``ShardedRunner.run`` on 4 chips): host wall
time per call with the outputs on the host, in ms."""
import numpy as np


def read(run):
    calls = getattr(run, "engine_calls", None)
    if run.kind != "back_to_back" or not calls:
        return None
    return float(np.mean([t1 - t0 for t0, t1, _ in calls]) * 1e3)
