"""Registry / AOT (``serve/registry.py``, ``core/aot.py``): wall time of
the registry insert with AOT precompile of the cell's own buckets, in s
(``Program.precompile`` of the one batch shape in an offline cell)."""


def read(run):
    return float(run.precompile_s)
