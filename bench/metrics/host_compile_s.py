"""Host compiler (``core/passes.py``, ``core/mapping/``,
``core/scheduling/``): partition, schedule and lower, in s.

``CompileReport.compile_seconds`` of the program the run built.
"""


def read(run):
    return float(run.compile_s)
