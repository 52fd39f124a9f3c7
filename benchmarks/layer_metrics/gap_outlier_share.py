"""Share of the traced window that lies in gaps over ten times the median
gap between two executions, in percent; each such gap is listed with what
covered it in ``chiprun_out/<cell>.gap_trace.json``."""
from benchmarks.harness import gap_trace


def read(obs, trace):
    return gap_trace.field(trace, "outlier_share")
