"""Device busy time (self time on device 0, traced window) of operations
under no ``mx.`` name scope, as a percentage of busy time: what the
per-scope metrics do not see.  The largest such operations are listed in
``chiprun_out/<cell>.program_trace.json`` (``harness/program_trace.py``)."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.busy_share(trace, "unscoped_s")
