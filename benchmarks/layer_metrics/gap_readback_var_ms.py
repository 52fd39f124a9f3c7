"""The read-back's excess in ``gap_ms``: the end of an execution to the
return of its ``engine.*.fetch``, less the least of its block of 50.  Mean
over the counted gaps, in ms."""
from benchmarks.harness import gap_trace


def read(obs, trace):
    return gap_trace.leg_ms(trace, "readback_var")
