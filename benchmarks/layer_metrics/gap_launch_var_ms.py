"""The launch's excess in ``gap_ms``: ``engine.*.dispatch`` begin to the
start of its execution, less the least of its block of 50.  Mean over the
counted gaps, in ms."""
from benchmarks.harness import gap_trace


def read(obs, trace):
    return gap_trace.leg_ms(trace, "launch_var")
