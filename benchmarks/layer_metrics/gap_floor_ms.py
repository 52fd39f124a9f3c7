"""The floor of ``gap_ms``: ``U - L`` of the clock offset's causal bounds
over blocks of 50 executions — the least launch (``engine.*.dispatch``
begin to execution start) plus the least read-back (execution end to
``engine.*.fetch`` return) any step of the block took: what no step does
without.  Mean over the counted gaps, in ms."""
from benchmarks.harness import gap_trace


def read(obs, trace):
    return gap_trace.leg_ms(trace, "floor")
