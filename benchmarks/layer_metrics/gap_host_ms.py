"""The host's leg of ``gap_ms``: from the return of ``engine.*.fetch`` of
execution N to the begin of ``engine.*.dispatch`` of N+1 (host clock):
emit, finish, admit, prepare.  Mean over the counted gaps, in ms."""
from benchmarks.harness import gap_trace


def read(obs, trace):
    return gap_trace.leg_ms(trace, "host")
