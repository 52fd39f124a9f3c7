"""Mean device-idle time between two consecutive executions of the
engine's programs in the traced window (device clock; by the union of
operations, so the slice behind a prefill's first token counts as busy),
in ms.  Gaps across an ``engine.wait`` and gaps over ten times the median
are left out (the latter are ``gap_outlier_share``).  The four legs below
add up to it: ``harness/gap_trace.py``; the whole reduction is in
``chiprun_out/<cell>.gap_trace.json``.  None for a program without the
``engine.*.dispatch`` spans."""
from benchmarks.harness import gap_trace


def read(obs, trace):
    return gap_trace.leg_ms(trace, "gap")
