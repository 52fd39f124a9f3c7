"""Device self time under the ``mx.opt_update`` name scope (the optimizer
epilogue of the step program, either route) per step, in ms: over the
executions of the trainer's step program wholly in the traced window
(``harness/program_trace.py``)."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.opt_update", "spmd")
