"""Device self time under the ``mx.kv_write`` name scope (the scatter of
the new token's K and V into its page, and whatever copy of the pool
inherits the scope) per decode iteration, in ms: over the executions of the
engine's decode programs wholly in the traced window
(``harness/program_trace.py``)."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.kv_write", "serving",
                                  "/decode-")
