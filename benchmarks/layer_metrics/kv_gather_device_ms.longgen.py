"""Device self time under the ``mx.kv_gather`` name scope (the gather of a
request's pages into the attention window, and whatever relayout of the
gathered K and V inherits the scope) per decode iteration, in ms: over the
executions of the engine's decode programs that lie wholly in the traced
window.  Read by ``harness/program_trace.py``: the instruction of each
device event joined with the program's ``mx.perf.op_names()`` table."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.kv_gather", "serving",
                                  "/decode-")
