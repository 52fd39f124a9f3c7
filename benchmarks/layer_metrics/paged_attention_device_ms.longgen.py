"""Device self time under the ``mx.paged_attention`` name scope (the Pallas
kernel ``mx_paged_attention`` or its XLA twin, whichever the route picked)
per decode iteration, in ms: over the executions of the engine's decode
programs wholly in the traced window (``harness/program_trace.py``)."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.paged_attention", "serving",
                                  "/decode-")
