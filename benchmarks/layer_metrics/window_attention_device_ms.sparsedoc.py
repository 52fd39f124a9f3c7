"""Device self time under the ``mx.window_attention`` name scope (the absorbed
query's scores and values over the slot's ring) per decode iteration, in
ms, all its blocks together."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(
        trace, "mx.window_attention", "serving", "/decode-")
