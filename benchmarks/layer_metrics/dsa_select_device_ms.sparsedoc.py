"""Device self time under the ``mx.dsa_select`` name scope (the top-k over the
index scores and the selection mask made of it) per decode iteration, in
ms, all its blocks together."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(
        trace, "mx.dsa_select", "serving", "/decode-")
