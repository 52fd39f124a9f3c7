"""Device self time under the ``mx.moe_router`` name scope (float32 scores
over all experts and the top-k choice: a sort, latency-bound) per decode
iteration, in ms, all ``E`` blocks together."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.moe_router", "serving",
                                  "/decode-")
