"""Device self time under the ``mx.lightning_scan`` name scope (the chunked
form of the ``N`` blocks over a whole prompt: the decayed in-chunk product
and the float32 state carried between chunks) per prefill in the traced
window, in ms, all ``N`` blocks together."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.lightning_scan", "serving",
                                  "/prefill-")
