"""Device self time under the ``mx.retention_scan`` name scope (the
chunked form of a whole prompt's retention: the masked, decayed ``(q
k^T)^2`` product inside a chunk, the float32 state and the keys' and
queries' symmetric squares between chunks) per execution of a prefill
program in the traced window, in ms, all ``R`` blocks together, averaged
over the buckets that ran."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.retention_scan", "serving",
                                  "/prefill-")
