"""Device self time under the ``mx.retention_update`` name scope (every
decode slot's float32 retention state and normaliser decayed, fed the new
key's symmetric square and read out by the slot's query heads) per decode
iteration, in ms, all ``R`` blocks together."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.retention_update", "serving",
                                  "/decode-")
