"""Device self time under the ``mx.lightning_update`` name scope (the
retention update kernel ``mx_retention_update`` with the key and query
themselves in the expanded ones' place, or its XLA twin, and the elementwise
work beside it) per decode iteration, in ms, all ``N`` blocks together."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.lightning_update", "serving",
                                  "/decode-")
