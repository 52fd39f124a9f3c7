"""Device self time under the ``mx.block_sparse_attention`` name scope (the
paged kernel ``mx_paged_attention`` over each row's kept pages, one K/V
head a row, or its XLA twin) per decode iteration, in ms, all ``B`` blocks
together."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.block_sparse_attention",
                                  "serving", "/decode-")
