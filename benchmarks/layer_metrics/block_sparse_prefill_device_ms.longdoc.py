"""Device self time under the ``mx.block_sparse_prefill`` name scope (the
masked K/V-tiled flash kernel ``mx_attention_tiled_masked`` under a mask of
blocks, or its XLA twin) per prefill in the traced window, in ms, all ``B``
blocks together."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.block_sparse_prefill",
                                  "serving", "/prefill-")
