"""Device self time under the ``mx.block_select`` name scope (the
compressed keys scored, summed over each group's query heads, the largest
over a block's kernels, the top-k and the kept pages in order) per decode
iteration, in ms, all ``B`` blocks together."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.block_select", "serving",
                                  "/decode-")
