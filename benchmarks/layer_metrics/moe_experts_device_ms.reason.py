"""Device time of the held experts per decode iteration, in ms, all ``E``
blocks together: self time under the ``mx.moe_experts`` name scope (pair
sort, gather, un-sort, the weighted sum) plus the two grouped products,
which are the compiler's own kernels and carry no scope
(``harness/decode_trace.py`` ``grouped_product_ms``)."""
from benchmarks.harness import decode_trace, program_trace


def read(obs, trace):
    scoped = program_trace.scope_ms(trace, "mx.moe_experts", "serving",
                                    "/decode-")
    products = decode_trace.grouped_product_ms(trace)
    if scoped is None or products is None:
        return None
    return scoped + products
