"""Device self time under the ``mx.sparse_attention`` name scope (the sparse
latent kernel ``mx_sparse_latent_attention`` (whole pages copied, the
unselected tokens masked) or its XLA twin) per decode iteration, in ms, all
its blocks together."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(
        trace, "mx.sparse_attention", "serving", "/decode-")
