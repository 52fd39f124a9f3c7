"""Device self time under the ``mx.latent_attention`` name scope (the
absorbed decode kernel ``mx_latent_paged_attention``, or its XLA twin's
softmax and products) per decode iteration, in ms, all ``L`` layers
together."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.latent_attention", "serving",
                                  "/decode-")
