"""Device self time under the ``mx.mla_proj`` name scope (latent
attention's projections outside the attention itself: the two
down-projections and their norms, the queries' up-projection, rotary, the
absorb product ``q_nope W_uk^T``, the un-absorb product ``ctx W_uv`` and
the output projection) per decode iteration, in ms, all ``L`` layers
together."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.mla_proj", "serving",
                                  "/decode-")
