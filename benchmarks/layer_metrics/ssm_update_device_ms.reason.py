"""Device self time under the ``mx.ssm_update`` name scope (the float32
recurrent state of every decode slot decayed, fed and read out) per decode
iteration, in ms, all ``M`` blocks together."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(trace, "mx.ssm_update", "serving",
                                  "/decode-")
