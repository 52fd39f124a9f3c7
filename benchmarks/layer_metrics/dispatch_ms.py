"""Mean duration of the ``engine.decode.dispatch`` spans matched in the
traced window: host time inside the call into the decode program (JAX's
argument handling, the runtime's host-to-device copies of the step's host
arrays, the hand-over to the chip), in ms."""
from benchmarks.harness import gap_trace


def read(obs, trace):
    return gap_trace.field(trace, "call_ms")
