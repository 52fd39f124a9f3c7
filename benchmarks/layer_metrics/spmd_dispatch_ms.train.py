"""Mean duration of the ``spmd.dispatch`` span (the call of the jitted step
program alone, inside ``SPMDTrainer.step``) per step in the traced window,
in ms: the part of ``train_enqueue_ms`` that is JAX's dispatch, not the
trainer's wrapper."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.span_mean_ms(trace, "spmd.dispatch")
