"""Per ``engine.iteration`` span in the traced window: its duration less
its ``engine.prefill.device`` / ``engine.decode.device`` children (and an
``engine.wait`` for pages, should one fall inside), in ms — the host time
the engine thread spends between device calls (admission, preparing the
inputs, emitting tokens and resolving futures).  The split over the
children is in ``chiprun_out/<cell>.program_trace.json``."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.host_ms(trace, "engine.iteration",
                                 (".device", ".wait"))
