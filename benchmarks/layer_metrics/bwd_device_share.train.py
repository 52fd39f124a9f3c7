"""Device busy time of the backward pass — operations whose op_name runs
through ``transpose(`` over the ``mx.forward`` scope — as a percentage of
busy time in the traced window (``harness/program_trace.py``)."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.busy_share(trace, "backward_of_forward_s")
