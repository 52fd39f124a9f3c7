"""Share of the ``engine.decode`` spans in the traced window whose step was
dispatched while an earlier program's tokens were not yet fetched (the
span's ``ahead`` argument, 1 or 0), in percent: how much of the decode
loop runs one step ahead of the host.  None for a program whose spans do
not say (an engine that fetches each step before it dispatches the next)."""
from benchmarks.harness import decode_trace


def read(obs, trace):
    mean = decode_trace.decode_span_mean(trace, "ahead")
    return None if mean is None else mean * 100.0
