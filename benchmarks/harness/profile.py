"""The device trace of a run: ``jax.profiler`` with the python tracer off
(the benchmark's ``TraceAnnotation``s and the device's own lines are what the
reduction reads) and without the HLO protos, which keeps the file small."""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def traced_window(trace_dir):
    """Trace the block; inside it, the span named ``bench.window`` is what
    ``trace_reduce`` takes for the window."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        jax.profiler.stop_trace()
