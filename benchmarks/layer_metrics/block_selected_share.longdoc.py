"""Tokens a ``B`` block's query head attended (``selected_tokens``) over the
tokens the rows held (``held_tokens``), summed over the ``engine.decode``
spans in the traced window, in percent: how much of the cache the
selection keeps."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.span_args_ratio(trace, "engine.decode",
                                         "selected_tokens", "held_tokens")
