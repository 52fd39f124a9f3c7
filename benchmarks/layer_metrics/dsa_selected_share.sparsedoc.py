"""Rows the ``S`` blocks attended (``selected_tokens``) over rows their
indexer scored (``index_tokens``), summed over the ``engine.decode`` spans
in the traced window, in percent: how much of the cache the selection
keeps."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.span_args_ratio(trace, "engine.decode",
                                         "selected_tokens", "index_tokens")
