"""Tokens the decoding rows hold (``held_tokens``) over tokens the decode
program attends over (``window_tokens`` = slots x page-table width x page
size), summed over the ``engine.decode`` spans in the traced window, in
percent: how much of the K/V the iteration moves belongs to a request."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.span_args_ratio(trace, "engine.decode",
                                         "held_tokens", "window_tokens")
