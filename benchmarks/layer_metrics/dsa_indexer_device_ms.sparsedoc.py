"""Device self time under the ``mx.dsa_indexer`` name scope (the index
queries, keys and head weights, the index keys gathered from the pages and
every held token's index score) per decode iteration, in ms, all its blocks
together."""
from benchmarks.harness import program_trace


def read(obs, trace):
    return program_trace.scope_ms(
        trace, "mx.dsa_indexer", "serving", "/decode-")
