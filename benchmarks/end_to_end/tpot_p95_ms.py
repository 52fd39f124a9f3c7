"""95th percentile over the same requests of (completion time at the client
- submit time - time to first token) / (new tokens - 1): what a reader of
the stream feels between tokens, stalls behind other requests' prefills
included."""
from benchmarks.harness.stats import percentile


def read(obs, trace):
    return percentile(obs["tpot_ms"], 95)
