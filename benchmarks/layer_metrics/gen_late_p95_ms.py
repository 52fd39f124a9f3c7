"""The benchmark's own generator: 95th percentile of submit call time - due
time.  A starved generator must not read as a fast server."""
from benchmarks.harness.stats import percentile


def read(obs, trace):
    return percentile(obs["late_ms"], 95)
