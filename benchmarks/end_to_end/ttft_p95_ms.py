"""95th percentile, over the requests due in the window that completed, of
the engine's time to first token plus how late the generator submitted the
request (submit call time - due time).  Failed requests are counted in
``failed``.  The server does not stream: the first token's time is the
engine's own host-clock stamp."""
from benchmarks.harness.stats import percentile


def read(obs, trace):
    return percentile(obs["ttft_ms"], 95)
