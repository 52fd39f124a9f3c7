"""Mean of the engine's ``serving.decode_step_ms`` timer over the window,
from its ``total`` and ``count`` at window open and close (exact; the
timer's own percentiles are a lifetime reservoir that warm-up pollutes)."""


def read(obs, trace):
    t = obs["window"]["serving.decode_step_ms"]
    return t["total_ms"] / t["count"] if t["count"] else None
