"""Mean of the engine's ``serving.prefill_ms`` timer over the window, from
its ``total`` and ``count`` at window open and close."""


def read(obs, trace):
    t = obs["window"]["serving.prefill_ms"]
    return t["total_ms"] / t["count"] if t["count"] else None
