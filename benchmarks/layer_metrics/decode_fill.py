"""Occupied share of the decode batch in the window, in percent: (tokens
generated - requests started, whose first token comes from the prefill) /
(decode iterations x decode slots)."""


def read(obs, trace):
    w = obs["window"]
    iters = w["serving.decode_step_ms"]["count"]
    if not iters:
        return None
    tokens = w["serving.tokens_generated"] - w["serving.prefill_ms"]["count"]
    return tokens / (iters * obs["slots"]) * 100.0
