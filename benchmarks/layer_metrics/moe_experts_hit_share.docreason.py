"""Held experts hit a ``G`` block a decode iteration, as a percentage of
the experts held, over the window: counter ``serving.moe_experts_hit``
(``dropless_experts``' ``experts_hit``, summed over blocks and iterations).
Every seed computes the same (token, expert) pairs; this is the share of
the experts' weights those pairs make an iteration read, the number that
must not differ by seed."""


def read(obs, trace):
    w = obs["window"]
    lm = obs["lm"]
    steps = w["serving.decode_step_ms"]["count"] * lm["pattern"].count("G")
    held = lm.get("experts_held") or lm["num_experts"]
    if not steps or "serving.moe_experts_hit" not in w:
        return None
    return w["serving.moe_experts_hit"] / (steps * held) * 100.0
