"""The busiest held expert's rows over the mean rows of a held expert that
was hit, per ``G`` block and decode iteration, over the window: counters
``serving.moe_max_load`` (summed over blocks and iterations) against
``serving.moe_pairs`` / ``serving.moe_experts_hit``."""


def read(obs, trace):
    w = obs["window"]
    steps = w["serving.decode_step_ms"]["count"] \
        * obs["lm"]["pattern"].count("G")
    if not steps or not w.get("serving.moe_experts_hit"):
        return None
    return (w["serving.moe_max_load"] / steps) \
        / (w["serving.moe_pairs"] / w["serving.moe_experts_hit"])
