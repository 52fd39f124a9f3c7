"""(distinct held experts hit x one gated expert's three matrices / HBM
bandwidth) / the held experts' device time per decode iteration
(``moe_experts_device_ms.docreason``), in percent."""
from benchmarks.harness import manifest


def read(obs, trace):
    ms = manifest.load_module(
        "layer_metrics", "moe_experts_device_ms.docreason").read(obs, trace)
    return manifest.load_module(
        "layer_metrics", "roofline_share.docreason").share(
            obs, trace, "mx.moe_experts", ms)
