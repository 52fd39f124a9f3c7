"""((distinct held experts hit x one gated expert's three matrices) / HBM
bandwidth) / device time under ``mx.moe_experts`` per decode iteration
(``moe_experts_device_ms.sparsedoc``), in percent. The count is the least
the mathematics reads: a route that reads more reads lower."""
from benchmarks.harness import manifest


def read(obs, trace):
    ms = manifest.load_module(
        "layer_metrics", "moe_experts_device_ms.sparsedoc").read(obs, trace)
    return manifest.load_module(
        "layer_metrics", "roofline_share.sparsedoc").share(
            obs, trace, "mx.moe_experts", ms)
