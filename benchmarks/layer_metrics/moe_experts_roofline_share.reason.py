"""(distinct held experts hit x one expert's bytes / HBM bandwidth) / the
held experts' device time per decode iteration (the reader of
``moe_experts_device_ms``: the scope and the grouped products), in
percent."""
from benchmarks.harness import decode_trace, manifest


def read(obs, trace):
    ms = manifest.load_module("layer_metrics",
                              "moe_experts_device_ms.reason").read(obs, trace)
    return decode_trace.share_of_roofline(obs, trace, "mx.moe_experts", ms)
