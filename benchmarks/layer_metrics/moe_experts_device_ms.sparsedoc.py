"""Device time of the held experts per decode iteration, in ms, all ``G``
blocks together: the reader of ``moe_experts_device_ms.reason`` (self time
under ``mx.moe_experts``)."""
from benchmarks.harness import manifest


def read(obs, trace):
    return manifest.load_module(
        "layer_metrics", "moe_experts_device_ms.reason").read(obs, trace)
