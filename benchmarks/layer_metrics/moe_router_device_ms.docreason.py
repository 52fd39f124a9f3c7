"""Device self time under the ``mx.moe_router`` name scope per decode
iteration, in ms, all ``G`` blocks together: the reader of
``moe_router_device_ms.reason``."""
from benchmarks.harness import manifest


def read(obs, trace):
    return manifest.load_module(
        "layer_metrics", "moe_router_device_ms.reason").read(obs, trace)
