"""(the active rows' float32 lightning state read once and written once /
HBM bandwidth) / device time under ``mx.lightning_update`` per decode
iteration (``lightning_update_device_ms.longdoc``), in percent."""
from benchmarks.harness import manifest


def read(obs, trace):
    ms = manifest.load_module(
        "layer_metrics", "lightning_update_device_ms.longdoc").read(
            obs, trace)
    return manifest.load_module(
        "layer_metrics", "roofline_share.longdoc").share(
            obs, trace, "mx.lightning_update", ms)
