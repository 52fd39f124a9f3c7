"""((one index key of ``index_dim`` bf16 a scored token an ``S`` layer, and
the indexer's weights) / HBM bandwidth) / device time under
``mx.dsa_indexer`` per decode iteration
(``dsa_indexer_device_ms.sparsedoc``), in percent. The count is the least
the mathematics reads: a route that reads more reads lower."""
from benchmarks.harness import manifest


def read(obs, trace):
    ms = manifest.load_module(
        "layer_metrics", "dsa_indexer_device_ms.sparsedoc").read(obs, trace)
    return manifest.load_module(
        "layer_metrics", "roofline_share.sparsedoc").share(
            obs, trace, "mx.dsa_indexer", ms)
