"""((the SELECTED tokens' latent rows read once, one row of ``kv_rank +
rope_dim`` bf16 a selected token an ``S`` layer, for all heads, scores and
values) / HBM bandwidth) / device time under ``mx.sparse_attention`` per
decode iteration (``sparse_attention_device_ms.sparsedoc``), in percent.
The count is the least the mathematics reads: a route that reads more reads
lower."""
from benchmarks.harness import manifest


def read(obs, trace):
    ms = manifest.load_module(
        "layer_metrics", "sparse_attention_device_ms.sparsedoc").read(
            obs, trace)
    return manifest.load_module(
        "layer_metrics", "roofline_share.sparsedoc").share(
            obs, trace, "mx.sparse_attention", ms)
