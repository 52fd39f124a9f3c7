"""((K and V of one head, 512 B at heads of 128, for every token a K/V
head's selection kept) / HBM bandwidth) / device time under
``mx.block_sparse_attention`` per decode iteration
(``block_sparse_attention_device_ms.longdoc``), in percent.  The count is
the least the mathematics reads: a route that reads both heads of a page,
or more pages than were kept, reads lower."""
from benchmarks.harness import manifest


def read(obs, trace):
    ms = manifest.load_module(
        "layer_metrics", "block_sparse_attention_device_ms.longdoc").read(
            obs, trace)
    return manifest.load_module(
        "layer_metrics", "roofline_share.longdoc").share(
            obs, trace, "mx.block_sparse_attention", ms)
