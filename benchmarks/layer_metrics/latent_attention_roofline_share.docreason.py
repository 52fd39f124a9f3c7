"""(the active rows' latent pages read once — one row of ``kv_rank +
rope_dim`` bf16 a held token an ``L`` layer, for all heads, scores and
values / HBM bandwidth) / device time under ``mx.latent_attention`` per
decode iteration, in percent.  The count is the algorithm's, whatever
implements it: a twin that gathers the whole window first, or a kernel
that reads a page twice, reads lower."""
from benchmarks.harness import manifest


def read(obs, trace):
    ms = manifest.load_module(
        "layer_metrics", "latent_attention_device_ms.docreason").read(
            obs, trace)
    return manifest.load_module(
        "layer_metrics", "roofline_share.docreason").share(
            obs, trace, "mx.latent_attention", ms)
