"""(the operations of the (query head, key) pairs the prefills' selections
kept, at the traced prefills' mean prompt length (``engine.prefill`` spans'
``prompt_len``; ``ops_bytes/<config>.py``: ``prefill_pair_flops``) / the
chip's bf16 peak) / device time under ``mx.block_sparse_prefill`` per
prefill (``block_sparse_prefill_device_ms.longdoc``), in percent.  The
count is the selection's: a pass that computes every causal pair and masks
the ones not kept reads as low as the share it throws away."""
from benchmarks.harness import manifest, peaks, program_trace


def read(obs, trace):
    ms = manifest.load_module(
        "layer_metrics", "block_sparse_prefill_device_ms.longdoc").read(
            obs, trace)
    out = program_trace._loaded(trace)
    span = out["spans"].get("engine.prefill") if out else None
    if not ms or not span or "prompt_len" not in span["args"]:
        return None
    flops = manifest.load_module("ops_bytes", obs["ops_bytes"]) \
        .prefill_pair_flops(obs["lm"],
                            span["args"]["prompt_len"] / span["count"])
    least_ms = flops / peaks.peaks(obs["device_kind"])["bf16_flops"] * 1e3
    return least_ms / ms * 100.0
