"""Bytes a decode iteration of a dense decoder-only transformer needs from
HBM, from the shapes: every weight once (the tied embedding is read whole by
the output head; the position table is a gather of a few rows and is not
counted) plus the cached K and V of every token the rows in flight attend
to.  Decode is bandwidth-bound: at batch 32 the weights' 2 operations per
byte sit far under the chip's ~240 operations per byte."""
from __future__ import annotations


def weight_bytes(lm, bytes_per_value=2):
    d, f, v, layers = (lm["d_model"], lm["d_ff"], lm["vocab_size"],
                       lm["num_layers"])
    per_layer = 4 * d * d + 2 * d * f + 2 * d      # qkv, out, mlp, 2 norms
    return (layers * per_layer + v * d + d) * bytes_per_value


def kv_bytes_per_token(lm, bytes_per_value=2):
    return 2 * lm["num_layers"] * lm["d_model"] * bytes_per_value


def decode_iteration_bytes(lm, rows_in_flight, mean_cached_tokens):
    """Weights once + the K/V of ``mean_cached_tokens`` per row."""
    return weight_bytes(lm) + kv_bytes_per_token(lm) * \
        rows_in_flight * mean_cached_tokens


def parameter_count(lm):
    return weight_bytes(lm, 1) + lm["max_len"] * lm["d_model"]
