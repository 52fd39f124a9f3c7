"""(bytes a decode iteration needs / the chip's HBM bandwidth) / device time
per execution of the decode programs in the traced window, in percent.
Bytes from ``ops_bytes/<config>.py`` at what the traced iterations held
(``engine.decode`` span arguments: distinct held experts hit, rows the
``S`` blocks attended and scored, ring columns the ``W`` blocks attended):
weights outside the routed experts once, a routed expert's three matrices
once per distinct hit, one latent row per SELECTED token, one index key per
scored token, one ring column per attended position.  Prefills are other
programs and are left out of both sides.  ``share`` is the same quotient
for one device scope, for the cell's other roofline readers."""
from benchmarks.harness import decode_trace, manifest, peaks

ARGS = ("moe_experts_hit", "selected_tokens", "index_tokens", "ring_tokens")


def share(obs, trace, scope, busy_ms):
    means = [decode_trace.decode_span_mean(trace, a) for a in ARGS]
    if not busy_ms or None in means:
        return None
    need = manifest.load_module("ops_bytes", obs["ops_bytes"]).scope_bytes(
        obs["lm"], *means)
    need = sum(need.values()) if scope is None else need[scope]
    least_ms = need / peaks.peaks(obs["device_kind"])["hbm_bytes_per_s"] * 1e3
    return least_ms / busy_ms * 100.0


def read(obs, trace):
    return share(obs, trace, None, decode_trace.decode_busy_ms(trace))
