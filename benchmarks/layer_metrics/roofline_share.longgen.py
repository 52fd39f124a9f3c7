"""(bytes a decode iteration needs / the chip's HBM bandwidth) / device busy
time per decode iteration in the traced window, in percent.  Bandwidth-bound.
The device time includes the prefills that ran in the traced window (they
cannot be told apart until kernels carry stable names) and says so.  Bytes
from ``ops_bytes/<config>.py``: all weights once + K/V bytes per token x the
tokens cached for the rows in flight (per request alive in the traced
window, prompt + half its new tokens, weighted by its new tokens; rows in
flight = tokens decoded / decode iterations, in the traced window)."""
from benchmarks.harness import manifest, peaks


def read(obs, trace):
    t = obs.get("traced")
    if trace is None or not t or obs.get("mean_cached_tokens") is None:
        return None
    iters = t["serving.decode_step_ms"]["count"]
    if not iters:
        return None
    rows = (t["serving.tokens_generated"]
            - t["serving.prefill_ms"]["count"]) / iters
    ops = manifest.load_module("ops_bytes", obs["ops_bytes"])
    need = ops.decode_iteration_bytes(obs["lm"], rows,
                                      obs["mean_cached_tokens"])
    least_s = need / peaks.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    return least_s / (trace["busy_s"] / iters) * 100.0
