"""(operations a training step needs / the chip's bf16 peak) / device time
per step, in percent.  Compute-bound: the operations come from the layer
shapes (``ops_bytes/<config>.py``), not from what the compiler emitted."""
from benchmarks.harness import manifest, peaks


def read(obs, trace):
    if trace is None or not obs.get("traced_steps"):
        return None
    ops = manifest.load_module("ops_bytes", obs["ops_bytes"])
    flops = ops.step_flops(obs["sizes"], obs["per_chip_batch"])
    least_s = flops / peaks.peaks(obs["device_kind"])["bf16_flops"]
    return least_s / (trace["busy_s"] / obs["traced_steps"]) * 100.0
