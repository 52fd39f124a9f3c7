"""Published peaks per chip, keyed by ``device_kind`` as jax reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s per chip.
A device that is not in the table is an error, not a default."""
from __future__ import annotations

PEAKS = {
    # jax reports a v5e chip as "TPU v5 lite"
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


class UnknownDevice(Exception):
    pass


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            "device kind %r is not in benchmarks/harness/peaks.py; add it "
            "with its published source" % device_kind) from None
