"""Back-to-back training steps on one seeded batch that lives on the device.

Parameters (the traffic file): ``per_chip_batch``, ``in_flight`` (steps the
host keeps enqueued ahead of the one it waits for), ``warm_steps``.  A pure
function of seed and parameters."""
from __future__ import annotations

import numpy as np

from benchmarks.harness.stats import fold_seed


def generate(seed, params, sizes, chips):
    batch = int(params["per_chip_batch"]) * int(chips)
    rng = np.random.default_rng(fold_seed(seed))
    image = int(sizes["image"])
    data = rng.random((batch, 3, image, image), dtype=np.float32)
    label = rng.integers(0, int(sizes["classes"]), (batch,)).astype(
        np.float32)
    return {"data": data, "label": label, "batch": batch,
            "in_flight": int(params["in_flight"]),
            "warm_steps": int(params["warm_steps"])}
