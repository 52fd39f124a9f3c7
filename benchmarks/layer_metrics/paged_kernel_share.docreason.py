"""Decode iterations in the traced window that ran through the latent
Pallas kernel (``mx_latent_paged_attention``) / all of them, in percent:
the ``latent_kernel`` argument the engine writes on every ``engine.decode``
span of a model of latent pages (1 where the exported program's route is
the kernel, 0 where it is the XLA twin)."""
from benchmarks.harness import decode_trace


def read(obs, trace):
    mean = decode_trace.decode_span_mean(trace, "latent_kernel")
    return None if mean is None else mean * 100.0
