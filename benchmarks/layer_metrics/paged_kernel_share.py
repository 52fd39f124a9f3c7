"""Decode iterations in the window that ran through the paged Pallas kernel
(``kernels.paged_attention``) / all decode iterations, in percent."""


def read(obs, trace):
    w = obs["window"]
    iters = w["serving.decode_step_ms"]["count"]
    return w["kernels.paged_attention"] / iters * 100.0 if iters else None
