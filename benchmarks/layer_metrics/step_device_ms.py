"""Device busy time in the traced window / training steps in it (the traced
window starts and ends with the device drained, so the steps are exact)."""


def read(obs, trace):
    if trace is None or not obs.get("traced_steps"):
        return None
    return trace["busy_s"] / obs["traced_steps"] * 1e3
