"""Per training step, the time in which a collective ran on device 0 and no
other operation did."""


def read(obs, trace):
    if trace is None or not obs.get("traced_steps"):
        return None
    return trace["collective_exposed_s"] / obs["traced_steps"] * 1e3
