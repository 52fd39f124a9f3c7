"""1 - union of device-operation intervals / traced window, in percent; on
several chips the largest over the devices."""


def read(obs, trace):
    if trace is None:
        return None
    return trace["idle_fraction_max"] * 100.0
