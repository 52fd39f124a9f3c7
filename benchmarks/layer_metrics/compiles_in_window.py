"""Programs compiled inside the measured window (the driver's delta of the
program's compile counters); anything but 0 also makes the run incorrect."""


def read(obs, trace):
    return obs["compiles_in_window"]
