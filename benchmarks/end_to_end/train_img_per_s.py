"""Global batch x steps completed in the window / window seconds; the window
ends in ``block_until_ready`` on the last loss.  Host clock."""


def read(obs, trace):
    return obs["global_batch"] * obs["steps"] / obs["window_s"]
