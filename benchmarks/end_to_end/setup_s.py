"""Process start to window open: import, weights from the seed, compile or
cache load, warm-up, traffic fill.  Host clock."""


def read(obs, trace):
    return obs["setup_s"]
