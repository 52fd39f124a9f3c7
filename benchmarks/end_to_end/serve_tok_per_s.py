"""Output tokens the engine produced in the window (the delta of its
``serving.tokens_generated`` counter, which the run checks against the
tokens its futures returned) / window seconds.  Host clock."""


def read(obs, trace):
    return obs["window"]["serving.tokens_generated"] / obs["window_s"]
