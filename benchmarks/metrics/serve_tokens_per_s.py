"""Output tokens of the requests completed inside the window over the
window's length (host clock)."""


def read(obs):
    if "tokens_completed" not in obs:
        return None
    return obs["tokens_completed"] / obs["window_s"]
