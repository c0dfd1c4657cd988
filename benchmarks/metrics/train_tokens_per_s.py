"""Tokens of the steps completed in the window over the window's length
(host clock; the window ends with `block_until_ready` on the last state)."""


def read(obs):
    if "tokens_per_step" not in obs:
        return None
    return obs["steps"] * obs["tokens_per_step"] / obs["window_s"]
