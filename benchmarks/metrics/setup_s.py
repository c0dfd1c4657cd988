"""Process start to the start of the measured window (host clock)."""


def read(obs):
    return obs["setup_s"]
