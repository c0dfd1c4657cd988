"""1 - (union of the device's operation intervals) / (traced window), from
the profiler trace, mean over the chips used."""


def read(obs):
    tr = obs.get("trace")
    return None if tr is None else 100.0 * tr.idle_share
