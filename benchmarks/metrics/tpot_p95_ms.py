"""95th percentile over completed requests of (time of the last token -
time of the first) / (output tokens - 1), host clock. Per request, because
the engine delivers tokens a decode block at a time."""
from benchmarks.trafficgen import percentile


def read(obs):
    if not obs.get("tpot_s"):
        return None
    return 1e3 * percentile(obs["tpot_s"], 95)
