"""95th percentile, over the requests sent in the window, of the time from
when a request was due to the first `eng.step()` after which it showed a
token (host clock). A request that failed or never got a token counts as
infinite."""
from benchmarks.trafficgen import percentile


def read(obs):
    if "ttft_s" not in obs:
        return None
    return 1e3 * percentile(obs["ttft_s"], 95)
