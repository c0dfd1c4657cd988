"""Per request, the longest gap between two steps that brought it tokens;
95th percentile over completed requests. A prefill that stalls decoding
shows here before it shows in `tpot_p95_ms`."""
from benchmarks.trafficgen import percentile


def read(obs):
    if not obs.get("decode_gap_max_s"):
        return None
    return 1e3 * percentile(obs["decode_gap_max_s"], 95)
