"""How late the load generator ran: 95th percentile of (time sent - time
due). A starved generator must not read as a fast server."""
from benchmarks.trafficgen import percentile


def read(obs):
    if "late_s" not in obs:
        return None
    return 1e3 * percentile(obs["late_s"], 95)
