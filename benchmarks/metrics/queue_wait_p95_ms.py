"""95th percentile of `Request.admit_time` - time due: the wait in the
scheduler's queue (plus the generator's lateness)."""
from benchmarks.trafficgen import percentile


def read(obs):
    if "queue_wait_s" not in obs:
        return None
    return 1e3 * percentile(obs["queue_wait_s"], 95)
