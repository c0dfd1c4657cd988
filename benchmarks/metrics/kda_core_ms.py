"""Device time a step of the chunked delta rule with a decay per key
channel alone (scope `L_kda_core`: the decays from their low-rank input,
the chunks' pair sums and triangular systems, the scan over chunks that
carries the state), all layers, forward, backward and recomputed (device
trace through `trace/layers.py`)."""
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, ("L_kda_core",))
