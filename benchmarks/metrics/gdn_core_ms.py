"""Device time a step of the chunked gated delta rule alone (scope
`L_gdn_core`: the chunks' triangular systems and the scan over chunks that
carries the state), all layers, forward, backward and recomputed (device
trace through `trace/layers.py`)."""
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, ("L_gdn_core",))
