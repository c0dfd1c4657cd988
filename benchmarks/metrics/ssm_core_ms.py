"""Device time a step of the chunked state-space recurrence alone (scope
`L_ssm_core`: running sums and decays, C B^T a group, the chunks' own
parts, the states between chunks and segments), all layers, forward,
backward and recomputed (device trace through `trace/layers.py`)."""
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, ("L_ssm_core",))
