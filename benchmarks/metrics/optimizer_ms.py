"""Device time a step of the gradient norm, the clip, the AdamW update
and `apply_updates` (scope `L_optimizer`; device trace through
`trace/layers.py`)."""
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, ("L_optimizer",))
