"""Device time a step of the final norm, the vocabulary-wide head and the
(chunked) cross-entropy, forward and backward (scope `L_loss_head`; device
trace through `trace/layers.py`)."""
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, ("L_loss_head",))
