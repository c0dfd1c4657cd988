"""Device time a step of the dense SwiGLU layers with their norm and
residual add (scope `L_dense_ffn`), forward, backward and recomputed
(device trace through `trace/layers.py`). None against a program that has
no such scope."""
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, ("L_dense_ffn",))
