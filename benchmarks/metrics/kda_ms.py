"""Device time a step of the Kimi Delta Attention layers: norm, input
projections, the decay's and the gate's low-rank inputs, output norm, gate
and output projection (`L_kda_proj`), the causal convolution (`L_kda_conv`)
and the chunked rule with the decay made inside it (`L_kda_core`), forward,
backward and recomputed (device trace through `trace/layers.py`). None
against a program that has no such scopes."""
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, ("L_kda_proj", "L_kda_conv", "L_kda_core"))
