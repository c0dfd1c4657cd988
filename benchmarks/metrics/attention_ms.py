"""Device time a step of attention: MLA's projections, RoPE and output
projection, the attention product, and the flash kernels where the
configuration uses them (scopes `L_attn_proj`, `L_attn_core` and the three
kernels' names; device trace through `trace/layers.py`)."""
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, ("L_attn_proj", "L_attn_core") + layers.KERNELS)
