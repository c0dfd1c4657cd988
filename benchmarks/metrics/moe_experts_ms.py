"""Device time a step of the experts' matrix products, routed and shared
(scopes `L_moe_experts`, `L_moe_shared`; device trace through
`trace/layers.py`)."""
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, ("L_moe_experts", "L_moe_shared"))
