"""Device time a step of the MoE's routing: gate and one-hot, dispatch
einsum, combine einsum and balance statistics, forward and backward (the
program's scopes `L_moe_gate`, `L_moe_dispatch`, `L_moe_combine`,
`L_moe_stats`; device trace through `trace/layers.py`)."""
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, ("L_moe_gate", "L_moe_dispatch",
                               "L_moe_combine", "L_moe_stats"))
