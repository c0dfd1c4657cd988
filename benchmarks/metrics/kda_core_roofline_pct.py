"""Roofline share of the chunked delta rule with a decay per key channel:
the least time the chip could take for a step's rule, forward and backward
once (operations and bytes from `kernels/kda_rule.py` at the program's
chunk size, `ops.kda.CHUNK`; peaks from `peaks.json`), over the device time
of the scope `L_kda_core`, which also holds what the program recomputes
(remat of the layer and of the rule's segments) and the decays made from
their low-rank input. At 16,384 tokens both passes are bound by memory."""
from benchmarks.kernels import kda_rule
from benchmarks.trace import layers


def read(obs):
    spent_ms = layers.sum_ms(obs, ("L_kda_core",))
    sz = obs.get("sizes")
    if not spent_ms or not hasattr(sz, "kda_heads"):
        return None
    n_layers = sum(not sz.is_attention(i) for i in range(sz.layers))
    from solvingpapers_tpu.ops.kda import CHUNK

    least = kda_rule.least_seconds(sz, obs["seq_len"], CHUNK, obs["peaks"])
    return 100.0 * 1e3 * least * n_layers * obs["batch_size"] / spent_ms
