"""Roofline share of the chunked gated delta rule: the least time the chip
could take for a step's rule, forward and backward once (operations and
bytes from `kernels/gated_delta_rule.py` at the program's chunk size,
`ops.gated_delta.CHUNK`; peaks from `peaks.json`), over the device time of the scope `L_gdn_core`, which
also holds what the program recomputes (remat of the layer and of the
rule's segments). At 16,384 tokens both passes are bound by memory."""
from benchmarks.kernels import gated_delta_rule
from benchmarks.trace import layers


def read(obs):
    spent_ms = layers.sum_ms(obs, ("L_gdn_core",))
    sz = obs.get("sizes")
    if not spent_ms or not hasattr(sz, "gdn_v_heads"):
        return None
    n_layers = sum(not sz.is_attention(i) for i in range(sz.layers))
    from solvingpapers_tpu.ops.gated_delta import CHUNK

    least = gated_delta_rule.least_seconds(
        sz, obs["seq_len"], CHUNK, obs["peaks"])
    return 100.0 * 1e3 * least * n_layers * obs["batch_size"] / spent_ms
