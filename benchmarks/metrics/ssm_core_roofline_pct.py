"""Roofline share of the chunked state-space recurrence: the least time the
chip could take for a step's rule, forward and backward once (operations and
bytes from `kernels/ssd_rule.py` at the configuration's `chunk_size`; peaks
from `peaks.json`), over the device time of the scope `L_ssm_core`, which
also holds what the program recomputes (remat of the layer and of the
rule's segments). At 16,384 tokens both passes are bound by memory."""
from benchmarks.kernels import ssd_rule
from benchmarks.trace import layers


def read(obs):
    spent_ms = layers.sum_ms(obs, ("L_ssm_core",))
    sz = obs.get("sizes")
    if not spent_ms or not hasattr(sz, "ssm_heads"):
        return None
    least = ssd_rule.least_seconds(
        sz, obs["seq_len"], obs["config"]["chunk_size"], obs["peaks"])
    return (100.0 * 1e3 * least * sz.pattern.count("M") * obs["batch_size"]
            / spent_ms)
