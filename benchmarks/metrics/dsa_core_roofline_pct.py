"""Roofline share of attention over the indexer's keys: the least time the
chip could take for a step's NEEDED work of the mechanism, forward and
backward once (index scores of every causal pair, the selected pairs' two
products, their transposes; operations and bytes from `kernels/dsa_rule.py`,
peaks from `peaks.json`), over the device time of the four `L_dsa_*` scopes
(`dsa_ms`), which also holds the indexer's projections, the top-k, the KL,
the masked pairs a dense block multiplies and everything the program
recomputes. Answers only a family whose sizes name an indexer."""
from benchmarks.kernels import dsa_rule
from benchmarks.trace import layers


def read(obs):
    spent_ms = layers.sum_ms(obs, dsa_rule.SCOPES)
    sz = obs.get("sizes")
    if not spent_ms or not hasattr(sz, "idx_heads"):
        return None
    least = dsa_rule.least_seconds(sz, obs["seq_len"], obs["peaks"])
    return 100.0 * 1e3 * least * sz.layers * obs["batch_size"] / spent_ms
