"""The three flash attention kernels of `kernels/flash_attention.py` at the
`ouro` family's shape: 16 query heads on 16 key-value heads of width 128,
rotated outside the kernels, two sequences of 4,096, one call of each
kernel a LAYER APPLICATION and sequence (the same layers run in every pass
of the loop). The counts a call are `kernels/flash_gqa.py`'s (operations of
the causal half, each operand read once); this file counts the calls of a
looped decoder and says which family's trace they are held against: one
whose `Sizes` names `ut_steps`, so that the reader finds nothing in another
family's cell."""

from __future__ import annotations

from benchmarks.kernels import flash_gqa
from benchmarks.trace import layers


def roofline_share(obs: dict, kernels: tuple[str, ...]):
    """100 * (least time of a step's calls of `kernels`, one a layer
    application and sequence) / (the device time a step the trace gives
    them); None for any other family and where the trace has none of
    `kernels`."""
    sz = obs.get("sizes")
    if not hasattr(sz, "ut_steps"):
        return None
    spent_ms = layers.sum_ms(obs, kernels)
    if not spent_ms:
        return None
    calls = sz.ut_steps * sz.layers * obs["batch_size"]
    least = sum(flash_gqa.least_seconds(
        k, obs["seq_len"], sz.heads, sz.kv_heads, sz.head_dim, obs["peaks"])
        for k in kernels)
    return 100.0 * 1e3 * least * calls / spent_ms
