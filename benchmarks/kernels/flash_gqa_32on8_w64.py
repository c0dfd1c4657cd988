"""The three flash attention kernels of `kernels/flash_attention.py` at the
Granite-hybrid family's shape: 32 query heads on 8 key-value heads of width
64 (half the 128 lanes), no rotation, the softmax scaled by 1/64, one
attention layer among nine Mamba-2 layers. The counts are
`kernels/flash_gqa.py`'s (operations of the causal half at the width the
algorithm needs, 64, whatever lanes the tiles take; each operand read
once); this file only says which family's trace they are held against: one
whose `Sizes` names 8 key-value heads of width 64 and `ssm_heads`, so that
the reader finds nothing in another grouped-query family's cell."""

from __future__ import annotations

from benchmarks.kernels import flash_gqa


def roofline_share(obs: dict, kernels: tuple[str, ...]):
    """`flash_gqa.roofline_share` for a decoder with 8 key-value heads of
    width 64 beside state-space layers; None for any other family and where
    the trace has none of `kernels`."""
    sz = obs.get("sizes")
    if not hasattr(sz, "ssm_heads") or (
            getattr(sz, "kv_heads", None), getattr(sz, "head_dim", None)
    ) != (8, 64):
        return None
    return flash_gqa.roofline_share(obs, kernels)
