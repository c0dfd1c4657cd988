"""The three flash attention kernels of `kernels/flash_attention.py` at the
`nemotron_h` family's shape: 32 query heads on 2 key-value heads of width
128, no rotation, one attention layer among Mamba-2 and MoE layers. The
counts are `kernels/flash_gqa.py`'s (operations of the causal half, each
operand read once); this file only says which family's trace they are held
against: one whose `Sizes` names both `kv_heads` and `ssm_heads`, so that
the reader finds nothing in another grouped-query family's cell."""

from __future__ import annotations

from benchmarks.kernels import flash_gqa


def roofline_share(obs: dict, kernels: tuple[str, ...]):
    """`flash_gqa.roofline_share` for a Nemotron-H-style decoder; None for
    any other family and where the trace has none of `kernels`."""
    if not hasattr(obs.get("sizes"), "ssm_heads"):
        return None
    return flash_gqa.roofline_share(obs, kernels)
