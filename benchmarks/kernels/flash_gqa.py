"""The three flash attention kernels of `kernels/flash_attention.py` at a
grouped-query shape (n query heads on n_kv key/value heads of one width w,
scores and values alike), and what each call needs by the algorithm.
`kernels/flash_mla.py` counts the same kernels for latent attention (one
shared head, k = v); this file is for a family whose `Sizes` names
`kv_heads` and `head_dim`.

A kernel is found in the trace by the `name=` of its `pallas_call`
(`trace/layers.py` KERNELS). Operations: causal attention of S queries over
S keys, 2 per multiply-add, half of S * S, what causality leaves. fwd: QK^T
and PV. bwd_dq: QK^T again, dO V^T, dS K. bwd_dkv: QK^T again, P^T dO,
dO V^T, dS^T Q. Bytes: each operand read once, each result written once;
q, o, do, dq are (n, S, w), k, v, dk, dv (n_kv, S, w), lse and delta (n, S)
float32. Recomputation under remat is the program's cost and not counted.
"""

from __future__ import annotations

from benchmarks.trace import layers

PRODUCTS = {"flash_mla_fwd": 2, "flash_mla_bwd_dq": 3, "flash_mla_bwd_dkv": 4}


def flops(kernel: str, seq: int, heads: int, width: int) -> float:
    return PRODUCTS[kernel] * 2.0 * heads * (seq * seq / 2.0) * width


def hbm_bytes(kernel: str, seq: int, heads: int, kv_heads: int, width: int,
              itemsize: int = 2) -> float:
    q = heads * seq * width * itemsize
    kv = 2 * kv_heads * seq * width * itemsize
    row = heads * seq * 4
    return {"flash_mla_fwd": q + kv + q + row,
            "flash_mla_bwd_dq": q + kv + q + 2 * row + q,
            "flash_mla_bwd_dkv": q + kv + q + 2 * row + kv}[kernel]


def least_seconds(kernel: str, seq: int, heads: int, kv_heads: int,
                  width: int, peaks: dict) -> float:
    """The least time the chip could take for one call over one sequence:
    the larger of its operations over the bf16 peak and its bytes over the
    HBM peak (at 16,384 tokens every call is bound by compute)."""
    return max(flops(kernel, seq, heads, width) / peaks["bf16_flops_per_s"],
               hbm_bytes(kernel, seq, heads, kv_heads, width)
               / peaks["hbm_bytes_per_s"])


def roofline_share(obs: dict, kernels: tuple[str, ...]):
    """100 * (least time of a step's calls of `kernels`, one a
    softmax-attention layer and sequence) / (the device time a step the
    trace gives them); None where the trace has none or the family is not
    a grouped-query one."""
    spent_ms = layers.sum_ms(obs, kernels)
    sz = obs.get("sizes")
    if not spent_ms or not hasattr(sz, "kv_heads"):
        return None
    calls = sum(sz.is_attention(i) for i in range(sz.layers)) \
        * obs["batch_size"]
    least = sum(least_seconds(k, obs["seq_len"], sz.heads, sz.kv_heads,
                              sz.head_dim, obs["peaks"]) for k in kernels)
    return 100.0 * 1e3 * least * calls / spent_ms
