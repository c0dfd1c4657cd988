"""The three flash attention kernels of `kernels/flash_attention.py` at the
shape of latent attention trained decompressed: n heads, each with its own
keys `wk` wide ([nope | shared positional part]) and values `wv` wide, and
what each call needs by the algorithm. `kernels/flash_mla.py` counts the
same kernels for the absorbed form (one shared head, k = v),
`kernels/flash_gqa.py` for grouped queries of one width; this file is for a
family whose `Sizes` names `nope_dim`, `rope_dim` and `v_dim`.

A kernel is found in the trace by the `name=` of its `pallas_call`
(`trace/layers.py` KERNELS). Operations: causal attention of S queries over
S keys, 2 per multiply-add, half of S * S, what causality leaves, a product
over the key width or over the value width. fwd: QK^T (wk) and PV (wv).
bwd_dq: QK^T again (wk), dO V^T (wv), dS K (wk). bwd_dkv: QK^T again (wk),
P^T dO (wv), dO V^T (wv), dS^T Q (wk). Bytes: each operand read once, each
result written once; q, k, dq, dk are (n, S, wk), v, o, do, dv (n, S, wv),
lse and delta (n, S) float32. Recomputation under remat (the layer's second
forward calls the forward kernel again) is the program's cost and not
counted: one call a layer of each kernel.
"""

from __future__ import annotations

from benchmarks.trace import layers

# (products over the key width, products over the value width)
PRODUCTS = {"flash_mla_fwd": (1, 1), "flash_mla_bwd_dq": (2, 1),
            "flash_mla_bwd_dkv": (2, 2)}


def flops(kernel: str, seq: int, heads: int, wk: int, wv: int) -> float:
    over_k, over_v = PRODUCTS[kernel]
    return 2.0 * heads * (seq * seq / 2.0) * (over_k * wk + over_v * wv)


def hbm_bytes(kernel: str, seq: int, heads: int, wk: int, wv: int,
              itemsize: int = 2) -> float:
    key = heads * seq * wk * itemsize
    val = heads * seq * wv * itemsize
    row = heads * seq * 4
    return {"flash_mla_fwd": 2 * key + val + val + row,
            "flash_mla_bwd_dq": 2 * key + 2 * val + 2 * row + key,
            "flash_mla_bwd_dkv": 2 * key + 2 * val + 2 * row + key + val,
            }[kernel]


def least_seconds(kernel: str, seq: int, heads: int, wk: int, wv: int,
                  peaks: dict) -> float:
    """The least time the chip could take for one call over one sequence:
    the larger of its operations over the bf16 peak and its bytes over the
    HBM peak (at 16,384 tokens every call is bound by compute)."""
    return max(flops(kernel, seq, heads, wk, wv) / peaks["bf16_flops_per_s"],
               hbm_bytes(kernel, seq, heads, wk, wv)
               / peaks["hbm_bytes_per_s"])


def roofline_share(obs: dict, kernels: tuple[str, ...]):
    """100 * (least time of a step's calls of `kernels`, one a
    latent-attention layer and sequence) / (the device time a step the
    trace gives them); None where the trace has none or the family is not
    this one."""
    spent_ms = layers.sum_ms(obs, kernels)
    sz = obs.get("sizes")
    if not spent_ms or not hasattr(sz, "nope_dim"):
        return None
    calls = sum(sz.is_attention(i) for i in range(sz.layers)) \
        * obs["batch_size"]
    least = sum(least_seconds(k, obs["seq_len"], sz.heads,
                              sz.nope_dim + sz.rope_dim, sz.v_dim,
                              obs["peaks"]) for k in kernels)
    return 100.0 * 1e3 * least * calls / spent_ms
