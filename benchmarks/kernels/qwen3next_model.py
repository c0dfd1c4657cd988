"""Operations one token costs ONE EXPERT-PARALLEL RANK of a Qwen3-Next-style
decoder in training, from the reference's sizes: what `mfu_pct.ep_share`
divides by the chip's peak.

6 * N per token (2 operations a multiply-add forward, 4 backward), N the
weights a token multiplies with ON THIS RANK:
  * a Gated DeltaNet layer: the two input projections, the width-`conv`
    convolution over q, k, v, and `out_proj`;
  * the gated attention layer: q (with its gate), k, v and o projections;
  * every MoE layer: the router's whole width, the shared expert with its
    gate, and top_k * held / router routed experts: the pairs that fall on
    the experts held here when routing is balanced (0.625 of 10 with 32 of
    512), not top_k;
  * the untied head over the vocabulary's slice; not the embedding lookup.
Plus what has no weights: causal attention's two products over the sequence
(6 * heads * 2 * head_dim * S / 2 a token: the causal half, as
`kernels/flash_mla.py` counts a call), and the delta rule's state, three
dk x dv multiply-adds a value head and token (decay-and-read, write, read
out). Recomputed operations, the experts' padding to capacity and the
chunked form's extra products are the program's cost and are not counted.
"""

from __future__ import annotations


def rank_params(sz) -> dict[str, float]:
    """Weights a token multiplies with on this rank, by kind of layer."""
    d = sz.dim
    n_qk, n_v = sz.gdn_k_heads * sz.gdn_k_dim, sz.gdn_v_heads * sz.gdn_v_dim
    gdn = (d * (2 * n_qk + 2 * n_v) + d * 2 * sz.gdn_v_heads
           + sz.conv * (2 * n_qk + n_v) + n_v * d)
    width = sz.heads * sz.head_dim
    attn = d * 2 * width + 2 * d * sz.kv_heads * sz.head_dim + width * d
    routed = sz.top_k * sz.held / sz.router
    moe = (d * sz.router + routed * 3 * d * sz.expert_hidden
           + 3 * d * sz.shared_hidden + d)
    return {"gdn": gdn, "attn": attn, "moe": moe, "head": sz.vocab * d}


def train_flops_per_token(sz, seq_len: int) -> float:
    p = rank_params(sz)
    n_attn = sum(sz.is_attention(i) for i in range(sz.layers))
    n_gdn = sz.layers - n_attn
    weights = (n_gdn * p["gdn"] + n_attn * p["attn"]
               + sz.layers * p["moe"] + p["head"])
    scores = n_attn * sz.heads * 2 * sz.head_dim * seq_len / 2.0
    state = n_gdn * 3 * sz.gdn_v_heads * sz.gdn_k_dim * sz.gdn_v_dim
    return 6.0 * (weights + scores + state)
