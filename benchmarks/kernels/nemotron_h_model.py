"""Operations one token costs ONE EXPERT-PARALLEL RANK of a Nemotron-H-style
decoder in training, from the reference's sizes: what
`mfu_pct.nemotron_ep_share` divides by the chip's peak.

6 * N per token (2 operations a multiply-add forward, 4 backward), N the
weights a token multiplies with ON THIS RANK:
  * a Mamba-2 layer: the input projection to [z | x | B | C | dt], the
    width-`conv` convolution over [x | B | C], and the output projection;
  * an attention layer: the query, key, value and output projections;
  * an MoE layer: the router's whole width, the shared expert, and top_k *
    held / router routed experts: the pairs that fall on the experts held
    here when routing is balanced (0.375 of 6 with 8 of 128), not top_k; an
    expert is two matrices;
  * the untied head over the vocabulary's slice; not the embedding lookup.
Plus what has no weights: causal attention's two products over the sequence
(6 * heads * 2 * head_dim * S / 2 a token: the causal half, as
`kernels/flash_gqa.py` counts a call), and the recurrence's state, two P x
N multiply-adds a head and token (decay-and-write, read out). Recomputed
operations, the experts' padding to capacity and the chunked form's extra
products are the program's cost and are not counted.
"""

from __future__ import annotations


def rank_params(sz) -> dict[str, float]:
    """Weights a token multiplies with on this rank, by kind of layer."""
    d = sz.dim
    d_in = sz.ssm_heads * sz.ssm_head_dim
    conv_dim = d_in + 2 * sz.ssm_groups * sz.ssm_state
    mamba = (d * (d_in + conv_dim + sz.ssm_heads) + sz.conv * conv_dim
             + d_in * d)
    attn = d * (sz.heads + 2 * sz.kv_heads) * sz.head_dim \
        + sz.heads * sz.head_dim * d
    routed = sz.top_k * sz.held / sz.router
    moe = (d * sz.router + routed * 2 * d * sz.expert_hidden
           + 2 * d * sz.shared_hidden)
    return {"mamba": mamba, "attn": attn, "moe": moe, "head": sz.vocab * d}


def train_flops_per_token(sz, seq_len: int) -> float:
    p = rank_params(sz)
    count = {kind: sz.pattern.count(kind) for kind in "ME*"}
    weights = (count["M"] * p["mamba"] + count["*"] * p["attn"]
               + count["E"] * p["moe"] + p["head"])
    scores = count["*"] * sz.heads * 2 * sz.head_dim * seq_len / 2.0
    state = count["M"] * 2 * sz.ssm_heads * sz.ssm_head_dim * sz.ssm_state
    return 6.0 * (weights + scores + state)
