"""Operations one token costs ONE EXPERT-PARALLEL RANK of a Kimi-Linear-style
decoder in training, from the reference's sizes: what
`mfu_pct.kimi_ep_share` divides by the chip's peak.

6 * N per token (2 operations a multiply-add forward, 4 backward), N the
weights a token multiplies with ON THIS RANK:
  * a KDA layer: the [q | k | v] projection, the [f | o | b] projection,
    the two low-rank up-projections (decay, output gate), the
    width-`conv` convolution over q, k, v, and the output projection;
  * the latent-attention layer: the query projection, the down-projection
    to [latent | positional part], the up-projection to [k_nope | v] a
    head, and the output projection;
  * the dense layers' SwiGLU;
  * every MoE layer: the router's whole width, the shared expert, and
    top_k * held / router routed experts: the pairs that fall on the
    experts held here when routing is balanced (0.25 of 8 with 8 of 256),
    not top_k;
  * the untied head over the vocabulary's slice; not the embedding lookup.
Plus what has no weights: causal attention's two products over the sequence
(6 * heads * (key width + value width) * S / 2 a token: the causal half, as
`kernels/flash_mla_nope.py` counts a call), and the delta rule's state,
three dk x dv multiply-adds a head and token (decay-and-read, write, read
out). Recomputed operations, the experts' padding to capacity and the
chunked form's extra products are the program's cost and are not counted.
"""

from __future__ import annotations


def rank_params(sz) -> dict[str, float]:
    """Weights a token multiplies with on this rank, by kind of layer."""
    d, dk = sz.dim, sz.kda_dim
    n = sz.kda_heads * dk
    kda = (d * 3 * n + d * (2 * dk + sz.kda_heads) + 2 * dk * n
           + sz.conv * 3 * n + n * d)
    attn = (d * sz.heads * (sz.nope_dim + sz.rope_dim)
            + d * (sz.latent + sz.rope_dim)
            + sz.latent * sz.heads * (sz.nope_dim + sz.v_dim)
            + sz.heads * sz.v_dim * d)
    routed = sz.top_k * sz.held / sz.router
    moe = (d * sz.router + routed * 3 * d * sz.expert_hidden
           + 3 * d * sz.shared_hidden)
    return {"kda": kda, "attn": attn, "dense": 3 * d * sz.dense_hidden,
            "moe": moe, "head": sz.vocab * d}


def train_flops_per_token(sz, seq_len: int) -> float:
    p = rank_params(sz)
    n_attn = sum(sz.is_attention(i) for i in range(sz.layers))
    n_kda = sz.layers - n_attn
    n_dense = sum(sz.is_dense(i) for i in range(sz.layers))
    weights = (n_kda * p["kda"] + n_attn * p["attn"] + n_dense * p["dense"]
               + (sz.layers - n_dense) * p["moe"] + p["head"])
    scores = n_attn * sz.heads * (sz.nope_dim + sz.rope_dim + sz.v_dim) \
        * seq_len / 2.0
    state = n_kda * 3 * sz.kda_heads * sz.kda_dim * sz.kda_dim
    return 6.0 * (weights + scores + state)
