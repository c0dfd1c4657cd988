"""Operations one token costs a DeepSeekV3-style decoder in training, from
its sizes: what `mfu_pct` divides by the chip's peak.

6 * N_active + 6 * L * n * (W_qk + W_v) * S per token: N_active counts the weights a
token multiplies with (attention projections, the gate, `top_k` routed
experts and the shared one, the tied output head; not the embedding lookup,
not the experts a token is not routed to), each costing 2 operations forward
and 4 backward. The second term is attention's score and value products
over the whole sequence (not halved for causality, the usual convention):
n heads, scores over W_qk = latent + rope_dim, values over W_v = latent.
With rope_dim 0 and n * latent = D it is the familiar 12 * L * D * S. Recomputed operations (remat) and
experts' padding to capacity are not counted: they are the program's cost,
not the model's.
"""

from __future__ import annotations


def active_params(sz) -> int:
    d, n, hd, lat, r = sz.dim, sz.heads, sz.head_dim, sz.latent, sz.rope_dim
    attn = d * lat + d * n * hd + lat * n * hd * 2 + n * hd * d
    if r:
        attn += d * n * r + d * r
    expert = 3 * d * sz.hidden
    moe = d * sz.experts + (sz.top_k + 1) * expert
    return sz.layers * (attn + moe) + sz.vocab * d


def train_flops_per_token(sz, seq_len: int) -> float:
    width = sz.heads * (2 * sz.latent + sz.rope_dim)
    return 6.0 * active_params(sz) + 6.0 * sz.layers * width * seq_len
