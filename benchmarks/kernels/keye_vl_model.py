"""Operations one token costs ONE EXPERT-PARALLEL RANK of a Keye-VL-2.0-style
decoder (attention over an indexer's keys, held experts, no shared expert)
in training, from the reference's sizes: what `mfu_pct.keye_ep_share`
divides by the chip's peak.

6 * N per token (2 operations a multiply-add forward, 4 backward), N the
weights a token multiplies with ON THIS RANK:
  * every layer's attention: the query, key, value and output projections
    and the indexer's three;
  * every layer's MoE: the router's whole width and top_k * held / router
    routed experts: the pairs that fall on the experts held here when
    routing is balanced (1 of 8 with 16 of 128), not top_k; no shared one;
  * the untied head over the vocabulary's slice; not the embedding lookup.
Plus what has no weights, as `kernels/dsa_rule.py` counts it a sequence:
attention's two products over the SELECTED pairs, not the causal ones, and
the index scores, every causal pair forward and the selected ones backward.
Recomputed operations, the masked pairs a dense block multiplies, the top-k,
the experts' padding to capacity are the program's cost and not counted.
"""

from __future__ import annotations

from benchmarks.kernels import dsa_rule


def rank_params(sz) -> dict[str, float]:
    """Weights a token multiplies with on this rank, by part of a layer."""
    d = sz.dim
    attn = (d * (sz.heads + 2 * sz.kv_heads) * sz.head_dim
            + sz.heads * sz.head_dim * d)
    indexer = d * ((sz.idx_heads + 1) * sz.idx_dim + sz.idx_heads)
    routed = sz.top_k * sz.held / sz.router
    moe = d * sz.router + routed * 3 * d * sz.expert_hidden
    return {"attn": attn, "indexer": indexer, "moe": moe,
            "head": sz.vocab * d}


def train_flops_per_token(sz, seq_len: int) -> float:
    p = rank_params(sz)
    weights = sz.layers * (p["attn"] + p["indexer"] + p["moe"]) + p["head"]
    mechanism = sz.layers * sum(
        dsa_rule.flops(pass_, sz, seq_len) for pass_ in dsa_rule.PASSES
    ) / seq_len
    return 6.0 * weights + mechanism
