"""What attention over the keys a lightning indexer picks (DeepSeek Sparse
Attention; `ops/dsa.py`, scopes `L_dsa_index`, `L_dsa_select`,
`L_dsa_attend`, `L_dsa_loss`) NEEDS for one layer and one sequence, from the
sizes alone and whatever implements it: operations and bytes, forward and
backward.

Pairs: a sequence of S tokens has S (S + 1) / 2 causal (query, key) pairs; a
query past position `topk` selects `topk` keys, an earlier one all it has:
topk (topk + 1) / 2 + (S - topk) topk selected pairs (23.4% of the causal
ones at 16,384 tokens and 2,048).

Operations (2 per multiply-add):
  * forward: the index score of EVERY causal pair, J heads of D (the
    selection needs them all); QK^T and PV of every SELECTED pair at N heads
    of W;
  * backward: the index scores' two transposes over the selected pairs
    (the KL's gradient is zero elsewhere); dO V^T, P^T dO, dS K, dS^T Q of
    every selected pair.
The top-k itself (comparisons, no multiply-add), the softmaxes, the KL's
own logarithms and sums, the masked pairs a dense block multiplies all the
same, and every recomputation (a layer's remat, a block's) are the
program's cost, not the algorithm's: they are in the reader's denominator
only, so a masked dense block reads low.

Bytes: each operand read once and each result written once, in the
program's dtypes: q and the output (S, N, W), k and v (S, G, W), the
indexer's queries (S, J, D) and key (S, D) at `itemsize` bytes, its weights
(S, J) and p_t (one float32 a selected pair) float32. Forward reads q, k, v
and the indexer's three and writes the output and p_t; backward reads those,
p_t and the output's gradient, and writes the six gradients.
"""

from __future__ import annotations

PASSES = ("fwd", "bwd")
# the mechanism's layer scopes, whose device time the readers sum
SCOPES = ("L_dsa_index", "L_dsa_select", "L_dsa_attend", "L_dsa_loss")


def causal_pairs(seq: int) -> float:
    return seq * (seq + 1) / 2.0


def selected_pairs(seq: int, topk: int) -> float:
    if seq <= topk:
        return causal_pairs(seq)
    return causal_pairs(topk) + (seq - topk) * float(topk)


def flops(pass_: str, sz, seq: int) -> float:
    index = 2.0 * sz.idx_heads * sz.idx_dim
    attend = 2.0 * sz.heads * sz.head_dim
    chosen = selected_pairs(seq, sz.topk)
    if pass_ == "fwd":
        return index * causal_pairs(seq) + 2 * attend * chosen
    if pass_ == "bwd":
        return (2 * index + 4 * attend) * chosen
    raise ValueError(pass_)


def hbm_bytes(pass_: str, sz, seq: int, itemsize: int = 2) -> float:
    q = seq * sz.heads * sz.head_dim * itemsize
    kv = 2 * seq * sz.kv_heads * sz.head_dim * itemsize
    indexer = (seq * (sz.idx_heads + 1) * sz.idx_dim * itemsize
               + seq * sz.idx_heads * 4)
    target = selected_pairs(seq, sz.topk) * 4
    forward = q + kv + indexer + q + target
    if pass_ == "fwd":
        return forward
    if pass_ == "bwd":
        return forward + q + (q + kv + indexer)
    raise ValueError(pass_)


def least_seconds(sz, seq: int, peaks: dict) -> float:
    """The least time the chip could take for one layer's mechanism over one
    sequence, forward and backward, each pass bound by the larger of its
    operations over the bf16 peak and its bytes over the HBM peak (at 16,384
    tokens both are bound by compute)."""
    return sum(
        max(flops(p, sz, seq) / peaks["bf16_flops_per_s"],
            hbm_bytes(p, sz, seq) / peaks["hbm_bytes_per_s"])
        for p in PASSES)
