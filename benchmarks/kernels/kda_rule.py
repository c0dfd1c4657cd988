"""What the delta rule with a decay per key channel (Kimi Delta Attention;
`ops/kda.py`, scope `L_kda_core`) needs by the chunked algorithm at a chunk
size C, for one layer and one sequence: operations and bytes, forward and
backward. The same whatever implements the scope (XLA today, a kernel
later): the algorithm's products, not the program's.

Operations (2 per multiply-add), a head and a chunk, forward (every head
has its own q, k and v):
  * the pairs' sums with the decay inside the contraction, sum_c k_ic k_jc
    e^(G_ic - G_jc) and the same with q_i, on and below the diagonal:
    C^2/2 * dk each (the decays themselves are elementwise and not counted);
  * the unit lower-triangular system (I + A) [U | W] = [beta V | beta (K *
    e^G)] by forward substitution: C^2/2 * (dv + dk);
  * what meets the state: W S, (Q * e^G) S and (K * e^(G_C - G))^t U,
    C * dk * dv each;
  * the chunk's own causal part, the q-pairs times U: C^2/2 * dv.
The backward pass is counted as twice the forward (each product has two
transposes). Recomputation (remat of the layer, of the segments), the
whole-matrix halving the program inverts with, the sub-blocks' masked
products and making the decay from its low-rank input are the program's
cost, not the algorithm's, and are not counted.

Bytes: each operand read once and each result written once, in the
program's dtypes: q, k (S, H, dk) and v, o (S, H, dv) at `itemsize` bytes,
the log decay g (S, H, dk) and beta (S, H) float32. Forward reads q, k, v,
g, beta and writes o; backward reads those and do, and writes dq, dk, dv,
dg, dbeta.
"""

from __future__ import annotations


def _shapes(sz):
    return sz.kda_heads, sz.kda_dim, sz.kda_dim


def forward_flops(sz, seq: int, chunk: int) -> float:
    h, dk, dv = _shapes(sz)
    half = chunk * chunk / 2.0
    per_head = (2 * half * dk + half * (dv + dk) + 3 * chunk * dk * dv
                + half * dv)
    return 2.0 * -(-seq // chunk) * h * per_head


def flops(pass_: str, sz, seq: int, chunk: int) -> float:
    return {"fwd": 1.0, "bwd": 2.0}[pass_] * forward_flops(sz, seq, chunk)


def hbm_bytes(pass_: str, sz, seq: int, itemsize: int = 2) -> float:
    h, dk, dv = _shapes(sz)
    qk = 2 * seq * h * dk * itemsize
    v = seq * h * dv * itemsize
    gates = seq * h * dk * 4 + seq * h * 4
    if pass_ == "fwd":
        return qk + v + gates + v
    if pass_ == "bwd":
        return (qk + v + gates + v) + (qk + v + gates)
    raise ValueError(pass_)


def least_seconds(sz, seq: int, chunk: int, peaks: dict) -> float:
    """The least time the chip could take for one layer's rule over one
    sequence, forward and backward, each pass bound by the larger of its
    operations over the bf16 peak and its bytes over the HBM peak."""
    return sum(
        max(flops(p, sz, seq, chunk) / peaks["bf16_flops_per_s"],
            hbm_bytes(p, sz, seq) / peaks["hbm_bytes_per_s"])
        for p in ("fwd", "bwd"))
