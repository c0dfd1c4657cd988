"""What the chunked gated delta rule (`ops/gated_delta.py`, scope
`L_gdn_core`) needs by the algorithm at a chunk size C, for one layer and
one sequence: operations and bytes, forward and backward.

Operations (2 per multiply-add), a value head and a chunk, forward:
  * k.k and q.k below the diagonal, C^2/2 * dk each, made once a KEY head
    and shared by the Hv/Hk value heads on it;
  * the unit lower-triangular system (I + A) [U | W] = [beta V | beta e^G
    K] by forward substitution: C^2/2 * (dv + dk);
  * what meets the state: W S, Q S and K^t V_new, C * dk * dv each;
  * the chunk's own causal part, (q.k * decay) V_new: C^2/2 * dv.
The backward pass is counted as twice the forward (each product has two
transposes). Recomputation (remat of the layer, of the segments) and the
whole-matrix halving that the program inverts with are the program's cost,
not the algorithm's, and are not counted.

Bytes: each operand read once and each result written once, in the
program's dtypes: q, k (S, Hk, dk) and v, o (S, Hv, dv) at `itemsize`
bytes, g and beta (S, Hv) float32. Forward reads q, k, v, g, beta and
writes o; backward reads those and do, and writes dq, dk, dv, dg, dbeta.
"""

from __future__ import annotations


def _shapes(sz):
    return (sz.gdn_k_heads, sz.gdn_v_heads, sz.gdn_k_dim, sz.gdn_v_dim)


def forward_flops(sz, seq: int, chunk: int) -> float:
    hk, hv, dk, dv = _shapes(sz)
    half = chunk * chunk / 2.0
    per_key_head = 2 * half * dk
    per_value_head = (half * (dv + dk) + 3 * chunk * dk * dv + half * dv)
    chunks = -(-seq // chunk)
    return 2.0 * chunks * (hk * per_key_head + hv * per_value_head)


def flops(pass_: str, sz, seq: int, chunk: int) -> float:
    return {"fwd": 1.0, "bwd": 2.0}[pass_] * forward_flops(sz, seq, chunk)


def hbm_bytes(pass_: str, sz, seq: int, itemsize: int = 2) -> float:
    hk, hv, dk, dv = _shapes(sz)
    qk = 2 * seq * hk * dk * itemsize
    v = seq * hv * dv * itemsize
    gates = 2 * seq * hv * 4
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
