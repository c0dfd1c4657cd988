"""What the Mamba-2 state-space recurrence (`ops/ssd.py`, scope
`L_ssm_core`) needs by the chunked algorithm at a chunk size Q, for one
layer and one sequence: operations and bytes, forward and backward. The same
whatever implements the scope (XLA today, a kernel later): the algorithm's
products, not the program's.

Operations (2 per multiply-add), a chunk, forward:
  * C B^T on and below the diagonal, once a GROUP (its heads share B and
    C): Q^2/2 * N;
  * a head's masked, decayed scores times x: Q^2/2 * P;
  * what a head's chunk writes to the state, (x * decay)^T B, and what it
    reads from the state that entered, C S_0: Q * P * N each.
The decays, the running sums and the chunk-to-chunk state (P * N a head and
chunk) are elementwise and not counted. The backward pass is counted as
twice the forward (each product has two transposes). Recomputation (remat
of the layer and of the rule's segments) is the program's cost, not the
algorithm's, and is not counted.

Bytes: each operand read once and each result written once, in the
program's dtypes: x, y (S, H, P) and B, C (S, G, N) at `itemsize` bytes,
the step dt (S, H) float32. Forward reads x, B, C, dt and writes y; backward
reads those and dy, and writes dx, dB, dC, ddt.
"""

from __future__ import annotations


def forward_flops(sz, seq: int, chunk: int) -> float:
    h, p, g, n = sz.ssm_heads, sz.ssm_head_dim, sz.ssm_groups, sz.ssm_state
    half = chunk * chunk / 2.0
    per_chunk = g * half * n + h * (half * p + 2 * chunk * p * n)
    return 2.0 * -(-seq // chunk) * per_chunk


def flops(pass_: str, sz, seq: int, chunk: int) -> float:
    return {"fwd": 1.0, "bwd": 2.0}[pass_] * forward_flops(sz, seq, chunk)


def hbm_bytes(pass_: str, sz, seq: int, itemsize: int = 2) -> float:
    x = seq * sz.ssm_heads * sz.ssm_head_dim * itemsize
    bc = 2 * seq * sz.ssm_groups * sz.ssm_state * itemsize
    dt = seq * sz.ssm_heads * 4
    if pass_ == "fwd":
        return x + bc + dt + x
    if pass_ == "bwd":
        return (x + bc + dt + x) + (x + bc + dt)
    raise ValueError(pass_)


def least_seconds(sz, seq: int, chunk: int, peaks: dict) -> float:
    """The least time the chip could take for one layer's rule over one
    sequence, forward and backward, each pass bound by the larger of its
    operations over the bf16 peak and its bytes over the HBM peak."""
    return sum(
        max(flops(p, sz, seq, chunk) / peaks["bf16_flops_per_s"],
            hbm_bytes(p, sz, seq) / peaks["hbm_bytes_per_s"])
        for p in ("fwd", "bwd"))
