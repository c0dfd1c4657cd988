"""The delta rule with a decay per key channel (Kimi Delta Attention).

A head keeps a state S (d_k x d_v, zero at the sequence's start) and reads
the sequence token by token:

    S <- Diag(exp(g_t)) S;  r = v_t - S^T k_t;  S <- S + k_t (beta_t r)^T;
    o_t = S^T q_t

which is S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t
v_t^T with alpha_t = exp(g_t). q and k are normalised to unit length a head,
q scaled by d_k^-0.5; g <= 0 is a VECTOR over the d_k key channels (the
gated delta rule of `ops/gated_delta.py` has one number a head: a g that
repeats one number over the channels gives that rule back), beta in (0, 1)
one number a head. Every head has its own q, k and v.

`kda_rule` is the chunked form for the timed path: two Pallas kernels,
`kda_fwd` and `kda_bwd` (`kernels/gated_delta.py`, the gated rule's kernels
with the system of a decay a channel, chosen there by g's rank; Mosaic on a
TPU, interpreted on the CPU). With G the running sum of g inside a chunk of
C tokens the rule is the unit lower-triangular system (I + A) U = beta V -
beta (K * e^G) S_0,

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc),  j < i,

and o_i = (q_i * e^G_i) S_0 + sum_{j<=i} [sum_c q_ic k_jc exp(G_ic - G_jc)]
U_j, S_C = Diag(e^G_C) S_0 + (K * e^(G_C - G))^T U. The decay sits INSIDE
the contraction over c, so the pairs' sums are no product of k.k with a
decay matrix.

The overflow hazard and how it is avoided. (k * e^G)(k * e^-G)^T is the
same sum and overflows float32 once a channel has decayed by e^-88 inside
a chunk (A_log up to log 16 and a softplus of order 1 reach that within a
few tokens). Here no exponent is ever positive: a chunk is cut into
sub-blocks of SUB tokens; a pair in DIFFERENT sub-blocks is taken through
the reference point r = G at the first row of i's sub-block, (x_i * e^(G_i
- r)) . (k_j * e^(r - G_j)), where G_i <= r <= G_j since G only falls, one
product a sub-block of rows on the MXU; a pair INSIDE one sub-block is
summed as written, exp(G_ic - G_jc) for each of the SUB^2 / 2 pairs and
each channel (`_pairs_within`). A channel that forgets everything
underflows to zero and nothing else; nothing is divided by.

All of that is made in VMEM by the kernel, a grid step of a few chunks at
a time (G too: g comes as the log decays themselves), and the float32
state rides the grid; the backward kernel is the derivative of the same
program, but for the inverse (needs the inverse alone) and the pairs inside
a sub-block (start again from x, k, G: the (SUB, SUB, d_k) array of decays
is never kept). No scan and no rematerialised segment is left here: the
forward kernel writes the state that enters each grid step, and that is all
the backward needs. SEGMENT is the block of the model's per-token stages
around the rule (`models/kimi_linear.py`, `_by_blocks`).

`kda_rule_recurrent` is the rule as written at the top, a scan over single
tokens: the numerics reference of the tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from solvingpapers_tpu.kernels import gated_delta as kernel
from solvingpapers_tpu.ops.gated_delta import _l2norm

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
# tokens a chunk holds (one triangular system), tokens a sub-block of it
# holds (pairs inside it are summed channel by channel), tokens a
# rematerialised block of the model's per-token stages around the rule
# holds. Read when called, so a test can shrink them.
CHUNK = 64
SUB = 16
SEGMENT = 2048


def kda_rule(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    *,
    chunk: int | None = None,
    sub: int | None = None,
    decay=None,
) -> jax.Array:
    """q, k (B, S, H, dk); v (B, S, H, dv); g (B, S, H, dk) float32, the log
    of each key channel's decay; beta (B, S, H) float32. Returns o (B, S, H,
    dv) in v's dtype. With `decay`, g is instead whatever per-token array
    that function turns into the (B, S, H, dk) log decays: a layer whose
    decay comes from a low-rank input hands that over, and the float32
    array (268 MB at 16,384 tokens of 32 x 128, and as much again for its
    gradient) is made here, beside the kernels that read it, and dies with
    them. Products with the state take operands in v's dtype (bfloat16 on
    the chip) and add up in float32; the state, the decays and the
    triangular system are float32. Any S: the tail is padded with tokens
    that write nothing.

    `chunk` and `sub` default to the module's CHUNK and SUB (`sub` is cut
    to `chunk` where that is smaller)."""
    chunk = CHUNK if chunk is None else chunk
    sub = min(SUB if sub is None else sub, chunk)
    if decay is not None:
        g = decay(g)
    if g.shape != q.shape:
        raise ValueError(f"g {g.shape} must be a decay per key channel, "
                         f"shaped as q {q.shape}")
    if v.shape[:3] != q.shape[:3] or beta.shape != q.shape[:3]:
        raise ValueError(f"every head has its own q, k and v: q {q.shape}, "
                         f"v {v.shape}, beta {beta.shape}")
    if chunk < 1 or chunk & (chunk - 1) or sub < 1 or chunk % sub:
        raise ValueError(f"chunk {chunk} must be a power of two and a "
                         f"multiple of sub {sub}")
    return kernel.gated_delta_rule(q, k, v, g, beta, chunk=chunk, sub=sub)


def kda_rule_recurrent(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array
) -> jax.Array:
    """The rule token by token, float32 throughout; same arguments and
    result as `kda_rule` (the result float32)."""
    b, s, h, dk = q.shape
    dv = v.shape[3]
    q = _l2norm(q.astype(F32)) * dk ** -0.5
    k = _l2norm(k.astype(F32))

    def step(state, xs):  # state (B, H, dk, dv)
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None]
        r = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=HI)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * r,
                                   precision=HI)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=HI)

    xs = tuple(jnp.moveaxis(a.astype(F32), 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), F32), xs)
    return jnp.moveaxis(o, 0, 1)
