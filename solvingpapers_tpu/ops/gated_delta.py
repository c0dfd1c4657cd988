"""The gated delta rule (Gated DeltaNet's mixer) and its gated norm.

A head keeps a state S (d_k x d_v, zero at the sequence's start) and reads
the sequence token by token:

    S <- exp(g_t) S;  r = v_t - S^T k_t;  S <- S + k_t (beta_t r)^T;
    o_t = S^T q_t

with q and k normalised to unit length a head, q scaled by d_k^-0.5, g <= 0
the log of the decay and beta in (0, 1) the writing strength, both a value
head's. Each key head serves `Hv // Hk` value heads.

Decay shapes. Both entries here take g of shape (B, S, Hv): ONE decay a
value head and token, the same for every key channel (Gated DeltaNet). A
decay per key channel, g (B, S, H, d_k) (Kimi Delta Attention), is
`ops/kda.py`'s rule: there the decay sits inside the contraction over the
channels and a chunk's system is no longer (K K^t) times a decay matrix, so
the kernels make that system another way (sub-blocked pair sums, chosen
there by g's rank) and share the rest.
A g that repeats one number over the channels gives this rule back
(tests/test_kda.py). The causal convolution in front of both rules is
`ops/conv.py`'s.

`gated_delta_rule` is the chunked form for the timed path: inside a chunk
of C tokens the rule is a unit lower-triangular system (I + A) U = beta V -
diag(beta e^G) K S_0, with A_ij = beta_i e^(G_i - G_j) k_i.k_j below the
diagonal and G the running sum of g in the chunk. It runs as two Pallas
kernels (`kernels/gated_delta.py`), on a TPU compiled by Mosaic and on the
CPU interpreted: the forward streams over the sequence once, makes and
inverts each chunk's system in VMEM ((I + A)^-1 by halving, which is
forward substitution in blocks: no power of A is formed, so keys that
repeat cost no precision) and carries S in float32 in VMEM from chunk to
chunk; the backward streams once in reverse with dS as the carry, makes
each chunk's system again and starts from the float32 state that entered
its grid step, which the forward wrote. Nothing of a C x C system reaches
HBM. No decay is ever divided by, so a head that forgets
within a few tokens underflows to zero and nothing else.

`gated_delta_rule_recurrent` is the rule as written above, a scan over
single tokens: the numerics reference of the tests, and fine for tiny
sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from solvingpapers_tpu.kernels import gated_delta as kernel

HI = jax.lax.Precision.HIGHEST
# tokens a chunk holds (one triangular system), and tokens a rematerialised
# block of the model's per-token stages around the rule holds. Read when
# called, so a test can shrink them.
CHUNK = 64
SEGMENT = 2048


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gated_rms_norm(o: jax.Array, z: jax.Array, w: jax.Array, eps: float):
    """o * rsqrt(mean(o^2) + eps) * w * SiLU(z) over the last axis, computed
    in float32 and returned in o's dtype. The backward pass starts again
    from o and z as they came (bfloat16 on the chip), so no float32 copy of
    a (tokens, heads, width) array outlives its pass."""
    return _gated_rms_norm(o, z, w, eps).astype(o.dtype)


def _gated_rms_norm(o, z, w, eps):
    o32, z32 = o.astype(jnp.float32), z.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(o32 * o32, -1, keepdims=True) + eps)
    return o32 * rms * w.astype(jnp.float32) * jax.nn.silu(z32)


def _gated_rms_norm_fwd(o, z, w, eps):
    return gated_rms_norm(o, z, w, eps), (o, z, w)


def _gated_rms_norm_bwd(eps, res, dy):
    o, z, w = res
    _, vjp = jax.vjp(lambda o, z, w: _gated_rms_norm(o, z, w, eps), o, z, w)
    return vjp(dy.astype(jnp.float32))


gated_rms_norm.defvjp(_gated_rms_norm_fwd, _gated_rms_norm_bwd)


def _l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def gated_delta_rule(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    *,
    chunk: int | None = None,
) -> jax.Array:
    """q, k (B, S, Hk, dk); v (B, S, Hv, dv); g, beta (B, S, Hv) float32
    (one decay a value head: a decay per key channel goes to
    `ops.kda.kda_rule`). Returns o (B, S, Hv, dv) in v's dtype. Products take operands in v's
    dtype (bfloat16 on the chip) and add up in float32; the state, the
    decays and the triangular system are float32. Any S: the tail is
    padded with tokens that write nothing. `chunk` defaults to the
    module's CHUNK."""
    chunk = CHUNK if chunk is None else chunk
    hk, hv = q.shape[2], v.shape[2]
    if hv % hk:
        raise ValueError(f"{hv} value heads over {hk} key heads")
    if g.shape != v.shape[:3]:
        raise ValueError(
            f"g {g.shape} must be one decay a value head, {v.shape[:3]}; a "
            "decay per key channel is ops.kda.kda_rule's")
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} must be a power of two")
    return kernel.gated_delta_rule(q, k, v, g, beta, chunk=chunk)


def gated_delta_rule_recurrent(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array
) -> jax.Array:
    """The rule token by token, float32 throughout; same arguments and
    result as `gated_delta_rule` (the result float32)."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    grp = hv // hk
    f32 = jnp.float32
    q = jnp.repeat(_l2norm(q.astype(f32)) * dk ** -0.5, grp, axis=2)
    k = jnp.repeat(_l2norm(k.astype(f32)), grp, axis=2)

    def step(state, xs):  # state (B, Hv, dk, dv)
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        r = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=HI)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * r,
                                   precision=HI)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=HI)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), f32), xs)
    return jnp.moveaxis(o, 0, 1)
