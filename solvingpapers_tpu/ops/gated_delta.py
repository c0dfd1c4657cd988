"""The gated delta rule (Gated DeltaNet's mixer) and its causal convolution.

A head keeps a state S (d_k x d_v, zero at the sequence's start) and reads
the sequence token by token:

    S <- exp(g_t) S;  r = v_t - S^T k_t;  S <- S + k_t (beta_t r)^T;
    o_t = S^T q_t

with q and k normalised to unit length a head, q scaled by d_k^-0.5, g <= 0
the log of the decay and beta in (0, 1) the writing strength, both a value
head's. Each key head serves `Hv // Hk` value heads.

`gated_delta_rule` is the chunked form for the timed path: inside a chunk
of C tokens the rule is a unit lower-triangular system (I + A) U = beta V -
diag(beta e^G) K S_0, with A_ij = beta_i e^(G_i - G_j) k_i.k_j below the
diagonal and G the running sum of g in the chunk. (I + A)^-1 is made for
many chunks at once by halving (the inverse of [[a, 0], [c, d]] is [[a^-1,
0], [-d^-1 c a^-1, d^-1]]), which is forward substitution in blocks: no
power of A is formed, so keys that repeat cost no precision. What depends
on the state is left to one `lax.scan` over the chunks carrying S in
float32: four small products a step. No decay is ever divided by, so a
head that forgets within a few tokens underflows to zero and nothing else.
Backward is autodiff of the same program (the scan's transpose is a scan
over the chunks in reverse), but for the inverse, whose backward needs
the inverse alone.

`gated_delta_rule_recurrent` is the rule as written above, a scan over
single tokens: the numerics reference of the tests, and fine for tiny
sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
# tokens a chunk holds (one triangular system), and tokens a rematerialised
# segment of the rule holds; the model's per-token stages around the rule
# run in blocks of SEGMENT too. Read when called, so a test can shrink them.
CHUNK = 64
SEGMENT = 2048


def _shifted_sum(x: jax.Array, w: jax.Array, back: bool) -> jax.Array:
    """sum_j w[j] * x[t - (K-1) + j], or with `back` its transpose in t,
    sum_j w[j] * x[t + (K-1) - j]: K slices of one padded array, one pass."""
    k, s = w.shape[0], x.shape[1]
    pad = (0, k - 1) if back else (k - 1, 0)
    xp = jnp.pad(x, ((0, 0), pad, (0, 0)))
    at = (lambda j: k - 1 - j) if back else (lambda j: j)
    out = xp[:, at(0):at(0) + s] * w[0]
    for j in range(1, k):
        out = out + xp[:, at(j):at(j) + s] * w[j]
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def causal_depthwise_conv(x: jax.Array, w: jax.Array, silu: bool = False):
    """y_t = sum_j w[j] * x[t - (K-1) + j] a channel, zeros before the
    start, then SiLU if `silu`. x (B, S, C), w (K, C); K shifted
    multiply-adds, no convolution op (K is 4: an elementwise pass the
    compiler fuses). The backward pass is written the same way and starts
    again from x, so that it too is one pass over the sequence, and neither
    K arrays of the sequence's size nor the convolution's output are kept."""
    y = _shifted_sum(x, w.astype(x.dtype), back=False)
    return jax.nn.silu(y) if silu else y


def _conv_fwd(x, w, silu):
    return causal_depthwise_conv(x, w, silu), (x, w)


def _conv_bwd(silu, res, dy):
    x, w = res
    k, s = w.shape[0], x.shape[1]
    if silu:
        y = _shifted_sum(x, w.astype(x.dtype), back=False).astype(jnp.float32)
        sig = jax.nn.sigmoid(y)
        dy = (dy.astype(jnp.float32) * sig * (1.0 + y * (1.0 - sig))).astype(
            dy.dtype)
    dx = _shifted_sum(dy, w.astype(dy.dtype), back=True)
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    dw = jnp.stack([
        jnp.sum(xp[:, j:j + s].astype(jnp.float32) * dy.astype(jnp.float32),
                axis=(0, 1))
        for j in range(k)
    ])
    return dx.astype(x.dtype), dw.astype(w.dtype)


causal_depthwise_conv.defvjp(_conv_fwd, _conv_bwd)


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


def _inverse_by_halving(m: jax.Array) -> jax.Array:
    n = m.shape[-1]
    rows = jnp.arange(n)
    inv = jnp.broadcast_to(jnp.eye(n, dtype=m.dtype), m.shape)
    s = 1
    while s < n:
        # the blocks under the diagonal of each pair of s x s blocks
        under = ((rows[:, None] // (2 * s) == rows[None, :] // (2 * s))
                 & ((rows[:, None] // s) % 2 == 1)
                 & ((rows[None, :] // s) % 2 == 0))
        c = jnp.where(under, m, 0.0)
        inv = inv - jnp.matmul(
            inv, jnp.matmul(c, inv, precision=HI), precision=HI)
        s *= 2
    return inv


@jax.custom_vjp
def _unit_lower_inverse(m: jax.Array) -> jax.Array:
    """Inverse of unit lower-triangular matrices (..., n, n), n a power of
    two, by halving: with the s x s blocks on the diagonal inverted (D,
    block-diagonal), the 2s x 2s blocks' inverses are D - D C D, C the
    blocks under the diagonal of each pair. log2(n) rounds of two products
    on whole n x n matrices (their zeros cost less than the layouts of
    small blocks would), float32 at the highest precision. Backward from
    the inverse alone: dM = -T^t dT T^t."""
    return _inverse_by_halving(m)


def _unit_lower_inverse_fwd(m):
    t = _inverse_by_halving(m)
    return t, t


def _unit_lower_inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-jnp.matmul(tt, jnp.matmul(dt, tt, precision=HI), precision=HI),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _segment(state, xs, *, chunk: int, dt):
    """One segment of whole chunks: state (B, Hk, G, dk, dv) float32 in and
    out; xs = q, k (B, L, Hk, dk), v (B, L, Hv, dv), g, beta (B, L, Hv).
    Returns (state, o (B, L, Hv, dv))."""
    q, k, v, g, beta = xs
    b, seg, hk, dk = q.shape
    grp, dv = v.shape[2] // hk, v.shape[3]
    n = seg // chunk
    f32 = jnp.float32
    q = _l2norm(q.astype(f32)) * dk ** -0.5
    k = _l2norm(k.astype(f32))
    # chunks first (the scan's axis), then batch, key head, group
    qc = q.reshape(b, n, chunk, hk, dk).transpose(1, 0, 3, 2, 4)
    kc = k.reshape(b, n, chunk, hk, dk).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, n, chunk, hk, grp, dv).transpose(1, 0, 3, 4, 2, 5)
    gc = g.reshape(b, n, chunk, hk, grp).transpose(1, 0, 3, 4, 2)
    bc = beta.reshape(b, n, chunk, hk, grp).transpose(1, 0, 3, 4, 2)
    # qc, kc (N, B, Hk, C, dk); vc (N, B, Hk, G, C, dv); gc, bc (N, B, Hk, G, C)

    gcum = jnp.cumsum(gc, -1)
    rows = jnp.arange(chunk)
    on_or_below = rows[:, None] >= rows[None, :]
    diff = gcum[..., :, None] - gcum[..., None, :]
    decay = jnp.where(on_or_below,
                      jnp.exp(jnp.where(on_or_below, diff, 0.0)), 0.0)
    kk = jnp.einsum("nbhcd,nbhmd->nbhcm", kc, kc, precision=HI)
    qk = jnp.einsum("nbhcd,nbhmd->nbhcm", qc, kc, precision=HI)
    strictly = rows[:, None] > rows[None, :]
    a = jnp.where(strictly, bc[..., :, None] * kk[:, :, :, None] * decay, 0.0)
    t = _unit_lower_inverse(a + jnp.eye(chunk, dtype=f32))
    attn = (qk[:, :, :, None] * decay).astype(dt)  # diagonal included
    k_beta = kc[:, :, :, None] * bc[..., None]  # (N, B, Hk, G, C, dk)
    u = jnp.matmul(t, vc.astype(f32) * bc[..., None], precision=HI)
    w = jnp.matmul(t, k_beta * jnp.exp(gcum)[..., None], precision=HI)
    # what a chunk hands the state: k_j e^(G_last - G_j)
    k_tail = (kc[:, :, :, None]
              * jnp.exp(gcum[..., -1:] - gcum)[..., None]).astype(dt)
    q_in = (qc[:, :, :, None] * jnp.exp(gcum)[..., None]).astype(dt)
    last = jnp.exp(gcum[..., -1])  # (N, B, Hk, G)

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=f32)

    def step(state, xs):
        u_n, w_n, q_n, k_n, attn_n, last_n = xs
        s_dt = state.astype(dt)
        v_new = u_n - mm(w_n, s_dt)  # (B, Hk, G, C, dv) float32
        v_dt = v_new.astype(dt)
        o_n = mm(q_n, s_dt) + mm(attn_n, v_dt)
        state = state * last_n[..., None, None] + mm(
            jnp.swapaxes(k_n, -1, -2), v_dt)
        return state, o_n.astype(dt)

    state, o = jax.lax.scan(step, state,
                            (u, w.astype(dt), q_in, k_tail, attn, last))
    # (N, B, Hk, G, C, dv) -> (B, L, Hv, dv)
    return state, o.transpose(1, 0, 4, 2, 3, 5).reshape(b, seg, hk * grp, dv)


def gated_delta_rule(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    *,
    chunk: int | None = None,
    segment: int | None = None,
) -> jax.Array:
    """q, k (B, S, Hk, dk); v (B, S, Hv, dv); g, beta (B, S, Hv) float32.
    Returns o (B, S, Hv, dv) in v's dtype. Products take operands in v's
    dtype (bfloat16 on the chip) and add up in float32; the state, the
    decays and the triangular system are float32. Any S: the tail of the
    last chunk is padded with tokens that write nothing.

    `chunk` and `segment` default to the module's CHUNK and SEGMENT.
    A sequence longer than `segment` tokens runs as a scan over segments
    of whole chunks, each rematerialised in the backward pass, the state
    carried between them: what one chunked pass keeps for its backward
    (the chunks' triangular systems, a state a chunk) is then a segment's
    and not the sequence's."""
    chunk = CHUNK if chunk is None else chunk
    segment = SEGMENT if segment is None else segment
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    grp = hv // hk
    if hk * grp != hv:
        raise ValueError(f"{hv} value heads over {hk} key heads")
    if chunk & (chunk - 1) or segment % chunk:
        raise ValueError(f"chunk {chunk} must be a power of two that "
                         f"divides segment {segment}")
    dt = v.dtype
    f32 = jnp.float32
    g, beta = g.astype(f32), beta.astype(f32)
    seg = segment if s > segment else -(-s // chunk) * chunk
    pad = (-s) % seg
    if pad:
        widen = lambda a: jnp.pad(  # noqa: E731
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    n_seg = (s + pad) // seg
    state0 = jnp.zeros((b, hk, grp, dk, dv), f32)
    body = functools.partial(_segment, chunk=chunk, dt=dt)
    if n_seg == 1:
        _, o = body(state0, (q, k, v, g, beta))
    else:
        xs = tuple(
            jnp.moveaxis(a.reshape((b, n_seg, seg) + a.shape[2:]), 1, 0)
            for a in (q, k, v, g, beta))
        _, o = jax.lax.scan(jax.checkpoint(body), state0, xs)
        o = jnp.moveaxis(o, 0, 1).reshape(b, n_seg * seg, hv, dv)
    return o[:, :s]


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
