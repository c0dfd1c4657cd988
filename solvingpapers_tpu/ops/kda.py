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

`kda_rule` is the chunked form for the timed path, plain XLA (no kernel
yet: `kernels/gated_delta.py` takes a decay a head only). With G the
running sum of g inside a chunk of C tokens the rule is the unit
lower-triangular system (I + A) U = beta V - beta (K * e^G) S_0,

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

Backward is autodiff of the same program, but for the inverse (needs the
inverse alone) and the pairs inside a sub-block (start again from x, k, G:
the (SUB, SUB, d_k) array of decays is never kept). Sequences longer than
SEGMENT tokens run as a scan over rematerialised segments carrying S.

`kda_rule_recurrent` is the rule as written at the top, a scan over single
tokens: the numerics reference of the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from solvingpapers_tpu.ops.gated_delta import _l2norm

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
# tokens a chunk holds (one triangular system), tokens a sub-block of it
# holds (pairs inside it are summed channel by channel), tokens a
# rematerialised segment of the rule holds. Read when called, so a test can
# shrink them.
CHUNK = 64
SUB = 16
SEGMENT = 2048


def _inverse_by_halving(a: jax.Array) -> jax.Array:
    n = a.shape[-1]
    rows = jnp.arange(n)
    inv = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), a.shape)
    s = 1
    while s < n:
        # the blocks under the diagonal of each pair of s x s blocks
        under = ((rows[:, None] // (2 * s) == rows[None, :] // (2 * s))
                 & ((rows[:, None] // s) % 2 == 1)
                 & ((rows[None, :] // s) % 2 == 0))
        low = jnp.where(under, a, 0.0)
        inv = inv - jnp.matmul(
            inv, jnp.matmul(low, inv, precision=HI), precision=HI)
        s *= 2
    return inv


@jax.custom_vjp
def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """(I + A)^-1 for strictly lower-triangular A (..., n, n), n a power of
    two, by halving: with the s x s blocks on the diagonal inverted (D), the
    2s x 2s blocks' inverses are D - D L D, L the blocks under the diagonal
    of each pair. Forward substitution in blocks: no power of A is formed.
    float32 at the highest precision. Backward from the inverse alone:
    dA = -T^t dT T^t."""
    return _inverse_by_halving(a)


def _unit_lower_inverse_fwd(a):
    t = _inverse_by_halving(a)
    return t, t


def _unit_lower_inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-jnp.matmul(tt, jnp.matmul(dt, tt, precision=HI), precision=HI),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _decays_within(g: jax.Array, strictly: bool = False) -> jax.Array:
    """exp(G_rc - G_sc) for s <= r (s < r with `strictly`), else 0: (...,
    SUB, SUB, dk) of g (..., SUB, dk). The exponent is taken only where it
    is <= 0."""
    n = g.shape[-2]
    rows = jnp.arange(n)
    below = rows[:, None] > rows[None, :] if strictly else \
        rows[:, None] >= rows[None, :]
    below = below[..., None]
    diff = g[..., :, None, :] - g[..., None, :, :]
    return jnp.where(below, jnp.exp(jnp.where(below, diff, 0.0)), 0.0)


@jax.custom_vjp
def _pairs_within(x: jax.Array, k: jax.Array, g: jax.Array) -> jax.Array:
    """P_rs = sum_c x_rc k_sc exp(G_rc - G_sc) for s <= r inside a
    sub-block, 0 above the diagonal: x, k, g (..., SUB, dk) float32 ->
    (..., SUB, SUB). One fused pass; the backward makes the decays again.
    dG = x * dx - k * dk, since both derivatives carry the same terms, but
    for the diagonal's (decay 1, no G in it), which are added to dx and dk
    afterwards so that they do not have to cancel in rounding."""
    e = _decays_within(g)
    return jnp.sum(x[..., :, None, :] * k[..., None, :, :] * e, axis=-1)


def _pairs_within_fwd(x, k, g):
    return _pairs_within(x, k, g), (x, k, g)


def _pairs_within_bwd(res, dp):
    x, k, g = res
    w = dp[..., None] * _decays_within(g, strictly=True)  # (..., r, s, c)
    dx = jnp.sum(w * k[..., None, :, :], axis=-2)
    dk = jnp.sum(w * x[..., :, None, :], axis=-3)
    on_diag = jnp.diagonal(dp, axis1=-2, axis2=-1)[..., None]
    return dx + on_diag * k, dk + on_diag * x, x * dx - k * dk


_pairs_within.defvjp(_pairs_within_fwd, _pairs_within_bwd)


def _pair_sums(xs, k, g, sub: int):
    """For each x of `xs` (..., C, dk) the (..., C, C) matrix P_ij = sum_c
    x_ic k_jc exp(G_ic - G_jc) for j <= i, 0 above the diagonal; k, g (...,
    C, dk), g the running sum of the log decays in the chunk. No exponent is
    positive (module docstring)."""
    c, dk = k.shape[-2:]
    nb = c // sub
    lead = k.shape[:-2]
    blocks = lambda a: a.reshape(lead + (nb, sub, dk))  # noqa: E731
    gb, kb = blocks(g), blocks(k)
    ref = gb[..., :1, :]  # G at each sub-block's first row
    # <= 1, since G only falls; the first row's is 1 and is written so, or
    # its two equal and opposite gradients would have to cancel in rounding
    first = (jnp.arange(sub) == 0)[:, None]
    row_decay = jnp.where(first, 1.0, jnp.exp(gb - ref))
    # keys as the rows of sub-block I see them, k_j e^(r_I - G_j); the
    # columns at or after I's first row are masked below, their exponent
    # held at 0 meanwhile
    k_seen = k[..., None, :, :] * jnp.exp(jnp.minimum(
        ref - g[..., None, :, :], 0.0))  # (..., nb, C, dk)
    rows = jnp.arange(c)
    earlier = (rows[None, :] // sub) < (rows[:, None] // sub)  # (C, C)
    same = jnp.eye(nb, dtype=bool)[:, None, :, None]  # (nb, 1, nb, 1)
    out = []
    for x in xs:
        xb = blocks(x)
        far = jnp.einsum("...ird,...ijd->...irj", xb * row_decay, k_seen,
                         precision=HI).reshape(lead + (c, c))
        near = _pairs_within(xb, kb, gb)  # (..., nb, sub, sub)
        near = jnp.where(same, near[..., :, :, None, :], 0.0).reshape(
            lead + (c, c))
        out.append(jnp.where(earlier, far, 0.0) + near)
    return out


def _segment(state, xs, *, chunk: int, sub: int, dt, decay=None):
    """One segment of whole chunks: state (B, H, dk, dv) float32 in and
    out; xs = q, k (B, L, H, dk), v (B, L, H, dv), g (B, L, H, dk) (or what
    `decay` makes it from), beta (B, L, H). Returns (state, o (B, L, H,
    dv))."""
    q, k, v, g, beta = xs
    if decay is not None:
        g = decay(g).astype(F32)
    b, seg, h, dk = q.shape
    dv = v.shape[3]
    n = seg // chunk
    q = _l2norm(q.astype(F32)) * dk ** -0.5
    k = _l2norm(k.astype(F32))
    # chunks first (the scan's axis), then batch, head
    by_chunk = lambda a: a.reshape(  # noqa: E731
        (b, n, chunk, h) + a.shape[3:]).transpose(
            (1, 0, 3, 2) + tuple(range(4, a.ndim + 1)))
    qc, kc, vc, gc = by_chunk(q), by_chunk(k), by_chunk(v), by_chunk(g)
    bc = by_chunk(beta)[..., None]  # (N, B, H, C, 1)
    gcum = jnp.cumsum(gc, axis=-2)  # (N, B, H, C, dk)
    kk, qk = _pair_sums((kc, qc), kc, gcum, sub)
    rows = jnp.arange(chunk)
    a = jnp.where(rows[:, None] > rows[None, :], bc * kk, 0.0)
    t = _unit_lower_inverse(a)
    attn = qk.astype(dt)  # diagonal included
    e_g = jnp.exp(gcum)
    u = jnp.matmul(t, vc.astype(F32) * bc, precision=HI)
    w = jnp.matmul(t, kc * (bc * e_g), precision=HI)
    g_last = gcum[..., -1:, :]
    # what a chunk hands the state: k_j e^(G_last - G_j)
    k_tail = (kc * jnp.exp(g_last - gcum)).astype(dt)
    q_in = (qc * e_g).astype(dt)
    last = jnp.exp(g_last[..., 0, :])  # (N, B, H, dk)

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=F32)

    def step(state, xs):
        u_n, w_n, q_n, k_n, attn_n, last_n = xs
        s_dt = state.astype(dt)
        v_new = u_n - mm(w_n, s_dt)  # (B, H, C, dv) float32
        v_dt = v_new.astype(dt)
        o_n = mm(q_n, s_dt) + mm(attn_n, v_dt)
        state = state * last_n[..., None] + mm(
            jnp.swapaxes(k_n, -1, -2), v_dt)
        return state, o_n.astype(dt)

    state, o = jax.lax.scan(step, state,
                            (u, w.astype(dt), q_in, k_tail, attn, last))
    # (N, B, H, C, dv) -> (B, L, H, dv)
    return state, o.transpose(1, 0, 3, 2, 4).reshape(b, seg, h, dv)


def kda_rule(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    *,
    chunk: int | None = None,
    sub: int | None = None,
    segment: int | None = None,
    decay=None,
) -> jax.Array:
    """q, k (B, S, H, dk); v (B, S, H, dv); g (B, S, H, dk) float32, the log
    of each key channel's decay; beta (B, S, H) float32. Returns o (B, S, H,
    dv) in v's dtype. With `decay`, g is instead whatever per-token array
    (B, S, ...) that function turns into the (B, L, H, dk) log decays, a
    segment at a time inside the segment's rematerialised body: a layer
    whose decay comes from a low-rank input hands that over, and the (S,
    H, dk) float32 array never exists whole (at 16,384 tokens of 32 x 128
    it is 268 MB, and as much again for its gradient). Products with the state take operands in v's dtype
    (bfloat16 on the chip) and add up in float32; the state, the decays
    and the triangular system are float32. Any S: the tail is padded with
    tokens that write nothing.

    `chunk`, `sub` and `segment` default to the module's CHUNK, SUB and
    SEGMENT (`sub` is cut to `chunk` where that is smaller)."""
    chunk = CHUNK if chunk is None else chunk
    sub = min(SUB if sub is None else sub, chunk)
    segment = SEGMENT if segment is None else segment
    b, s, h, dk = q.shape
    dv = v.shape[3]
    if decay is None and g.shape != q.shape:
        raise ValueError(f"g {g.shape} must be a decay per key channel, "
                         f"shaped as q {q.shape}")
    if chunk & (chunk - 1) or chunk % sub or segment % chunk:
        raise ValueError(f"chunk {chunk} must be a power of two, a multiple "
                         f"of sub {sub}, and divide segment {segment}")
    dt = v.dtype
    beta = beta.astype(F32)
    if decay is None:
        g = g.astype(F32)
    seg = segment if s > segment else -(-s // chunk) * chunk
    pad = (-s) % seg
    if pad:
        widen = lambda a: jnp.pad(  # noqa: E731
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    n_seg = (s + pad) // seg
    state0 = jnp.zeros((b, h, dk, dv), F32)
    body = functools.partial(_segment, chunk=chunk, sub=sub, dt=dt,
                             decay=decay)
    if n_seg == 1:
        _, o = body(state0, (q, k, v, g, beta))
    else:
        xs = tuple(
            jnp.moveaxis(a.reshape((b, n_seg, seg) + a.shape[2:]), 1, 0)
            for a in (q, k, v, g, beta))
        _, o = jax.lax.scan(jax.checkpoint(body), state0, xs)
        o = jnp.moveaxis(o, 0, 1).reshape(b, n_seg * seg, h, dv)
    return o[:, :s]


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
