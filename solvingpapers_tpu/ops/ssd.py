"""The Mamba-2 state-space recurrence (the "state-space dual", SSD) and the
gated norm that follows it.

A head keeps a state S (P x N, zero at the sequence's start) and reads the
sequence token by token:

    S <- exp(dt_t a) S + dt_t x_t B_t^T;   y_t = S C_t + d x_t

with x_t (P,) the head's input, dt_t > 0 its step (a softplus upstream), a
< 0 ONE number a head (so the decay is a scalar a head and token: no
triangular system, unlike the delta rules of `ops/gated_delta.py` and
`ops/kda.py`, whose state is corrected by what it already holds), B_t and
C_t (N,) shared by the H // G heads of a group, d the head's skip weight.

`ssd_chunked` is the chunked form for the timed path, plain `jax.numpy`.
With l = dt a the log decay a token and L its running sum inside a chunk of
Q tokens (inclusive), a chunk is

    y_t = sum_{s<=t} exp(L_t - L_s) dt_s (C_t . B_s) x_s     [inside it]
        + exp(L_t) S_0 C_t                                   [what entered]
    S_Q = exp(L_Q) S_0 + sum_s exp(L_Q - L_s) dt_s x_s B_s^T

Every decay is the exponential of a DIFFERENCE of running sums taken where
it is <= 0 (s <= t; the masked half is set to -inf before the exponential,
never after), so nothing overflows and nothing is divided by: a head that
forgets within a token underflows to zero and nothing else. C B^T is made
once a group and shared by its heads. The chunks of a SEGMENT of tokens run
side by side, their states chained by one small product over the chunks'
total decays (again differences <= 0); a `lax.scan` carries the float32
state from segment to segment, and each segment is rematerialised in the
backward pass, so the (heads, Q, Q) float32 decay matrices live a segment at
a time. Products take their operands in x's dtype (bfloat16 on the chip)
and add up in float32; decays, running sums and the state are float32.

`ssd_step` is the rule for one token as written at the top (what a decode
step would run), `ssd_recurrent` a scan of it over the sequence: the
numerics reference of the tests, float32 throughout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
# tokens a chunk holds (the published `chunk_size`), and tokens a
# rematerialised segment of chunks holds: also the block of the model's
# per-token stages around the rule. Read when called, so a test can shrink
# them.
CHUNK = 128
SEGMENT = 2048


def ssd_step(state, x, dt, a, b, c, d=None):
    """One token. state (B, H, P, N) float32; x (B, H, P); dt (B, H); a (H,);
    b, c (B, G, N); d (H,) or None. Returns (state, y (B, H, P)), float32."""
    h, g = x.shape[1], b.shape[1]
    x, dt, a = x.astype(F32), dt.astype(F32), a.astype(F32)
    b = jnp.repeat(b.astype(F32), h // g, axis=1)
    c = jnp.repeat(c.astype(F32), h // g, axis=1)
    state = (state * jnp.exp(dt * a)[..., None, None]
             + (dt[..., None] * x)[..., None] * b[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", state, c, precision=HI)
    if d is not None:
        y = y + d.astype(F32)[:, None] * x
    return state, y


def _zero_state(x, b):
    return jnp.zeros((x.shape[0], x.shape[2], x.shape[3], b.shape[3]), F32)


def ssd_recurrent(x, dt, a, b, c, d=None, state=None):
    """The rule token by token; same arguments as `ssd_chunked`. Returns
    (y (B, S, H, P), the state after the last token), float32."""
    state = _zero_state(x, b) if state is None else state.astype(F32)
    state, y = jax.lax.scan(
        lambda s, t: ssd_step(s, t[0], t[1], a, t[2], t[3], d), state,
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), state


def _masked_exp(diff, keep):
    """exp(diff) where `keep`, zero elsewhere; the masked entries (whose
    difference may be large and positive) never reach the exponential."""
    return jnp.exp(jnp.where(keep, diff, -jnp.inf))


def _segment(state, xs, a, d, chunk):
    """`chunk`-token chunks of one segment side by side. state (B, H, P, N)
    float32; xs = x (B, L, H, P), dt (B, L, H) float32, b, c (B, L, G, N).
    Returns (state after the segment, y (B, L, H, P) in x's dtype)."""
    x, dt, b, c = xs
    bsz, seg, h, p = x.shape
    g, n = b.shape[2:]
    r, nc, cdt = h // g, seg // chunk, x.dtype
    # the sequence along the lanes: (B, H, chunks, Q)
    dt_h = jnp.moveaxis(dt, 1, 2).reshape(bsz, h, nc, chunk)
    cum = jnp.cumsum(dt_h * a.astype(F32)[:, None, None], -1)
    total = cum[..., -1]  # a chunk's whole log decay, (B, H, chunks)
    xc = x.reshape(bsz, nc, chunk, g, r, p)
    bc = b.reshape(bsz, nc, chunk, g, n)
    cc = c.reshape(bsz, nc, chunk, g, n)
    # (B, H, chunks, Q) -> (B, chunks, Q, G, R, 1), beside xc
    beside_x = lambda v: jnp.moveaxis(  # noqa: E731
        v.reshape(bsz, g, r, nc, chunk), (3, 4), (1, 2))[..., None]

    # inside a chunk: C B^T once a group, the decays a head
    cb = jnp.einsum("bcqgn,bckgn->bgcqk", cc, bc,
                    preferred_element_type=F32)
    decay = _masked_exp(cum[..., :, None] - cum[..., None, :],
                        jnp.tril(jnp.ones((chunk, chunk), bool)))
    m = (decay * dt_h[..., None, :]).reshape(
        bsz, g, r, nc, chunk, chunk) * cb[:, :, None]
    y = jnp.einsum("bgrcqk,bckgrp->bcqgrp", m.astype(cdt), xc,
                   preferred_element_type=F32)

    # what each chunk writes, decayed to its own end
    w = jnp.exp(total[..., None] - cum) * dt_h
    wrote = jnp.einsum(
        "bcqgrp,bcqgn->bcgrpn", (xc.astype(F32) * beside_x(w)).astype(cdt),
        bc, preferred_element_type=F32).reshape(bsz, nc, h, p, n)
    # the state entering chunk c, and after the last: z the running sum of
    # the chunks' totals with a leading zero, row c of `carry` takes chunk
    # c' < c from its end (z[c' + 1]) to c's start (z[c])
    z = jnp.concatenate(
        [jnp.zeros_like(total[..., :1]), jnp.cumsum(total, -1)], -1)
    carry = _masked_exp(
        z[..., :, None] - z[..., None, 1:],
        jnp.arange(nc)[None, :] < jnp.arange(nc + 1)[:, None])
    states = (jnp.einsum("bhcz,bzhpn->bchpn", carry, wrote, precision=HI)
              + jnp.moveaxis(jnp.exp(z), 2, 1)[..., None, None]
              * state[:, None])
    entered = states[:, :nc].reshape(bsz, nc, g, r, p, n)
    y = y + beside_x(jnp.exp(cum)) * jnp.einsum(
        "bcqgn,bcgrpn->bcqgrp", cc, entered.astype(cdt),
        preferred_element_type=F32)
    if d is not None:
        y = y + d.astype(F32).reshape(g, r, 1) * xc.astype(F32)
    return states[:, nc], y.reshape(bsz, seg, h, p).astype(cdt)


def ssd_chunked(x, dt, a, b, c, d=None, *, state=None, chunk=None,
                segment=None):
    """x (B, S, H, P); dt (B, S, H) float32, > 0; a (H,) < 0; b, c (B, S, G,
    N) with G dividing H (head h reads group h // (H / G)); d (H,) or None;
    `state` (B, H, P, N) what an earlier call left (None: zeros). Returns
    (y (B, S, H, P) in x's dtype, the float32 state after the last token).
    Any S: the tail is padded with tokens of step zero, which neither decay
    nor write. `chunk` and `segment` default to the module's CHUNK and
    SEGMENT; a segment is a whole number of chunks."""
    chunk = CHUNK if chunk is None else chunk
    segment = SEGMENT if segment is None else segment
    bsz, s, h, _ = x.shape
    if h % b.shape[2] or b.shape != c.shape:
        raise ValueError(f"{h} heads over b {b.shape}, c {c.shape}")
    if dt.shape != (bsz, s, h) or a.shape != (h,):
        raise ValueError(f"dt {dt.shape} and a {a.shape} must be one step a "
                         f"head and token and one decay a head of {x.shape}")
    if segment % chunk:
        raise ValueError(f"segment {segment} is no whole number of chunks "
                         f"of {chunk}")
    whole = -(-s // chunk) * chunk
    seg = min(segment, whole)
    padded = -(-whole // seg) * seg
    pad = lambda v: jnp.pad(  # noqa: E731
        v, ((0, 0), (0, padded - s)) + ((0, 0),) * (v.ndim - 2))
    split = lambda v: jnp.moveaxis(  # noqa: E731
        pad(v).reshape((bsz, padded // seg, seg) + v.shape[2:]), 1, 0)
    state = _zero_state(x, b) if state is None else state.astype(F32)
    body = jax.checkpoint(
        lambda st, xs: _segment(st, xs, a, d, chunk), prevent_cse=False)
    state, y = jax.lax.scan(
        body, state, (split(x), split(dt.astype(F32)), split(b), split(c)))
    y = jnp.moveaxis(y, 0, 1).reshape((bsz, padded) + x.shape[2:])
    return y[:, :s], state


def gate_then_group_norm(y, z, w, groups: int, eps: float):
    """Mamba-2's gated norm: u = y * SiLU(z) FIRST, then u * rsqrt(mean(u^2)
    + eps) over each of `groups` contiguous groups of the last axis, times
    w; float32 inside, y's dtype out. (`ops.gated_delta.gated_rms_norm`
    normalises a head and gates after: another function.)"""
    u = y.astype(F32) * jax.nn.silu(z.astype(F32))
    grouped = u.reshape(u.shape[:-1] + (groups, u.shape[-1] // groups))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True) + eps)
    return (grouped.reshape(u.shape) * w.astype(F32)).astype(y.dtype)
