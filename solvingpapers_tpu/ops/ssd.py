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

`ssd_chunked` is the chunked form for the timed path. With l = dt a the log
decay a token and L its running sum inside a chunk of Q tokens (inclusive),
a chunk is

    y_t = sum_{s<=t} exp(L_t - L_s) dt_s (C_t . B_s) x_s     [inside it]
        + exp(L_t) S_0 C_t                                   [what entered]
    S_Q = exp(L_Q) S_0 + sum_s exp(L_Q - L_s) dt_s x_s B_s^T

It runs as two Pallas kernels (`kernels/ssd.py`: `ssd_fwd`, `ssd_bwd`), on a
TPU compiled by Mosaic and on the CPU interpreted: the forward streams over
the sequence once, makes each chunk's running sums, decays and C B^T (once a
group, shared by its heads) in VMEM and carries S in float32 in VMEM from
chunk to chunk; the backward streams once in reverse with dS as the carry,
makes each chunk's sums and decays again and starts from the float32 state
that entered its grid step, which the forward wrote. Nothing of size Q x Q
reaches HBM. Every decay is the exponential of a DIFFERENCE of running sums
taken where it is <= 0 (s <= t; the masked half is set to -inf before the
exponential, never after), so nothing overflows and nothing is divided by: a
head that forgets within a token underflows to zero and nothing else.
Products take their operands in x's dtype (bfloat16 on the chip) and add up
in float32; decays, running sums and the state are float32.

`ssd_step` is the rule for one token as written at the top (what a decode
step would run), `ssd_recurrent` a scan of it over the sequence: the
numerics reference of the tests, float32 throughout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from solvingpapers_tpu.kernels import ssd as kernel

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
# tokens a chunk holds (the published `chunk_size`), and tokens a
# rematerialised block of the model's per-token stages around the rule
# holds. Read when called, so a test can shrink them.
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


def ssd_chunked(x, dt, a, b, c, d=None, *, state=None, chunk=None):
    """x (B, S, H, P); dt (B, S, H) float32, > 0; a (H,) < 0; b, c (B, S, G,
    N) with G dividing H (head h reads group h // (H / G)); d (H,) or None;
    `state` (B, H, P, N) what an earlier call left (None: zeros). Returns
    (y (B, S, H, P) in x's dtype, the float32 state after the last token).
    Any S: the tail is padded with tokens of step zero, which neither decay
    nor write. `chunk` defaults to the module's CHUNK."""
    chunk = CHUNK if chunk is None else chunk
    bsz, s, h, _ = x.shape
    if h % b.shape[2] or b.shape != c.shape:
        raise ValueError(f"{h} heads over b {b.shape}, c {c.shape}")
    if dt.shape != (bsz, s, h) or a.shape != (h,):
        raise ValueError(f"dt {dt.shape} and a {a.shape} must be one step a "
                         f"head and token and one decay a head of {x.shape}")
    zero = _zero_state(x, b)
    if state is not None and state.shape != zero.shape:
        raise ValueError(f"state {state.shape} must be a head's (P, N), "
                         f"{zero.shape}")
    return kernel.ssd_chunked(
        x, dt, a, b, c, jnp.zeros_like(a) if d is None else d,
        zero if state is None else state, chunk=chunk)


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
