"""The chunked Mamba-2 state-space recurrence for TPU (Pallas/Mosaic),
forward and backward.

The rule and its chunked form are described in `ops/ssd.py`. Here a chunk's
running sums, its (Q, Q) decays a head and C B^T a group are made and used
inside VMEM and nothing of them reaches HBM; the float32 state (a group's
heads stacked, (R P, N)) is a VMEM scratch that rides the grid's last,
sequential axis, as `kernels/gated_delta.py` carries the delta rules'.

Forward (`ssd_fwd`), one streaming pass: grid (B, G K, tiles), a grid step
holds `nc` chunks of one BLOCK of a group's heads (K blocks a group: a group
of up to `HEADS_A_STEP` heads is one block, a wider one, 64 heads that share
ONE group, is cut into blocks of that many, and the blocks of B and C ignore
which block of their group reads them): x (tokens, R P) with the block's R
heads side by side along the lanes as the convolution hands them, B and C
(tokens, N), and the float32 step as rows (R, Q), the tokens along the
lanes. What no state enters (`_system`: the running sums L, a product
with a triangle of ones; e^L and the writing weights e^(L_Q - L_s) dt_s, made as rows and turned
to columns by a product with the identity; the decays e^(L_t - L_s), masked
to -inf BEFORE the exponential; C B^T once a group; the chunk's own part and
the skip term) is made for the step's chunks at once; what meets the state (`_step`: C S_0^t scaled by e^L, and the state's
decay and the chunk's write) runs chunk after chunk. Heads narrower than the
128 lanes share a tile of lanes: their (Q, Q) score matrices sit side by side
and multiply x's tile with each head's columns down a diagonal, so that no
slice starts inside a tile. Only y is written and the state after the last
token, and under differentiation the float32 state entering each grid step.
In training y and those entering states are all that anything after the
forward kernel reads (the caller's gate, norm and output projection start
from y, the backward kernel walks from the entering states, and no training
caller reads the last state), so the `custom_vjp`'s forward rule names the
two (`SSD_RESIDUALS`): a caller whose layers are rematerialised and whose
slice has the room keeps them by name and the forward kernel stands in its
step once (`models/nemotron_h.py`, `models/granite_hybrid.py`); without
such a policy the names are identities and the remat runs the kernel again.

Backward (`ssd_bwd`), one streaming pass in reverse with dS as the carry: a
grid step makes its chunks' sums, decays and C B^T again (`jax.vjp` of
`_system`), walks its chunks forwards from the saved entering state and
backwards with dS (`jax.vjp` of `_step`), and writes dx, dB and dC (summed
over the block's heads by construction; where a group has several blocks
each writes its own in float32 and they are added outside), d(dt) as rows,
and what d(a), d(d) and d(state) add up from in blocks that stay in VMEM
along the sequence. It is the derivative of the forward program as written,
product for product.

Products take their operands in x's dtype and add up in float32 (the sums
with the triangle and the identity: float32 at the highest precision, both
exact in their 0/1 operand); running sums, decays and the state are float32;
every decay is the exponential of a difference that is <= 0; nothing is
divided by.

Numerics reference: `ops.ssd.ssd_recurrent` (tests/test_ssd.py, interpret
mode).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from solvingpapers_tpu.kernels.gated_delta import (
    F32, HI, LANES, _PARAMS, _at, _dot, _split)

# chunks a grid step holds: what no state enters is made for all of them at
# once, and the saved entering states are one a grid step; at most
# `TOKENS_A_STEP` tokens of them, since a chunk's decays are (Q, Q) a head
CHUNKS_A_STEP = 4
TOKENS_A_STEP = 512
# heads a grid step holds: a group's, or where a group has more (one group of
# 64) a block of this many, with B and C read again by every block
HEADS_A_STEP = 8
# The forward kernel's two results that anything after it reads in training,
# as `_rule_fwd` names them: y, which the caller's gate, norm and output
# projection start from, and the float32 state entering each grid step,
# which the backward kernel walks from. A caller whose remat can afford
# them (B*S*H*P in x's dtype and B*H*tiles*P*N float32 a call) keeps them
# with `policy=jax.checkpoint_policies.save_only_these_names(
# *SSD_RESIDUALS)` and the forward kernel runs once; under any other policy
# the names are identities and the kernel runs again in the remat. The
# state after the last token is not named: no training caller reads it, and
# with y and the states kept nothing in the remat asks for the call.
SSD_RESIDUALS = ("ssd_y", "ssd_states")
# the backward holds what four chunks' `_system` made and its cotangents at
# once: 18 MiB at the published widths, over the 16 the compiler grants unasked
_BWD_PARAMS = pltpu.CompilerParams(
    dimension_semantics=_PARAMS.dimension_semantics,
    vmem_limit_bytes=32 * 2 ** 20)


class _Plan(NamedTuple):
    """What a call's kernels are built from (static)."""

    chunk: int
    nc: int  # chunks a grid step
    r: int  # heads a grid step: a group's, or a block of them
    p: int  # a head's width (padded)
    interpret: bool
    blocks: int = 1  # blocks of heads a group

    @property
    def hp(self) -> int:
        """Heads that share a tile of lanes."""
        hp = LANES // self.p if LANES % self.p == 0 else 1
        return hp if self.r % hp == 0 else 1


def _lane_width(r: int, p: int) -> int:
    """A head's width off the interpreter: whole tiles of lanes, or the next
    power of two under a tile where that many heads share one (`hp` of them,
    dividing the group's R)."""
    if p >= LANES:
        return -(-p // LANES) * LANES
    wide = 1 << (p - 1).bit_length()
    return wide if r % (LANES // wide) == 0 else LANES


def _lane(width: int):
    return jax.lax.broadcasted_iota(jnp.int32, (1, 1, width), 2)


def _beside(parts, axis: int = -1):
    return jnp.concatenate(parts, axis) if len(parts) > 1 else parts[0]


def _spread(cols, plan: _Plan):
    """R columns (nc, Q, 1), one a head, as (nc, Q, R P): each head's over
    its P lanes."""
    p, hp = plan.p, plan.hp
    tiles = []
    for k in range(0, plan.r, hp):
        tile = cols[k + hp - 1]
        for i in reversed(range(hp - 1)):
            tile = jnp.where(_lane(hp * p) < (i + 1) * p, cols[k + i], tile)
        tiles.append(jnp.broadcast_to(tile, tile.shape[:2] + (hp * p,)))
    return _beside(tiles)


def _down_a_diagonal(x, plan: _Plan):
    """A tile of x (nc, Q, hp P) as (nc, hp Q, hp P): head i's columns in
    rows i Q to (i + 1) Q, zeros elsewhere."""
    lane = _lane(x.shape[-1]) // plan.p
    return _beside(
        [jnp.where(lane == i, x, jnp.zeros_like(x)) for i in range(plan.hp)],
        axis=1)


@jax.custom_vjp
def _columns(cols):
    """The columns of cols (nc, Q, n), each (nc, Q, 1); backward puts each
    back at its lane (a concatenation would start n arrays inside a tile)."""
    return _split(cols, (1,) * cols.shape[-1])


def _columns_fwd(cols):
    return _columns(cols), None


def _columns_bwd(_, gs):
    return (sum(jnp.where(_lane(len(gs)) == j, g, 0.0)
                for j, g in enumerate(gs)),)


_columns.defvjp(_columns_fwd, _columns_bwd)


# ------------------------------------------------- a grid step's two parts

def _system(x, b, c, dt, a, d, *, plan: _Plan):
    """What a step's chunks need that no state enters. x (nc, Q, R P), b, c
    (nc, Q, N) as they came; dt (nc, R, Q) float32 rows; a (R, 1); d (1,
    R P). Returns a chunk: its own part of y with the skip term (Q, R P)
    float32; e^L over each head's lanes (Q, R P); what it writes, x e^(L_Q -
    L_s) dt_s (Q, R P) in x's dtype; e^(L_Q) a head (R, 1)."""
    nc, q, _ = x.shape
    r, p, hp = plan.r, plan.p, plan.hp
    cdt = x.dtype
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # L, inclusive, along the lanes: l times the upper triangle of ones
    run = _dot((dt * a).reshape(nc * r, q), (row <= col).astype(F32), "nn",
               HI).reshape(nc, r, q)
    # L_Q (nc, R, 1): a sum, since a slice would start at the last lane
    total = jnp.sum(jnp.where(_lane(q) == q - 1, run, 0.0), -1, keepdims=True)
    rows = jnp.concatenate(
        [run, jnp.exp(run), jnp.exp(total - run) * dt], axis=1)
    # rows to columns, a chunk: (nc, Q, 3 R), column k R + h
    eye = jnp.broadcast_to((row == col).astype(F32), (nc, q, q))
    cols = _columns(_dot(eye, rows, "nt", HI))
    cb = _dot(c, b, "nt")  # (nc, Q, Q), once a group
    x_tiles = _split(x, (hp * p,) * (r // hp))
    own = []
    for k, x_tile in enumerate(x_tiles):
        scores = []
        for h in range(k * hp, (k + 1) * hp):
            diff = cols[h] - run[:, h:h + 1]
            decay = jnp.exp(jnp.where(row >= col, diff, -jnp.inf))
            scores.append((decay * dt[:, h:h + 1] * cb).astype(cdt))
        own.append(_dot(_beside(scores), _down_a_diagonal(x_tile, plan), "nn"))
    x32 = x.astype(F32)
    e_l = _spread(cols[r:2 * r], plan)
    wrote = (x32 * _spread(cols[2 * r:], plan)).astype(cdt)
    return _beside(own) + d * x32, e_l, wrote, jnp.exp(total)


def _step(own, e_l, wrote, last, b, c, state, *, p: int):
    """One chunk meets the state (R P, N) float32. Returns y (Q, R P)
    float32 and the state the chunk leaves."""
    cdt = b.dtype
    y = own + e_l * _dot(c, state.astype(cdt), "nt")
    heads = _split(state, (p,) * (state.shape[0] // p), 0)
    kept = jnp.concatenate(
        [s * last[h:h + 1] for h, s in enumerate(heads)], axis=0)
    return y, kept + _dot(wrote, b, "tn")


# ----------------------------------------------------------------- kernels

def _load(plan: _Plan, x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref):
    by_chunk = lambda v: v.reshape(  # noqa: E731
        (plan.nc, plan.chunk) + v.shape[1:])
    return (by_chunk(x_ref[0]), by_chunk(b_ref[0]), by_chunk(c_ref[0]),
            dt_ref[0, 0], a_ref[0], d_ref[0])


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, s0_ref, y_ref,
                sn_ref, *rest, plan: _Plan):
    """`rest`: the scratch that carries the state, and before it, under
    differentiation, the output that keeps each grid step's entering one."""
    s_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[0, 0]

    for st_ref in rest[:-1]:
        st_ref[0, 0, 0] = s_ref[...]
    x, b, c, dt, a, d = _load(plan, x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref)
    parts = _system(x, b, c, dt, a, d, plan=plan)
    state = s_ref[...]
    for n in range(plan.nc):
        y, state = _step(*_at(parts, n), b[n], c[n], state, p=plan.p)
        y_ref[0, n * plan.chunk:(n + 1) * plan.chunk, :] = y.astype(
            y_ref.dtype)
    s_ref[...] = state
    sn_ref[0, 0] = state  # the block stays in VMEM: the last step's is kept


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, st_ref, dy_ref,
                dsn_ref, dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dd_ref,
                ds0_ref, ds_ref, *, plan: _Plan):
    nc, q = plan.nc, plan.chunk

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = dsn_ref[0, 0]
        # a head's sums over the sequence: their blocks ride the last axis
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    x, b, c, dt, a, d = _load(plan, x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref)
    parts, system_bwd = jax.vjp(
        functools.partial(_system, plan=plan), x, b, c, dt, a, d)
    # forwards through the step's chunks from the state that entered it,
    # then backwards through them with dS
    state = st_ref[0, 0, 0]
    step_bwd = [None] * nc
    for n in range(nc):
        (_, state), step_bwd[n] = jax.vjp(
            functools.partial(_step, p=plan.p), *_at(parts, n), b[n], c[n],
            state)
    d_state = ds_ref[...]
    d_parts, d_b, d_c = [None] * nc, [None] * nc, [None] * nc
    for n in reversed(range(nc)):
        d_y = dy_ref[0, n * q:(n + 1) * q, :].astype(F32)
        *d_parts[n], d_b[n], d_c[n], d_state = step_bwd[n]((d_y, d_state))
    ds_ref[...] = d_state
    ds0_ref[0, 0] = d_state  # the block stays in VMEM: the last step's is kept
    d_x, d_bs, d_cs, d_dt, d_a, d_d = system_bwd(
        tuple(jax.tree.map(lambda *xs: jnp.stack(xs), *d_parts)))
    flat = lambda v: v.reshape((nc * q,) + v.shape[2:])  # noqa: E731
    dx_ref[0] = flat(d_x).astype(dx_ref.dtype)
    db_ref[0] = flat(d_bs + jnp.stack(d_b)).astype(db_ref.dtype)
    dc_ref[0] = flat(d_cs + jnp.stack(d_c)).astype(dc_ref.dtype)
    ddt_ref[0, 0] = d_dt
    da_ref[0, 0] += d_a
    dd_ref[0, 0] += d_d


def _specs(plan: _Plan, n: int, tiles: int, reverse: bool):
    """Block specs on a grid (B, G K, tiles), K blocks of R heads a group,
    of x or y (B, S, H P), of b or c (B, S, G N), of the step's rows (B, G K,
    chunks, R, Q), of a (G K, R, 1), of d (G K, 1, R P), of a state a block
    (B, G K, R P, N) and of a grid step's entering state (B, G K, tiles, R P,
    N); `reverse` walks the tiles from the last to the first. Last, of what
    a block adds to db or dc where a group has several, (B, S, G K N)."""
    nc, q, rp = plan.nc, plan.chunk, plan.r * plan.p
    at = (lambda t: tiles - 1 - t) if reverse else (lambda t: t)
    own = pl.BlockSpec((1, nc * q, n), lambda i, g, t: (i, at(t), g))
    k = plan.blocks
    return (
        pl.BlockSpec((1, nc * q, rp), lambda i, g, t: (i, at(t), g)),
        own if k == 1 else pl.BlockSpec(
            (1, nc * q, n), lambda i, g, t: (i, at(t), g // k)),
        pl.BlockSpec((1, 1, nc, plan.r, q),
                     lambda i, g, t: (i, g, at(t), 0, 0)),
        pl.BlockSpec((1, plan.r, 1), lambda i, g, t: (g, 0, 0)),
        pl.BlockSpec((1, 1, rp), lambda i, g, t: (g, 0, 0)),
        pl.BlockSpec((1, 1, rp, n), lambda i, g, t: (i, g, 0, 0)),
        pl.BlockSpec((1, 1, 1, rp, n), lambda i, g, t: (i, g, at(t), 0, 0)),
        own,
    )


def _like(v):
    return jax.ShapeDtypeStruct(v.shape, v.dtype)


def _forward(plan: _Plan, x, b, c, dt, a, d, state, keep_states: bool):
    bsz, groups, rp, n = state.shape
    tiles = x.shape[1] // (plan.nc * plan.chunk)
    xs, bs, dts, a_s, d_s, ss, sts, _ = _specs(plan, n, tiles, False)
    out_shape, out_specs = [_like(x), _like(state)], [xs, ss]
    if keep_states:
        out_shape.append(jax.ShapeDtypeStruct(
            (bsz, groups, tiles, rp, n), F32))
        out_specs.append(sts)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan),
        grid=(bsz, groups, tiles),
        in_specs=[xs, bs, bs, dts, a_s, d_s, ss],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rp, n), F32)],
        compiler_params=_PARAMS,
        interpret=plan.interpret,
        name="ssd_fwd",
    )(x, b, c, dt, a, d, state)


def _backward(plan: _Plan, x, b, c, dt, a, d, states, dy, d_last):
    bsz, groups, tiles, rp, n = states.shape
    xs, bs, dts, a_s, d_s, ss, sts, dbs = _specs(plan, n, tiles, True)
    # a block's own db and dc where a group has several: float32, as the
    # sum over a group's heads is inside the kernel
    d_b = _like(b) if plan.blocks == 1 else jax.ShapeDtypeStruct(
        (bsz, x.shape[1], groups * n), F32)
    # d(a) and d(d) a batch row: summed over it outside
    per_row = lambda v: (  # noqa: E731
        jax.ShapeDtypeStruct((bsz,) + v.shape, F32),
        pl.BlockSpec((1, 1) + v.shape[1:], lambda i, g, t: (i, g, 0, 0)))
    (da_shape, da_spec), (dd_shape, dd_spec) = per_row(a), per_row(d)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan),
        grid=(bsz, groups, tiles),
        in_specs=[xs, bs, bs, dts, a_s, d_s, sts, xs, ss],
        out_specs=[xs, dbs, dbs, dts, da_spec, dd_spec, ss],
        out_shape=[_like(x), d_b, d_b, _like(dt), da_shape, dd_shape,
                   _like(d_last)],
        scratch_shapes=[pltpu.VMEM((rp, n), F32)],
        compiler_params=_BWD_PARAMS,
        interpret=plan.interpret,
        name="ssd_bwd",
    )(x, b, c, dt, a, d, states, dy, d_last)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rule(plan: _Plan, x, b, c, dt, a, d, state):
    return tuple(_forward(plan, x, b, c, dt, a, d, state, keep_states=False))


def _rule_fwd(plan, x, b, c, dt, a, d, state):
    y, last, states = _forward(plan, x, b, c, dt, a, d, state,
                               keep_states=True)
    # named in the kernel's own layouts, (B, S, H P) and (B, G K, tiles,
    # R P, N) float32 (SSD_RESIDUALS)
    y = checkpoint_name(y, SSD_RESIDUALS[0])
    states = checkpoint_name(states, SSD_RESIDUALS[1])
    return (y, last), (x, b, c, dt, a, d, states)


def _rule_bwd(plan, res, cts):
    d_x, d_b, d_c, d_dt, d_a, d_d, d_state = _backward(plan, *res, *cts)
    if plan.blocks > 1:
        b = res[1]
        over_blocks = lambda v: v.reshape(  # noqa: E731
            v.shape[:2] + (-1, plan.blocks, d_state.shape[-1])
        ).sum(3).reshape(b.shape).astype(b.dtype)
        d_b, d_c = over_blocks(d_b), over_blocks(d_c)
    return (d_x, d_b, d_c, d_dt, d_a.sum(0), d_d.sum(0), d_state)


_rule.defvjp(_rule_fwd, _rule_bwd)


def ssd_chunked(x, dt, a, b, c, d, state, *, chunk: int,
                interpret: bool | None = None):
    """The chunked rule; arguments and results as `ops.ssd.ssd_chunked`,
    with d (H,) and state (B, H, P, N) float32 given. Any S (the tail of
    the last grid step is padded with tokens of step zero, which neither
    decay nor write) and any widths (off the interpreter a head's width and
    the state's are padded with zeros to the lanes a block needs).
    `interpret` None: interpret on the CPU, the test platform, and only
    there."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    # heads a grid step, and blocks of them a group
    r = h // g
    if r > HEADS_A_STEP and r % HEADS_A_STEP == 0:
        r = HEADS_A_STEP
    k = h // (g * r)
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    nc = min(CHUNKS_A_STEP, max(1, TOKENS_A_STEP // chunk), -(-s // chunk))
    pad_s = (-s) % (nc * chunk)
    pad_n = 0 if interpret else (-n) % LANES
    wide = p if interpret else _lane_width(r, p)
    pad_p = wide - p
    plan = _Plan(chunk, nc, r, wide, interpret, k)
    widen = lambda v, w: jnp.pad(  # noqa: E731
        v, ((0, 0), (0, pad_s), (0, 0), (0, w)))
    s_all = s + pad_s
    x2 = widen(x, pad_p).reshape(bsz, s_all, h * wide)
    b2 = widen(b, pad_n).reshape(bsz, s_all, -1)
    c2 = widen(c, pad_n).reshape(bsz, s_all, -1)
    # the step as rows, the tokens along the lanes: (B, G K, chunks, R, Q)
    gk = g * k
    rows = jnp.pad(dt.astype(F32), ((0, 0), (0, pad_s), (0, 0))).reshape(
        bsz, s_all // chunk, chunk, gk, r).transpose(0, 3, 1, 4, 2)
    d2 = jnp.broadcast_to(
        d.astype(F32).reshape(gk, 1, r, 1), (gk, 1, r, wide)).reshape(
            gk, 1, r * wide)
    state2 = jnp.pad(state.astype(F32), (
        (0, 0), (0, 0), (0, pad_p), (0, pad_n))).reshape(
            bsz, gk, r * wide, n + pad_n)
    y, last = _rule(plan, x2, b2, c2, rows, a.astype(F32).reshape(gk, r, 1),
                    d2, state2)
    return (y.reshape(bsz, s_all, h, wide)[:, :s, :, :p],
            last.reshape(bsz, h, wide, n + pad_n)[:, :, :p, :n])
