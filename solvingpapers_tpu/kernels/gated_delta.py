"""The chunked gated delta rule for TPU (Pallas/Mosaic), forward and backward.

The rule and its chunked form are described in `ops/gated_delta.py`. Here a
chunk's C x C triangular system is made, inverted and used inside VMEM and
nothing of it reaches HBM; the state S (d_k x d_v a value head, float32)
is a VMEM scratch that rides the grid's last, sequential axis, as the flash
kernels carry their accumulators.

Forward, one streaming pass: grid (B, Hk, tiles), a grid step holds `nc`
chunks of one key head and its `grp = Hv // Hk` value heads. What does not
depend on the state (`_system`: normalised q and k, k.k and q.k once a key
head, the decays, A, (I + A)^-1 by halving, u, w, q e^G, k e^(G_last - G))
is made for the step's chunks at once, so their products overlap; what
meets the state (`_step`: four small products a value head, bf16 operands
and float32 sums) runs chunk after chunk. Only o is written, and under
differentiation the float32 state entering each grid step (at 16,384
tokens, 32 value heads of 128 x 128 and 256 tokens a step: 134 MB). Those
two are all that anything after the forward kernel reads (the caller's gate
and output projection start from o, the backward kernel walks from the
entering states; q, k, v and the decays are its inputs), so the
`custom_vjp`'s forward rule names them (`DELTA_RESIDUALS`): a caller whose
layers are rematerialised and whose slice has the room keeps them by name
and the forward kernel stands in its step once (`models/kimi_linear.py`);
without such a policy the names are identities and the layer's remat runs
the kernel again (`models/qwen3next.py`, which has no room for them).

The value heads of a key head sit side by side along the lanes: a
(C, grp*C) array holds [X_0 | X_1 | ...], one C x C matrix a value head
(at the published C = 64, grp = 2 a full 128 lanes). A product a head,
[X_0 Y_0 | X_1 Y_1], is one product of the packed X with the blocks of Y
down a diagonal, so the inverse costs a key head what it would cost one
value head.

Backward, one streaming pass in reverse with dS as the carry: a grid step
makes its chunks' systems again from q, k, G, beta (`jax.vjp` of
`_system`), walks its chunks forwards from the saved entering state and
backwards with dS (`jax.vjp` of `_step`), and hands the chunks' cotangents
to the system's backward. It is the derivative of the forward program as
written, product for product; the inverse's backward needs the inverse
alone (dM = -T^t dT T^t).

Decay shapes, chosen statically by g's rank. g (B, S, Hv), one decay a
value head and token (Gated DeltaNet; kernels `gated_delta_fwd` and
`gated_delta_bwd`): it enters as rows of running sums a head (`rows`,
`g_row`), and a chunk's system is (k.k) times a C x C matrix of decays
(`_system`). g (B, S, H, d_k), a decay a key channel (Kimi Delta
Attention, `ops/kda.py`; kernels `kda_fwd` and `kda_bwd`): the decay then
sits inside the contraction over the channels and needs a reference point a
sub-block to stay inside float32, so g enters as the log decays themselves,
laid out as q, and `_channel_system` makes the running sums and the pairs'
sums (`_pair_sums`: one product a later sub-block for the pairs in different
sub-blocks, a pass a column of the sub-blocks for the pairs inside one).
Both rules share the products, the inverse and its backward, `_step` (the
state's decay a number or a (d_k, 1) column), the grid, the block specs and
the `custom_vjp`.

Numerics references: `ops.gated_delta.gated_delta_rule_recurrent`
(tests/test_gated_delta.py) and `ops.kda.kda_rule_recurrent`
(tests/test_kda.py), interpret mode.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
LANES = 128
# chunks a grid step holds: their systems are independent, so their
# products fill the gaps in each other's chains of dependent ones
CHUNKS_A_STEP = 4
# The forward kernel's two results that anything after it reads, as
# `_rule_fwd` names them (either decay: the rule is chosen by g's rank, not
# by the name): o, which the caller's gate and output projection start
# from, and the float32 state entering each grid step, which the backward
# kernel walks from. A caller whose remat can afford them (B*S*Hv*dv in
# v's dtype and B*Hv*tiles*dk*dv float32 a call) keeps them with
# `policy=jax.checkpoint_policies.save_only_these_names(*DELTA_RESIDUALS)`
# and the forward kernel runs once; under any other policy the names are
# identities and the kernel runs again in the remat.
DELTA_RESIDUALS = ("delta_o", "delta_states")

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


class _Plan(NamedTuple):
    """What a call's kernels are built from (static)."""

    chunk: int
    nc: int  # chunks a grid step
    grp: int
    scale: float  # d_k^-0.5 of the unpadded width
    interpret: bool
    sub: int = 0  # tokens a sub-block of a chunk (a decay a channel only)


# ------------------------------------------------------------- products

def _dims(kind: str, batched: bool):
    lhs, rhs = {"nn": (1, 0), "nt": (1, 1), "tn": (0, 0)}[kind]
    if batched:
        return (((lhs + 1,), (rhs + 1,)), ((0,), (0,)))
    return (((lhs,), (rhs,)), ((), ()))


def _raw_dot(x, y, kind, precision):
    return jax.lax.dot_general(
        x, y, _dims(kind, x.ndim == 3), precision=precision,
        preferred_element_type=F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dot(x, y, kind: str, precision=None):
    """x y ("nn"), x y^t ("nt") or x^t y ("tn") over the last two axes,
    an optional leading axis batched; float32 sums. The backward products
    are written out, in the operands' dtype, so that no transpose of a
    result and no product of mixed dtypes is left to the compiler."""
    return _raw_dot(x, y, kind, precision)


def _dot_fwd(x, y, kind, precision):
    return _raw_dot(x, y, kind, precision), (x, y)


def _dot_bwd(kind, precision, res, g):
    x, y = res
    g = g.astype(x.dtype)
    dot = functools.partial(_raw_dot, precision=precision)
    if kind == "nn":
        dx, dy = dot(g, y, "nt"), dot(x, g, "tn")
    elif kind == "nt":
        dx, dy = dot(g, y, "nn"), dot(g, x, "tn")
    else:
        dx, dy = dot(y, g, "nt"), dot(x, g, "nn")
    return dx.astype(x.dtype), dy.astype(y.dtype)


_dot.defvjp(_dot_fwd, _dot_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _split(x, sizes: tuple[int, ...], axis: int = -1):
    """Slices of the last axis (or `axis`); backward is a concatenation
    (autodiff's would be one padded array a slice)."""
    out, at = [], 0
    for n in sizes:
        out.append(jax.lax.slice_in_dim(x, at, at + n, axis=axis))
        at += n
    return tuple(out)


def _split_fwd(x, sizes, axis):
    return _split(x, sizes, axis), None


def _split_bwd(sizes, axis, _, gs):
    return (jnp.concatenate(gs, axis=axis),)


_split.defvjp(_split_fwd, _split_bwd)


# ------------------------------------------- the packed (C, grp*C) layout

def _grid(c: int, grp: int):
    """(row, column within its head, head) of each place of a (C, grp*C)
    array; C is a power of two."""
    shape = (c, grp * c)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return row, lane & (c - 1), lane >> (c.bit_length() - 1)


def _down_a_diagonal(blocks):
    """[Y_0, Y_1, ...] (each (..., r, w)) as the (..., grp*r, grp*w) matrix
    with Y_j in the j-th place of the diagonal and zeros elsewhere."""
    grp = len(blocks)
    if grp == 1:
        return blocks[0]
    rows = []
    for j, y in enumerate(blocks):
        zero = jnp.zeros_like(y)
        rows.append(jnp.concatenate(
            [zero] * j + [y] + [zero] * (grp - 1 - j), axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _packed_diagonal(y, c: int, grp: int):
    """The same for a packed y (..., C, grp*C): (..., grp*C, grp*C)."""
    if grp == 1:
        return y
    head = _grid(c, grp)[2]
    return jnp.concatenate(
        [jnp.where(head == j, y, 0.0) for j in range(grp)], axis=-2)


def _packed_dot(x, y, c, grp, kind="nn"):
    """[X_0 Y_0 | X_1 Y_1 | ...] of packed x and y, or with "nt"
    [X_0 Y_0^t | ...]."""
    return _raw_dot(x, _packed_diagonal(y, c, grp), kind, HI)


def _halving(a, c: int, grp: int):
    row, col, _ = _grid(c, grp)

    def under(s):
        # the blocks under the diagonal of each pair of s x s blocks
        log = s.bit_length() - 1
        return ((row >> (log + 1) == col >> (log + 1))
                & ((row >> log) & 1 == 1) & ((col >> log) & 1 == 0))

    # pairs of single rows: the inverse of [[1, 0], [a, 1]] is [[1, 0], [-a, 1]]
    inv = (row == col).astype(F32) - jnp.where(under(1), a, 0.0)
    s = 2
    while s < c:
        low = jnp.where(under(s), a, 0.0)
        inv = inv - _packed_dot(inv, _packed_dot(low, inv, c, grp), c, grp)
        s *= 2
    return inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _unit_lower_inverse(a, c: int, grp: int):
    """(I + A_j)^-1 a head, for packed strictly lower-triangular A (...,
    C, grp*C), C a power of two, by halving: with the s x s blocks on the
    diagonal inverted (D, block-diagonal), the 2s x 2s blocks' inverses are
    D - D L D, L the blocks under the diagonal of each pair. That is
    forward substitution in blocks: no power of A is formed, so keys that
    repeat cost no precision. log2(C) - 1 rounds of two products on whole
    matrices, float32 at the highest precision."""
    return _halving(a, c, grp)


def _inverse_fwd(a, c, grp):
    t = _halving(a, c, grp)
    return t, t


def _inverse_bwd(c, grp, t, dt):
    x = _packed_dot(dt, t, c, grp, "nt")  # dT_j T_j^t
    tt = jnp.swapaxes(_packed_diagonal(t, c, grp), -1, -2)
    tt = sum(tt[..., j * c:(j + 1) * c, :] for j in range(grp))  # [T_j^t]
    return (-_packed_dot(tt, x, c, grp),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


# ------------------------------------------------- a grid step's two parts

def _l2norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _system(q, k, vs, gcols, bcols, grow, *, plan: _Plan, dt):
    """What a step's chunks need that no state enters. q, k (nc, C, dk) and
    vs[j] (nc, C, dv) as they came; gcols[j], bcols[j] (nc, C, 1) the
    running sum of g in the chunk and beta of value head j; grow (nc, 1,
    grp*C) the same sums along the lanes. Returns a head: u (nc, C, dv)
    float32; w, q e^G, k e^(G_last - G) (nc, C, dk) in dt; e^(G_last)
    (nc, 1, 1); and q.k * decay packed (nc, C, grp*C) in dt."""
    c, grp = plan.chunk, plan.grp
    dk, dv = q.shape[-1], vs[0].shape[-1]
    qh = _l2norm(q.astype(F32)) * plan.scale
    kh = _l2norm(k.astype(F32))
    row, col, head = _grid(c, grp)
    gi = sum(gcols[j] * (head == j).astype(F32) for j in range(grp))
    bi = sum(bcols[j] * (head == j).astype(F32) for j in range(grp))
    on_or_below = row >= col
    decay = jnp.where(
        on_or_below, jnp.exp(jnp.where(on_or_below, gi - grow, 0.0)), 0.0)
    keys = jnp.concatenate([kh] * grp, axis=1) if grp > 1 else kh
    kk = _dot(kh, keys, "nt", HI)  # [k.k | k.k | ...], once a key head
    qk = _dot(qh, keys, "nt", HI)
    a = jnp.where(row > col, bi * kk * decay, 0.0)
    t = _unit_lower_inverse(a, c, grp)
    attn = (qk * decay).astype(dt)  # diagonal included
    e_g = [jnp.exp(g) for g in gcols]
    rhs = jnp.concatenate([
        _down_a_diagonal([vs[j].astype(F32) * bcols[j] for j in range(grp)]),
        _down_a_diagonal([kh * (bcols[j] * e_g[j]) for j in range(grp)]),
    ], axis=-1)
    uw = _split(_dot(t, rhs, "nn", HI), (dv,) * grp + (dk,) * grp)
    us, ws = uw[:grp], [w.astype(dt) for w in uw[grp:]]
    last_row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    g_last = [jnp.sum(jnp.where(last_row, g, 0.0), axis=1, keepdims=True)
              for g in gcols]
    q_in = [(qh * e).astype(dt) for e in e_g]
    # what a chunk hands the state: k_j e^(G_last - G_j)
    k_tail = [(kh * jnp.exp(gl - g)).astype(dt)
              for gl, g in zip(g_last, gcols)]
    return list(us), ws, q_in, k_tail, [jnp.exp(gl) for gl in g_last], attn


# ------------------------------------- a decay per key channel (`ops/kda.py`)

def _eye(c: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _lower_ones(nc: int, c: int, upper: bool = False):
    row = jax.lax.broadcasted_iota(jnp.int32, (nc, c, c), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (nc, c, c), 2)
    return (row <= col if upper else row >= col).astype(F32)


@jax.custom_vjp
def _running_sum(g):
    """Row i of each chunk of g (nc, C, dk) becomes the sum of its rows 0
    to i: the lower triangle of ones times g, float32 at the highest
    precision (the ones are exact, g is taken in three pieces). Backward:
    the upper triangle."""
    return _raw_dot(_lower_ones(*g.shape[:2]), g, "nn", HI)


def _running_sum_fwd(g):
    return _running_sum(g), None


def _running_sum_bwd(_, dg):
    return (_raw_dot(_lower_ones(*dg.shape[:2], upper=True), dg, "nn", HI),)


_running_sum.defvjp(_running_sum_fwd, _running_sum_bwd)


def _places(n: int, sub: int, nb: int):
    """Of each place of the (n, sub, nb*sub) array that holds, a sub-block
    of rows, that sub-block's own columns of its chunk's (C, C) matrix: its
    row, and its column counted from the sub-block's first (0 to sub - 1
    on the diagonal blocks, outside that range elsewhere)."""
    shape = (n, sub, nb * sub)
    block = jax.lax.broadcasted_iota(jnp.int32, shape, 0) & (nb - 1)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return row, jax.lax.broadcasted_iota(jnp.int32, shape, 2) - block * sub


def _decays_from(gb, s: int, strictly: bool):
    """exp(G_r - G_s) for the rows r >= s (r > s with `strictly`) of each
    sub-block of gb (n, sub, dk), 0 for the rows before: the exponent is
    taken only where it is <= 0."""
    row = jax.lax.broadcasted_iota(jnp.int32, (1, gb.shape[1], 1), 1)
    below = row > s if strictly else row >= s
    return jnp.where(
        below, jnp.exp(jnp.where(below, gb - gb[:, s:s + 1], 0.0)), 0.0)


def _near_sums(kb, qb, gb, nb: int):
    n, sub, _ = kb.shape
    _, col = _places(n, sub, nb)
    pk = pq = jnp.zeros((n, sub, nb * sub), F32)
    for s in range(sub):  # a column of every sub-block at a time
        seen = kb[:, s:s + 1] * _decays_from(gb, s, False)  # (n, sub, dk)
        pk = jnp.where(col == s, jnp.sum(kb * seen, -1, keepdims=True), pk)
        pq = jnp.where(col == s, jnp.sum(qb * seen, -1, keepdims=True), pq)
    return pk, pq


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pairs_within(kb, qb, gb, nb: int):
    """The pairs inside a sub-block, summed channel by channel: for x = k
    and x = q, P_rs = sum_c x_rc k_sc exp(G_rc - G_sc) for s <= r, 0 above
    the diagonal. kb, qb, gb (n, sub, dk) float32, the n sub-blocks of the
    step's chunks, `nb` of them a chunk; each result (n, sub, nb*sub): a
    sub-block's rows with its pairs at its own columns of the chunk and
    zeros elsewhere, which read as (chunks, C, C) are the diagonal blocks.
    A column of the sub-blocks at a time: the key and G of that row spread
    over the sub-block's rows, one exp, two products and two sums over the
    lanes; the (sub, sub, dk) decays never exist. The backward makes the
    decays again, strictly below the diagonal: dG = k * dk_row + q * dq -
    k * dk_column, since both sides of a pair carry the same terms, but
    for the diagonal's (decay 1, no G in it), which are added to dk and dq
    afterwards so that they do not have to cancel in rounding."""
    return _near_sums(kb, qb, gb, nb)


def _pairs_within_fwd(kb, qb, gb, nb):
    return _near_sums(kb, qb, gb, nb), (kb, qb, gb)


def _pairs_within_bwd(nb, res, d):
    kb, qb, gb = res
    dpk, dpq = d
    n, sub, _ = kb.shape
    row, col = _places(n, sub, nb)
    at_row = jax.lax.broadcasted_iota(jnp.int32, (1, sub, 1), 1)
    column = lambda dp, s: jnp.sum(  # noqa: E731
        jnp.where(col == s, dp, 0.0), -1, keepdims=True)  # (n, sub, 1)
    d_krow = d_q = d_kcol = jnp.zeros_like(kb)
    for s in range(sub):
        e = _decays_from(gb, s, True)
        tk, tq = column(dpk, s) * e, column(dpq, s) * e
        d_krow = d_krow + tk * kb[:, s:s + 1]
        d_q = d_q + tq * kb[:, s:s + 1]
        d_kcol = jnp.where(
            at_row == s, jnp.sum(tk * kb + tq * qb, 1, keepdims=True), d_kcol)
    d_g = kb * (d_krow - d_kcol) + qb * d_q
    on_k = jnp.sum(jnp.where(col == row, dpk, 0.0), -1, keepdims=True)
    on_q = jnp.sum(jnp.where(col == row, dpq, 0.0), -1, keepdims=True)
    return (d_krow + d_kcol + 2.0 * on_k * kb + on_q * qb,
            d_q + on_q * kb, d_g)


_pairs_within.defvjp(_pairs_within_fwd, _pairs_within_bwd)


def _pair_sums(kh, qh, gsum, c: int, sub: int):
    """kk, qk (nc, C, C): P_ij = sum_c x_ic k_jc exp(G_ic - G_jc) for j <=
    i, x = k and x = q, 0 above the diagonal; kh, qh, gsum (nc, C, dk), G
    the running sum of the log decays in the chunk. No exponent is positive
    (`ops/kda.py`): a pair in different sub-blocks goes through G at the
    later sub-block's first row, one product a later sub-block with the
    rows of k and of q stacked; a pair inside one is `_pairs_within`'s."""
    nc, _, dk = kh.shape
    nb = c // sub
    blocks = lambda a: a.reshape(nc * nb, sub, dk)  # noqa: E731
    kb, qb, gb = blocks(kh), blocks(qh), blocks(gsum)
    near = [p.reshape(nc, c, c) for p in _pairs_within(kb, qb, gb, nb)]
    if nb == 1:
        return near
    first = jax.lax.broadcasted_iota(jnp.int32, (1, sub, 1), 1) == 0
    ref = jnp.sum(jnp.where(first, gb, 0.0), axis=1, keepdims=True)
    # <= 1, since G only falls; the first row's is 1 and is written so, or
    # its two equal and opposite gradients would have to cancel in rounding
    row_decay = jnp.where(first, 1.0, jnp.exp(gb - ref))
    # the sub-blocks that have earlier ones: all but each chunk's first
    later = lambda a: _split(  # noqa: E731
        a.reshape((nc, nb) + a.shape[1:]), (1, nb - 1), 1)[1]
    x = jnp.concatenate([
        later(xb * row_decay).reshape(nc * (nb - 1), sub, dk)
        for xb in (kb, qb)], axis=1)  # (nc (nb - 1), 2 sub, dk)
    # keys as the rows of sub-block I see them, k_j e^(r_I - G_j); the
    # columns at or after I's first row are masked below, their exponent
    # held at 0 meanwhile
    k_seen = kh[:, None] * jnp.exp(jnp.minimum(
        later(ref) - gsum[:, None], 0.0))  # (nc, nb - 1, C, dk)
    far = _dot(x, k_seen.reshape(nc * (nb - 1), c, dk), "nt", HI)
    row, col, _ = _grid(c, 1)
    log = sub.bit_length() - 1
    earlier = (col >> log) < (row >> log)
    out = []
    for rows, p in zip(_split(far, (sub, sub), 1), near):
        rows = jnp.concatenate([
            jnp.zeros((nc, sub, c), F32),
            rows.reshape(nc, (nb - 1) * sub, c)], axis=1)
        out.append(jnp.where(earlier, rows, 0.0) + p)
    return out


def _channel_system(q, k, vs, gcols, bcols, g, *, plan: _Plan, dt):
    """`_system` for a decay per key channel, one value head a key head:
    g (nc, C, dk) the log decays as they came (`gcols` is empty), the
    running sum made here. As `ops/kda.py` states the chunk: the pairs'
    sums with the decay inside the contraction, w = T (beta k e^G), q e^G,
    k e^(G_last - G), and the state's decay e^(G_last) a channel, returned
    as the (nc, dk, 1) column that `_step` multiplies the state by."""
    c = plan.chunk
    (v,), (beta,) = vs, bcols
    dk, dv = q.shape[-1], v.shape[-1]
    qh = _l2norm(q.astype(F32)) * plan.scale
    kh = _l2norm(k.astype(F32))
    gsum = _running_sum(g)
    kk, qk = _pair_sums(kh, qh, gsum, c, plan.sub)
    row, col, _ = _grid(c, 1)
    t = _unit_lower_inverse(jnp.where(row > col, beta * kk, 0.0), c, 1)
    e_g = jnp.exp(gsum)
    rhs = jnp.concatenate(
        [v.astype(F32) * beta, kh * (beta * e_g)], axis=-1)
    u, w = _split(_dot(t, rhs, "nn", HI), (dv, dk))
    last_row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    g_last = jnp.sum(jnp.where(last_row, gsum, 0.0), axis=1, keepdims=True)
    # what a chunk hands the state: k_j e^(G_last - G_j)
    k_tail = (kh * jnp.exp(g_last - gsum)).astype(dt)
    last = jnp.sum(jnp.where(_eye(dk), jnp.exp(g_last), 0.0),
                   axis=2, keepdims=True)  # the row turned: (nc, dk, 1)
    return ([u], [w.astype(dt)], [(qh * e_g).astype(dt)], [k_tail], [last],
            qk.astype(dt))  # q.k with its decay, diagonal included


def _step(us, ws, q_in, k_tail, last, attn, states, *, dt):
    """One chunk meets the state: a head u (C, dv), w, q_in, k_tail (C,
    dk), last (1, 1), state (dk, dv) float32; attn packed (C, grp*C).
    Returns o (C, grp*dv) float32 and the states the chunk leaves."""
    o_state, v_dt, new = [], [], []
    for u, w, q, kt, la, s in zip(us, ws, q_in, k_tail, last, states):
        s_dt = s.astype(dt)
        v_new = u - _dot(w, s_dt, "nn")
        v_dt.append(v_new.astype(dt))
        o_state.append(_dot(q, s_dt, "nn"))
        new.append(s * la + _dot(kt, v_dt[-1], "tn"))
    o = _dot(attn, _down_a_diagonal(v_dt), "nn")
    return o + jnp.concatenate(o_state, axis=-1), new


# ----------------------------------------------------------------- kernels

def _at(tree, n):
    """Chunk n of every array in `tree`."""
    return jax.tree.map(lambda x: x[n], tree)


def _per_channel(g) -> bool:
    """Which rule, from g as the kernels get it: the log decays themselves,
    (B, S, heads * dk), for a decay a key channel; (B, Hk, N, 1, grp * C)
    rows of running sums for a decay a head."""
    return g.ndim == 3


def _load(plan, q_ref, k_ref, v_ref, rows_ref, g_ref):
    """A grid step's blocks as `_system` (or `_channel_system`) takes them.
    A head's numbers a token (g's running sums with a decay a head; beta)
    come as rows (a (tokens, 1) column is 128 lanes wide in HBM) and are
    turned here, a chunk a time, through the identity's mask."""
    nc, c, grp = plan.nc, plan.chunk, plan.grp
    dv = v_ref.shape[-1] // grp
    by_chunk = lambda x: x.reshape((nc, c) + x.shape[1:])  # noqa: E731
    q, k = by_chunk(q_ref[0]), by_chunk(k_ref[0])
    vs = [by_chunk(v_ref[0, :, j * dv:(j + 1) * dv]) for j in range(grp)]
    eye = _eye(c)
    n = rows_ref.shape[3]  # G's sums and beta a value head, or beta alone
    cols = [jnp.sum(jnp.where(eye, rows_ref[0, 0, :, i:i + 1, :], 0.0),
                    axis=2, keepdims=True) for i in range(n)]
    g = by_chunk(g_ref[0]) if _per_channel(g_ref) else g_ref[0, 0]
    return q, k, vs, cols[:n - grp], cols[n - grp:], g


def _system_of(g_ref):
    return _channel_system if _per_channel(g_ref) else _system


def _fwd_kernel(q_ref, k_ref, v_ref, rows_ref, g_ref, o_ref, *rest,
                plan: _Plan):
    """`rest`: the scratch that carries the state, and before it, under
    differentiation, the output that keeps each grid step's entering one."""
    s_ref = rest[-1]
    dt = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    parts = _system_of(g_ref)(
        *_load(plan, q_ref, k_ref, v_ref, rows_ref, g_ref), plan=plan, dt=dt)
    states = [s_ref[j] for j in range(plan.grp)]
    for st_ref in rest[:-1]:
        st_ref[0, 0, 0] = s_ref[...]
    for n in range(plan.nc):
        o, states = _step(*_at(parts, n), states, dt=dt)
        o_ref[0, n * plan.chunk:(n + 1) * plan.chunk, :] = o.astype(dt)
    for j, s in enumerate(states):
        s_ref[j] = s


def _bwd_kernel(q_ref, k_ref, v_ref, rows_ref, g_ref, do_ref, st_ref,
                dq_ref, dk_ref, dv_ref, drows_ref, dg_ref, ds_ref, *,
                plan: _Plan):
    nc, c, grp = plan.nc, plan.chunk, plan.grp
    dt = v_ref.dtype
    dv = v_ref.shape[-1] // grp

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    parts, system_bwd = jax.vjp(
        functools.partial(_system_of(g_ref), plan=plan, dt=dt),
        *_load(plan, q_ref, k_ref, v_ref, rows_ref, g_ref))
    # forwards through the step's chunks from the state that entered it,
    # then backwards through them with dS
    states = [st_ref[0, 0, 0, j] for j in range(grp)]
    step_bwd = [None] * nc
    for n in range(nc):
        (_, states), step_bwd[n] = jax.vjp(
            functools.partial(_step, dt=dt), *_at(parts, n), states)
    d_states = [ds_ref[j] for j in range(grp)]
    d_parts = [None] * nc
    for n in reversed(range(nc)):
        d_o = do_ref[0, n * c:(n + 1) * c, :].astype(F32)
        *d_parts[n], d_states = step_bwd[n]((d_o, d_states))
    for j, s in enumerate(d_states):
        ds_ref[j] = s
    d_q, d_k, d_vs, d_gcols, d_bcols, d_g = system_bwd(
        tuple(jax.tree.map(lambda *xs: jnp.stack(xs), *d_parts)))
    flat = lambda x: x.reshape((nc * c,) + x.shape[2:])  # noqa: E731
    dq_ref[0] = flat(d_q).astype(dq_ref.dtype)
    dk_ref[0] = flat(d_k).astype(dk_ref.dtype)
    for j in range(grp):
        dv_ref[0, :, j * dv:(j + 1) * dv] = flat(d_vs[j]).astype(dv_ref.dtype)
    eye = _eye(c)
    for i, d_col in enumerate(d_gcols + d_bcols):  # columns back to rows
        drows_ref[0, 0, :, i:i + 1, :] = jnp.sum(
            jnp.where(eye, d_col, 0.0), axis=1, keepdims=True)
    if _per_channel(g_ref):
        dg_ref[0] = flat(d_g)
    else:
        dg_ref[0, 0] = d_g


def _specs(plan: _Plan, dk: int, dv: int, tiles: int, rows, g,
           reverse: bool):
    """Block specs of q or k, of v or o, of the rows a head (G and beta,
    or beta alone), of g (G's rows packed; with a decay a channel g itself,
    laid out as q), and of a step's entering state, on a grid (B, Hk,
    tiles); `reverse` walks the tiles from the last to the first. q, k, v
    are (B, S, heads * width) views: the key head's index picks the block
    of lanes."""
    nc, c, grp = plan.nc, plan.chunk, plan.grp
    at = (lambda t: tiles - 1 - t) if reverse else (lambda t: t)
    by_token = pl.BlockSpec((1, nc * c, dk), lambda b, h, t: (b, at(t), h))
    return (
        by_token,
        pl.BlockSpec((1, nc * c, grp * dv), lambda b, h, t: (b, at(t), h)),
        pl.BlockSpec((1, 1, nc, rows.shape[3], c),
                     lambda b, h, t: (b, h, at(t), 0, 0)),
        by_token if _per_channel(g) else pl.BlockSpec(
            (1, 1, nc, 1, grp * c), lambda b, h, t: (b, h, at(t), 0, 0)),
        pl.BlockSpec((1, 1, 1, grp, dk, dv),
                     lambda b, h, t: (b, h, at(t), 0, 0, 0)),
    )


def _name(g, which: str) -> str:
    return ("kda_" if _per_channel(g) else "gated_delta_") + which


def _sizes(plan: _Plan, q, v, rows):
    """(Hk, dk, dv, grid steps a sequence) of a call's arrays."""
    hk = rows.shape[1]
    return (hk, q.shape[2] // hk, v.shape[2] // (hk * plan.grp),
            q.shape[1] // (plan.nc * plan.chunk))


def _forward(plan: _Plan, q, k, v, rows, g_row, keep_states: bool):
    b = q.shape[0]
    hk, dk, dv, tiles = _sizes(plan, q, v, rows)
    qs, vs, rws, gr, sts = _specs(plan, dk, dv, tiles, rows, g_row, False)
    out_shape, out_specs = [jax.ShapeDtypeStruct(v.shape, v.dtype)], [vs]
    if keep_states:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, hk, tiles, plan.grp, dk, dv), F32))
        out_specs.append(sts)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan),
        grid=(b, hk, tiles),
        in_specs=[qs, qs, vs, rws, gr],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((plan.grp, dk, dv), F32)],
        compiler_params=_PARAMS,
        interpret=plan.interpret,
        name=_name(g_row, "fwd"),
    )(q, k, v, rows, g_row)


def _backward(plan: _Plan, q, k, v, rows, g_row, states, do):
    b = q.shape[0]
    hk, dk, dv, tiles = _sizes(plan, q, v, rows)
    qs, vs, rws, gr, sts = _specs(plan, dk, dv, tiles, rows, g_row, True)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan),
        grid=(b, hk, tiles),
        in_specs=[qs, qs, vs, rws, gr, vs, sts],
        out_specs=[qs, qs, vs, rws, gr],
        out_shape=[like(q), like(k), like(v), like(rows), like(g_row)],
        scratch_shapes=[pltpu.VMEM((plan.grp, dk, dv), F32)],
        compiler_params=_PARAMS,
        interpret=plan.interpret,
        name=_name(g_row, "bwd"),
    )(q, k, v, rows, g_row, do, states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rule(plan: _Plan, q, k, v, rows, g_row):
    return _forward(plan, q, k, v, rows, g_row, keep_states=False)[0]


def _rule_fwd(plan, q, k, v, rows, g_row):
    o, states = _forward(plan, q, k, v, rows, g_row, keep_states=True)
    # named in the kernel's own layouts, (B, S, Hv*dv) and (B, Hk, tiles,
    # grp, dk, dv) float32 (DELTA_RESIDUALS)
    o = checkpoint_name(o, DELTA_RESIDUALS[0])
    states = checkpoint_name(states, DELTA_RESIDUALS[1])
    return o, (q, k, v, rows, g_row, states)


def _rule_bwd(plan, res, do):
    return tuple(_backward(plan, *res, do))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int, sub: int = 0,
                     interpret: bool | None = None):
    """The chunked rule, either decay, chosen by g's rank. g (B, S, Hv),
    one decay a value head: arguments and result as
    `ops.gated_delta.gated_delta_rule`. g (B, S, H, dk), a decay a key
    channel: as `ops.kda.kda_rule` (every head its own q, k and v; `sub`
    the tokens a sub-block holds, a power of two that divides `chunk`).
    `chunk` a power of two. Any S (the tail of the last grid step is padded
    with tokens that write nothing) and any widths (off the interpreter
    they are padded with zeros to the 128 lanes a block of a head needs).
    `interpret` None: interpret on the CPU, the test platform, and only
    there."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    grp = hv // hk
    per_channel = g.ndim == 4
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    nc = min(CHUNKS_A_STEP, -(-s // chunk))
    plan = _Plan(chunk, nc, grp, dk ** -0.5, interpret,
                 sub if per_channel else 0)
    lane = 1 if interpret else LANES
    pad_s, pad_k, pad_v = (-s) % (nc * chunk), (-dk) % lane, (-dv) % lane
    widen = lambda a, w=0: jnp.pad(  # noqa: E731
        a, ((0, 0), (0, pad_s)) + ((0, 0),) * (a.ndim - 3) + ((0, w),))
    q, k, v = widen(q, pad_k), widen(k, pad_k), widen(v, pad_v)
    g = widen(g.astype(F32), pad_k if per_channel else 0)
    beta = widen(beta.astype(F32))
    s_all = s + pad_s
    n = s_all // chunk
    by_head = lambda a: a.reshape(b, n, chunk, hk, grp)  # noqa: E731
    if per_channel:  # the running sums are made in the kernels
        rows = by_head(beta).transpose(0, 3, 1, 4, 2)  # (B, H, N, 1, C)
        g_row = g.reshape(b, s_all, -1)
    else:
        g_sum = jnp.cumsum(by_head(g), axis=2)  # (B, N, C, Hk, grp)
        rows = jnp.concatenate([g_sum, by_head(beta)], axis=-1).transpose(
            0, 3, 1, 4, 2)  # (B, Hk, N, 2 grp, C)
        g_row = rows[:, :, :, :grp].reshape(b, hk, n, 1, grp * chunk)
    o = _rule(plan, q.reshape(b, s_all, -1), k.reshape(b, s_all, -1),
              v.reshape(b, s_all, -1), rows, g_row)
    return o.reshape(b, s_all, hv, dv + pad_v)[:, :s, :, :dv]
