"""The routed experts' feed-forward unit over the rows each expert really
holds (Pallas/Mosaic), forward and backward: the gated `w3(act(w1 x) * (w2
x))` (SwiGLU with act = swish) or, with no `w2`, the two-matrix `w3 act(w1
x)` (squared ReLU), the activation an argument.

`ops.moe` gathers the kept (token, expert) pairs into (E, C, D) capacity
slots; an expert's filled slots are a PREFIX of its C rows (`ops.moe._routes`
sorts them there) and the rest are zero rows, which either unit maps to zero
(the ungated one because its activation maps 0 to 0; nothing has a bias). So
the kernels here are handed the E fill counts as a scalar-prefetch argument
and skip every row tile that lies wholly behind an expert's fill: same
capacity, same drops, same rows, same result, and no product with a tile of
zeros.

Forward, grid (expert, row tile): a live tile makes a = x w1 and, gated,
g = x w2 (float32 sums, kept in the operands' dtype for the backward, as XLA
keeps them, but H padded to whole lanes) and y = h w3 with h = act(a) * g or
act(a); a skipped tile writes zeros to y and nothing else. The backward
differentiates h with `jax.vjp` inside the kernel, so the activation's
derivative is written nowhere. Where an expert's weights, their gradients'
float32 sums and the gradients' blocks on the way out fit in VMEM together
(`SUMS_VMEM`; at 1,408 x 512 they do), it is one kernel on the same grid:
from x, dy, a, g it makes dx = da w1^t + dg w2^t (written over dy, which
nothing else reads) and adds the tile's da^t x, dg^t x, h^t dy to the
expert's weight gradients, float32 in VMEM scratch along the row axis and
written once, in the weights' dtype, at the expert's last grid step. Where
they do not (the published widths: 1,920 x 2,688, 1,024 x 2,304), it is two:
`moe_glu_bwd_dw` on the grid (expert, block of H, row tile) needs a block of
w3 only, keeps the same sums a block of H at a time and writes da (and dg)
over a (and g); `moe_glu_bwd_dx` on the forward's grid makes dx from them and
the whole w1 (and w2). Nothing is computed twice, and a weight is fetched
once an expert: the row tiles are the innermost axis everywhere. A skipped
tile writes zeros to dx. An input of a skipped tile names the block of the
expert's last live tile, so nothing is fetched for it; so do a, g, da and dg
on the way out, and behind the fill they hold whatever was there, which
nothing reads.

The kernels take w1 and w2 in the order the TPU keeps them in, and their
gradients come back so (`_Plan.ups_t`, from H alone). A TPU keeps an
(E, D, H) float32 array of H = 1,365 or 1,856 with D innermost (that pads
nothing), so there all the weights go in as (E, H, D), rows of D, w1 and w2
handed over transposed, and on the way from the weights to the call and
from the call to the optimizer no rows are transposed; with (E, D, H)
operands XLA converted the optimizer's moments to the gradients' layout and
back, 36 copies a step. An H of whole lanes (1,024) it keeps as written, H
innermost, so there w1 and w2 go in as (E, D, H): handed over transposed,
the weights, their gradients and the moments were copied the other way, 56
copies and 12 ms a step. H is padded with zero rows to the next multiple of
128 (a and g have it as their lanes): whatever h reads there meets zero
rows of w3, the same result exactly.

A `pallas_call` returns three arrays or more where the unit has them: the
benchmark's `benchmarks/kernels/flash_mla.kind_of` reads ANY Mosaic call
with one or two results as a flash-attention kernel (in the one cell
whose metrics ask it, every call here has three; the ungated forward has
two and `moe_glu_bwd_dx` one).

Numerics reference: the einsums of `MoELayer.expert_body` and
`HeldExpertsMoE.expert_fn` (tests/test_moe_grouped.py, interpret mode).
"""

from __future__ import annotations

import functools
from collections.abc import Callable
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from solvingpapers_tpu.kernels.flash_attention import is_tpu_backend

F32 = jnp.float32
LANES = 128
# rows a grid step: at most one tile an expert is wasted on its fill's
# remainder (module constant read at call time: a test shrinks it)
ROW_TILE = 256
# what the backward's float32 sums, the weight blocks beside them and the
# gradient blocks on their way out may take of VMEM, so that the tiles of
# rows and a step's temporaries find room under the calls' 64 MiB (read at
# call time: a test shrinks it)
SUMS_VMEM = 48 * 1024 * 1024


def _params(grid_rank: int) -> pltpu.CompilerParams:
    # an expert's weights sit in VMEM beside a tile's temporaries: more
    # than the default 16 MiB scope
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) + ("arbitrary",) * (grid_rank - 1),
        vmem_limit_bytes=64 * 1024 * 1024,
    )


def engages(capacity: int, dim: int) -> bool:
    """Whether a layer runs its routed experts through these kernels: on
    one TPU, with whole row tiles and whole lanes. A `pallas_call` is
    opaque to GSPMD (`kernels/sharded_flash.py`): under a mesh of several
    devices its operands would be gathered and every device would run every
    expert, so there, as on the CPU and for a decode or prefill call's few
    slots, the einsums run."""
    return (
        is_tpu_backend()
        and jax.device_count() == 1
        and capacity % ROW_TILE == 0
        and dim % LANES == 0
    )


class _Plan(NamedTuple):
    tile: int
    interpret: bool
    act: Callable[[jax.Array], jax.Array]  # elementwise, float32
    ups_t: bool  # w1 and w2 are (E, H, D), as w3 is; else (E, D, H)


def _dot(x, y, lhs: int, rhs: int):
    """x . y contracting axis `lhs` of x with axis `rhs` of y, float32."""
    return jax.lax.dot_general(
        x, y, (((lhs,), (rhs,)), ((), ())), preferred_element_type=F32)


def _into_h(x, w, t: bool):
    """x (rows, D) through w1 or w2, (H, D) if `t` else (D, H)."""
    return _dot(x, w, 1, 1 if t else 0)


def _out_of_h(dp, w, t: bool):
    """dp (rows, H) back through the same weight: (rows, D)."""
    return _dot(dp, w, 1, 0 if t else 1)


def _hidden(act):
    """h of the float32 products into H: act(a) * g, or act(a) where the
    unit has no gate."""
    return lambda a, *g: act(a) * g[0] if g else act(a)


def _fwd_kernel(fill_ref, x_ref, *refs, tile, act, ups_t):
    n = len(refs) // 2 - 1  # products into H: a, or a and g
    up_refs, w3_ref, y_ref, pre_refs = (
        refs[:n], refs[n], refs[n + 1], refs[n + 2:])
    live = pl.program_id(1) * tile < fill_ref[pl.program_id(0)]

    @pl.when(live)
    def _():
        x = x_ref[0]
        pre = [_into_h(x, w_ref[0], ups_t).astype(p_ref.dtype)
               for w_ref, p_ref in zip(up_refs, pre_refs)]
        for p_ref, p in zip(pre_refs, pre):
            p_ref[0] = p
        # from a and g as they are kept, so that the backward differentiates
        # the function the forward computed
        h = _hidden(act)(*[p.astype(F32) for p in pre])
        y_ref[0] = _dot(h.astype(x.dtype), w3_ref[0], 1, 0).astype(y_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)


def _bwd_kernel(fill_ref, x_ref, dy_ref, *refs, tile, act, ups_t, n, whole):
    """The sums along the rows. `whole`: the grid is (expert, row tile),
    the weights are whole and dx is made here; else it is (expert, block
    of H, row tile), of the weights only w3's block is here, and da (and
    dg) leave for `_dx_kernel`."""
    refs = list(refs)
    take = lambda k: [refs.pop(0) for _ in range(k)]  # noqa: E731
    pre_refs, up_refs, (w3_ref,) = take(n), take(n if whole else 0), take(1)
    out_refs, dw_refs, sum_refs = take(1 if whole else n), take(n + 1), refs
    row_axis = 1 if whole else 2
    i = pl.program_id(row_axis)
    live = i * tile < fill_ref[pl.program_id(0)]

    @pl.when(i == 0)
    def _():
        for s_ref in sum_refs:
            s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(live)
    def _():
        x, dy = x_ref[0], dy_ref[0]
        dt = x.dtype
        h, pull = jax.vjp(_hidden(act), *[p_ref[0].astype(F32)
                                          for p_ref in pre_refs])
        sum_refs[n][...] += _dot(h.astype(dt), dy, 0, 0)
        dpre = [dp.astype(dt) for dp in pull(_dot(dy, w3_ref[0], 1, 1))]
        if whole:
            dx = sum(_out_of_h(dp, w_ref[0], ups_t)
                     for dp, w_ref in zip(dpre, up_refs))
            out_refs[0][0] = dx.astype(out_refs[0].dtype)
        else:
            for o_ref, dp in zip(out_refs, dpre):
                o_ref[0] = dp
        for s_ref, dp in zip(sum_refs, dpre):
            s_ref[...] += _dot(dp, x, 0, 0) if ups_t else _dot(x, dp, 0, 0)

    if whole:
        @pl.when(jnp.logical_not(live))
        def _():
            out_refs[0][...] = jnp.zeros_like(out_refs[0])

    @pl.when(i == pl.num_programs(row_axis) - 1)
    def _():
        for dw_ref, s_ref in zip(dw_refs, sum_refs):
            dw_ref[0] = s_ref[...].astype(dw_ref.dtype)


def _dx_kernel(fill_ref, *refs, tile, ups_t):
    n = len(refs) // 2
    dpre_refs, up_refs, dx_ref = refs[:n], refs[n:2 * n], refs[2 * n]
    live = pl.program_id(1) * tile < fill_ref[pl.program_id(0)]

    @pl.when(live)
    def _():
        dx = sum(_out_of_h(dp_ref[0], w_ref[0], ups_t)
                 for dp_ref, w_ref in zip(dpre_refs, up_refs))
        dx_ref[0] = dx.astype(dx_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        dx_ref[...] = jnp.zeros_like(dx_ref)


def _specs(tile: int, d: int, ups_t: bool, h_axis: bool = False):
    """Makers of block specs on the grid (expert, row tile) or, with
    `h_axis`, (expert, block of H, row tile): `rows(width)`, a tile of rows
    with skipped tiles held at the expert's last live one; `rows(width,
    of_h)`, the same of the grid step's block of H columns; `rows(width,
    walked)`, as the grid walks them; `down(h)`, an expert's w3, or the
    grid step's block of h of its H rows; `up(h)`, the same of w1 and w2,
    whose H is their rows or (`ups_t` false) their columns."""

    def on(index):
        # `index` over (expert, block of H, row tile, fills)
        if h_axis:
            return index
        return lambda e, i, fill_ref: index(e, 0, i, fill_ref)

    def held(e, i, fill_ref):
        last = jnp.maximum((fill_ref[e] + (tile - 1)) // tile - 1, 0)
        return jnp.minimum(i, last)

    def rows(width, index=on(lambda e, b, i, f: (e, held(e, i, f), 0))):
        return pl.BlockSpec((1, tile, width), index)

    of_h = on(lambda e, b, i, f: (e, held(e, i, f), b))
    walked = on(lambda e, b, i, f: (e, i, 0))
    down = lambda h: pl.BlockSpec(  # noqa: E731
        (1, h, d), on(lambda e, b, i, f: (e, b, 0)))
    up = down if ups_t else lambda h: pl.BlockSpec(  # noqa: E731
        (1, d, h), on(lambda e, b, i, f: (e, 0, b)))
    return rows, of_h, walked, down, up


def _h_blocks(hp: int, d: int, itemsize: int, n: int) -> int:
    """How the backward of n weights of (hp, d) fits `SUMS_VMEM`: 0, one
    kernel holds it all; else the number of blocks of H (whole lanes each)
    the sums are kept in. A row of H costs its n float32 sums, the n
    gradients' rows on their way out and w3's row (Pallas keeps two
    buffers an operand) and the float32 result of a product or two on its
    way into a sum; in one kernel also the other weights' rows."""
    row = d * (n * 4 + n * 2 * itemsize + 2 * itemsize + 2 * 4)
    if hp * (row + d * (n - 1) * 2 * itemsize) <= SUMS_VMEM:
        return 0
    lanes = hp // LANES
    for blocks in range(1, lanes + 1):
        if lanes % blocks == 0 and hp // blocks * row <= SUMS_VMEM:
            return blocks
    raise ValueError(f"{LANES} rows of {n} weights {d} wide do not fit "
                     f"{SUMS_VMEM} bytes of VMEM")


def _forward(plan: _Plan, xe, ups, w3, fill):
    e, c, d = xe.shape
    hp = w3.shape[1]
    n = len(ups)
    rows, _, walked, down, up = _specs(plan.tile, d, plan.ups_t)
    kept = jax.ShapeDtypeStruct((e, c, hp), xe.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tile=plan.tile, act=plan.act,
                          ups_t=plan.ups_t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e, c // plan.tile),
            in_specs=[rows(d)] + [up(hp)] * n + [down(hp)],
            out_specs=[rows(d, walked)] + [rows(hp)] * n,
        ),
        out_shape=[jax.ShapeDtypeStruct(xe.shape, xe.dtype)] + [kept] * n,
        compiler_params=_params(2),
        interpret=plan.interpret,
        name="moe_glu_fwd",
    )(fill, xe, *ups, w3)


def _backward(plan: _Plan, xe, ups, w3, fill, pre, dye):
    e, c, d = xe.shape
    hp = w3.shape[1]
    n = len(ups)
    blocks = _h_blocks(hp, d, xe.dtype.itemsize, n + 1)
    whole = blocks == 0
    hb = hp if whole else hp // blocks
    tiles = c // plan.tile
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    rows, of_h, walked, down, up = _specs(
        plan.tile, d, plan.ups_t, not whole)
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, tile=plan.tile, act=plan.act,
                          ups_t=plan.ups_t, n=n, whole=whole),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e, tiles) if whole else (e, blocks, tiles),
            in_specs=[rows(d), rows(d)] + [rows(hb, of_h)] * n
            + [up(hp)] * (n if whole else 0) + [down(hb)],
            out_specs=([rows(d, walked)] if whole else [rows(hb, of_h)] * n)
            + [up(hb)] * n + [down(hb)],
            scratch_shapes=[pltpu.VMEM(
                (hb, d) if plan.ups_t else (d, hb), F32)] * n
            + [pltpu.VMEM((hb, d), F32)],
        ),
        out_shape=[like(a) for a in ((xe,) if whole else pre) + (*ups, w3)],
        # dx takes dy's place: a tile of dy is read before its dx is
        # written; so da and dg take a's and g's
        input_output_aliases=(
            {2: 0} if whole else {3 + j: j for j in range(n)}),
        compiler_params=_params(2 if whole else 3),
        interpret=plan.interpret,
        name="moe_glu_bwd" if whole else "moe_glu_bwd_dw",
    )(fill, xe, dye, *pre, *(ups if whole else ()), w3)
    if whole:
        return outs
    rows, _, walked, _, up = _specs(plan.tile, d, plan.ups_t)
    dxe = pl.pallas_call(
        functools.partial(_dx_kernel, tile=plan.tile, ups_t=plan.ups_t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e, tiles),
            in_specs=[rows(hp)] * n + [up(hp)] * n,
            out_specs=rows(d, walked),
        ),
        out_shape=like(xe),
        compiler_params=_params(2),
        interpret=plan.interpret,
        name="moe_glu_bwd_dx",
    )(fill, *outs[:n], *ups)
    return [dxe, *outs[n:]]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _glu(plan, xe, ups, w3, fill):
    return _forward(plan, xe, ups, w3, fill)[0]


def _glu_fwd(plan, xe, ups, w3, fill):
    ye, *pre = _forward(plan, xe, ups, w3, fill)
    return ye, (xe, ups, w3, fill, tuple(pre))


def _glu_bwd(plan, res, dye):
    xe, ups, w3, fill, pre = res
    n = len(ups)
    dxe, *dws = _backward(plan, xe, ups, w3, fill, pre, dye)
    return dxe, tuple(dws[:n]), dws[n], None


_glu.defvjp(_glu_fwd, _glu_bwd)


def grouped_glu(xe, w1, w2, w3, fill, *, activation,
                interpret: bool | None = None):
    """`w3(activation(w1 x) * (w2 x))` or, with `w2` None,
    `w3 activation(w1 x)` an expert over (E, C, D) slots whose first
    `fill[e]` rows hold expert e's tokens and whose other rows are zero:
    xe (E, C, D), w1 and w2 (E, D, H), w3 (E, H, D) in one dtype, fill (E,)
    int32 -> (E, C, D). `activation` is elementwise, and without a gate it
    maps 0 to 0 (zero rows must give zero rows). C is a multiple of
    `ROW_TILE`. Differentiable in xe and the weights. `interpret` None:
    interpret on the CPU, Mosaic elsewhere, as `flash_attention` chooses."""
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    c, h = xe.shape[1], w1.shape[-1]
    if c % ROW_TILE:
        raise ValueError(f"{c} slots an expert, not whole tiles of {ROW_TILE}")
    # w1 and w2 in the order a TPU keeps them in, so that the way from the
    # weights and back to their gradients transposes no rows: an (E, D, H)
    # array whose H is no whole number of lanes is kept with D innermost
    # (that pads nothing), and goes in as (E, H, D), H padded; one whose H
    # is whole lanes is kept as it is written, and goes in so
    ups_t = h % LANES != 0
    pad = ((0, 0), (0, (-h) % LANES), (0, 0))
    ups = tuple(jnp.pad(w.swapaxes(1, 2), pad) if ups_t else w
                for w in (w1, w2) if w is not None)
    return _glu(_Plan(ROW_TILE, interpret, activation, ups_t), xe, ups,
                jnp.pad(w3, pad), fill.astype(jnp.int32))
