"""The routed experts' SwiGLU over the rows each expert really holds
(Pallas/Mosaic), forward and backward.

`ops.moe` gathers the kept (token, expert) pairs into (E, C, D) capacity
slots; an expert's filled slots are a PREFIX of its C rows (`ops.moe._routes`
sorts them there) and the rest are zero rows, which `w3(swish(w1 x) * (w2 x))`
maps to zero. So the kernels here are handed the E fill counts as a
scalar-prefetch argument and skip every row tile that lies wholly behind an
expert's fill: same capacity, same drops, same rows, same result, and no
product with a tile of zeros.

Forward, grid (expert, row tile): a live tile makes a = x w1, g = x w2
(float32 sums, kept in the operands' dtype for the backward, as XLA keeps
them, but H padded to whole lanes) and y = (swish(a) g) w3; a skipped tile
writes zeros to y and nothing else. Backward, one kernel on the same grid:
from x, dy, a, g it makes dx = da w1^t + dg w2^t (written over dy, which
nothing else reads) and adds the tile's da^t x, dg^t x, h^t dy to the
expert's three weight gradients, float32 in VMEM scratch along the row axis
and written once, in the weights' dtype, at the expert's last grid step; a
skipped tile writes zeros to dx. An input of a skipped tile names the block
of the expert's last live tile, so nothing is fetched for it; so do a and g
on the way out, and behind the fill they hold whatever was there, which
nothing reads.

The kernels take all three weights as (E, H, D), rows of D: w1 and w2 are
handed over transposed, and their gradients come back so. A TPU keeps an
(E, D, H) float32 array of H = 1,365 with D innermost (that pads nothing),
so on the way from the weights to the call and from the call to the
optimizer no rows are transposed; with (E, D, H) operands XLA converted the
optimizer's moments to the gradients' layout and back, 36 copies a step.
H is padded with zero rows to the next multiple of 128 (a and g have it as
their lanes): swish(0) * 0 = 0 meets zero rows of w3, the same result
exactly.

Each `pallas_call` returns three arrays or more: the benchmark's
`benchmarks/kernels/flash_mla.kind_of` reads ANY Mosaic call with one or
two results as a flash-attention kernel.

Numerics reference: the three einsums of `MoELayer.expert_body`
(tests/test_moe_grouped.py, interpret mode).
"""

from __future__ import annotations

import functools
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

# the expert's three weights and, backward, their three gradients twice
# (float32 sums, blocks on the way out) sit in VMEM beside a tile's
# temporaries: more than the default 16 MiB scope
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024,
)


def engages(capacity: int, dim: int) -> bool:
    """Whether `MoELayer` runs its routed experts through these kernels:
    on one TPU, with whole row tiles and whole lanes. A `pallas_call` is
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


def _dot(x, y, lhs: int, rhs: int):
    """x . y contracting axis `lhs` of x with axis `rhs` of y, float32."""
    return jax.lax.dot_general(
        x, y, (((lhs,), (rhs,)), ((), ())), preferred_element_type=F32)


def _fwd_kernel(fill_ref, x_ref, w1_ref, w2_ref, w3_ref, y_ref, a_ref, g_ref,
                *, tile):
    live = pl.program_id(1) * tile < fill_ref[pl.program_id(0)]

    @pl.when(live)
    def _():
        x = x_ref[0]
        a = _dot(x, w1_ref[0], 1, 1).astype(a_ref.dtype)
        g = _dot(x, w2_ref[0], 1, 1).astype(g_ref.dtype)
        a_ref[0], g_ref[0] = a, g
        # from a and g as they are kept, so that the backward differentiates
        # the function the forward computed
        a, g = a.astype(F32), g.astype(F32)
        h = a * jax.nn.sigmoid(a) * g
        y_ref[0] = _dot(h.astype(x.dtype), w3_ref[0], 1, 0).astype(y_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)


def _bwd_kernel(fill_ref, x_ref, dy_ref, a_ref, g_ref, w1_ref, w2_ref, w3_ref,
                dx_ref, dw1_ref, dw2_ref, dw3_ref, s1_ref, s2_ref, s3_ref,
                *, tile):
    i = pl.program_id(1)
    live = i * tile < fill_ref[pl.program_id(0)]

    @pl.when(i == 0)
    def _():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)
        s3_ref[...] = jnp.zeros_like(s3_ref)

    @pl.when(live)
    def _():
        x, dy = x_ref[0], dy_ref[0]
        dt = x.dtype
        a, g = a_ref[0].astype(F32), g_ref[0].astype(F32)
        s = jax.nn.sigmoid(a)
        sw = a * s
        s3_ref[...] += _dot((sw * g).astype(dt), dy, 0, 0)
        dh = _dot(dy, w3_ref[0], 1, 1)
        da = (dh * g * (s + sw * (1.0 - s))).astype(dt)
        dg = (dh * sw).astype(dt)
        dx = _dot(da, w1_ref[0], 1, 0) + _dot(dg, w2_ref[0], 1, 0)
        dx_ref[0] = dx.astype(dx_ref.dtype)
        s1_ref[...] += _dot(da, x, 0, 0)
        s2_ref[...] += _dot(dg, x, 0, 0)

    @pl.when(jnp.logical_not(live))
    def _():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        dw1_ref[0] = s1_ref[...].astype(dw1_ref.dtype)
        dw2_ref[0] = s2_ref[...].astype(dw2_ref.dtype)
        dw3_ref[0] = s3_ref[...].astype(dw3_ref.dtype)


def _specs(tile: int):
    """Makers of block specs: `rows(width)`, a tile of rows with skipped
    tiles held at the expert's last live one; `rows(width, walked)`, as the
    grid walks them; `expert(*shape)`, an expert's weight."""

    def held(e, i, fill_ref):
        last = jnp.maximum((fill_ref[e] + (tile - 1)) // tile - 1, 0)
        return e, jnp.minimum(i, last), 0

    def rows(width, index=held):
        return pl.BlockSpec((1, tile, width), index)

    walked = lambda e, i, fill_ref: (e, i, 0)  # noqa: E731
    expert = lambda *shape: pl.BlockSpec(  # noqa: E731
        (1, *shape), lambda e, i, fill_ref: (e, 0, 0))
    return rows, walked, expert


def _forward(plan: _Plan, xe, w1, w2, w3, fill):
    e, c, d = xe.shape
    hp = w3.shape[1]
    rows, walked, expert = _specs(plan.tile)
    kept = jax.ShapeDtypeStruct((e, c, hp), xe.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tile=plan.tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e, c // plan.tile),
            in_specs=[rows(d), expert(hp, d), expert(hp, d), expert(hp, d)],
            out_specs=[rows(d, walked), rows(hp), rows(hp)],
        ),
        out_shape=[jax.ShapeDtypeStruct(xe.shape, xe.dtype), kept, kept],
        compiler_params=_PARAMS,
        interpret=plan.interpret,
        name="moe_glu_fwd",
    )(fill, xe, w1, w2, w3)


def _backward(plan: _Plan, xe, w1, w2, w3, fill, a, g, dye):
    e, c, d = xe.shape
    hp = w3.shape[1]
    rows, walked, expert = _specs(plan.tile)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, tile=plan.tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e, c // plan.tile),
            in_specs=[rows(d), rows(d), rows(hp), rows(hp),
                      expert(hp, d), expert(hp, d), expert(hp, d)],
            out_specs=[rows(d, walked),
                       expert(hp, d), expert(hp, d), expert(hp, d)],
            scratch_shapes=[pltpu.VMEM((hp, d), F32)] * 3,
        ),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in (xe, w1, w2, w3)],
        # dx takes dy's place: a tile of dy is read before its dx is written
        input_output_aliases={2: 0},
        compiler_params=_PARAMS,
        interpret=plan.interpret,
        name="moe_glu_bwd",
    )(fill, xe, dye, a, g, w1, w2, w3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _glu(plan, xe, w1, w2, w3, fill):
    return _forward(plan, xe, w1, w2, w3, fill)[0]


def _glu_fwd(plan, xe, w1, w2, w3, fill):
    ye, a, g = _forward(plan, xe, w1, w2, w3, fill)
    return ye, (xe, w1, w2, w3, fill, a, g)


def _glu_bwd(plan, res, dye):
    xe, w1, w2, w3, fill, a, g = res
    return (*_backward(plan, xe, w1, w2, w3, fill, a, g, dye), None)


_glu.defvjp(_glu_fwd, _glu_bwd)


def grouped_glu(xe, w1, w2, w3, fill, *, interpret: bool | None = None):
    """`w3(swish(w1 x) * (w2 x))` an expert over (E, C, D) slots whose first
    `fill[e]` rows hold expert e's tokens and whose other rows are zero:
    xe (E, C, D), w1 and w2 (E, D, H), w3 (E, H, D) in one dtype, fill (E,)
    int32 -> (E, C, D). C is a multiple of `ROW_TILE`. Differentiable in xe
    and the weights. `interpret` None: interpret on the CPU, Mosaic
    elsewhere, as `flash_attention` chooses."""
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    c, h = xe.shape[1], w1.shape[-1]
    if c % ROW_TILE:
        raise ValueError(f"{c} slots an expert, not whole tiles of {ROW_TILE}")
    # all three as (E, H, D), rows of D: the layout a TPU keeps an (E, D, H)
    # array of this H in has D innermost too, so the way from the weights
    # and back to their gradients transposes no rows
    pad = ((0, 0), (0, (-h) % LANES), (0, 0))
    w1, w2, w3 = (jnp.pad(w, pad) for w in
                  (w1.swapaxes(1, 2), w2.swapaxes(1, 2), w3))
    return _glu(_Plan(ROW_TILE, interpret), xe, w1, w2, w3,
                fill.astype(jnp.int32))
