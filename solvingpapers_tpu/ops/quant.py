"""Symmetric int8 quantization with per-block absmax scales — the KV
cache compression primitive behind `ServeConfig.kv_quant` (serve/kv_pool.py).

Layout contract (the cache layout of `infer/cache.py`): a cache leaf is
``(batch, time, n_heads, head_dim)`` (KVCache k/v) or ``(batch, time,
channels)`` (LatentCache c). Quantization blocks tile the TIME axis with
a static `block` length and scales are kept at LLM.int8()-style fine
granularity so one outlier cannot flatten a whole lane:

* 4-D leaves: one f32 scale per ``(batch, time-block, head)`` — the
  "per-(page, head)-block" granularity (the paged pool passes
  ``block = page_size``, so each physical page carries one scale row per
  head; the lane pool tiles lanes with `ServeConfig.kv_quant_block`).
* 3-D leaves (MLA latents): one f32 scale per ``(batch, time-block)``.
  Per-channel scales would cost 4 bytes per `block` int8 entries (25%
  at block 16 — enough to push the latent pool past the 0.6x byte
  budget), so latents take the coarser per-block scalar; what that costs
  in greedy agreement is not measured on the chip (`cli replay` scores it).

Scale semantics: ``scale = absmax / 127`` over the block, so the
block's max-magnitude entry maps to exactly +-127 and every entry obeys
``|x - q * scale| <= scale / 2`` (the classic symmetric-absmax bound).
An all-zero block has scale 0 and round-trips bit-exact (q = 0 -> 0).
Round-tripping an already-dequantized block IN F32 with an unchanged
absmax reproduces the identical int8 payload. That fixed point is what
keeps committed entries stable under the serving programs' windowed
stores (serve/kv_pool.py): untouched blocks are never
re-read-modify-written at all, and within a block a step did write,
positions outside the written window are re-encoded from their own
f32-dequantized codes — NOT from the compute-dtype lane view, where a
bf16 cast breaks the fixed point (the cast shifts the block absmax and
walks committed codes step to step) — so repeated decode steps cannot
random-walk old entries on any compute dtype.

All math runs in f32 regardless of the cache compute dtype (bf16
reductions are scalar-emulated on XLA:CPU, and a bf16 absmax would also
quantize against a degraded scale).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def scale_shape(shape: tuple, block: int) -> tuple:
    """Scale-array shape for a cache leaf of `shape` tiled by `block`
    along the time axis: ``(B, T//block, H)`` for 4-D leaves,
    ``(B, T//block)`` for 3-D ones. The shapes the sidecar pools pin."""
    if len(shape) not in (3, 4):
        raise ValueError(
            f"cache leaves are (B, T, H, D) or (B, T, C); got {shape}"
        )
    b, t = shape[0], shape[1]
    if t % block:
        raise ValueError(
            f"time length {t} is not a multiple of the quant block {block}"
        )
    if len(shape) == 4:
        return (b, t // block, shape[2])
    return (b, t // block)


def _reduce_axes(ndim: int) -> tuple:
    # blocked view (B, nb, block, ...): reduce the block axis plus every
    # trailing axis EXCEPT the head axis of 4-D leaves
    if ndim == 4:
        return (2, 4)
    if ndim == 3:
        return (2, 3)
    raise ValueError(f"cache leaves are 3-D or 4-D; got ndim {ndim}")


def quantize(x, block: int):
    """Symmetric int8 quantization of a cache leaf (traced).

    Returns ``(q int8, scale f32)`` with `q` shaped like `x` and `scale`
    shaped `scale_shape(x.shape, block)`. ``q = round(x / scale)``
    clipped to [-127, 127] (the -128 code is unused, keeping the code
    space symmetric); zero-absmax blocks quantize to q = 0, scale = 0.
    """
    sshape = scale_shape(x.shape, block)  # validates shape + block
    b, t = x.shape[0], x.shape[1]
    xs = x.astype(jnp.float32).reshape((b, t // block, block) + x.shape[2:])
    red = _reduce_axes(x.ndim)
    absmax = jnp.max(jnp.abs(xs), axis=red, keepdims=True)
    sfull = absmax / 127.0
    q = jnp.where(sfull > 0.0, xs / jnp.where(sfull > 0.0, sfull, 1.0), 0.0)
    q = jnp.clip(jnp.round(q), -127.0, 127.0).astype(jnp.int8)
    return q.reshape(x.shape), sfull.reshape(sshape)


def dequantize(q, scale, dtype=jnp.float32):
    """Inverse of `quantize`: ``q * scale`` broadcast per block, cast to
    `dtype` (the cache compute dtype). The block length is recovered from
    the shapes, so the scale array IS the layout metadata."""
    b, t = q.shape[0], q.shape[1]
    nb = scale.shape[1]
    if nb < 1 or t % nb:
        raise ValueError(
            f"scale blocks {nb} do not tile the time axis {t}"
        )
    block = t // nb
    qs = q.astype(jnp.float32).reshape((b, nb, block) + q.shape[2:])
    if q.ndim == 4:
        sfull = scale[:, :, None, :, None]
    elif q.ndim == 3:
        sfull = scale[:, :, None, None]
    else:
        raise ValueError(f"cache leaves are 3-D or 4-D; got ndim {q.ndim}")
    return (qs * sfull).reshape(q.shape).astype(dtype)


def quantize_tree(tree, block: int):
    """Quantize every leaf of a cache pytree: ``(q_tree, scale_tree)``
    with both trees matching the input structure (flax-struct cache
    nodes keep their class — a KVCache of scales is just a container)."""
    flat, treedef = jax.tree_util.tree_flatten(tree)
    pairs = [quantize(a, block) for a in flat]
    return (jax.tree_util.tree_unflatten(treedef, [q for q, _ in pairs]),
            jax.tree_util.tree_unflatten(treedef, [s for _, s in pairs]))


def dequantize_tree(q_tree, scale_tree, dtype=jnp.float32):
    """Leafwise `dequantize` over parallel payload/scale pytrees."""
    return jax.tree_util.tree_map(
        lambda q, s: dequantize(q, s, dtype), q_tree, scale_tree
    )
