"""The causal depthwise convolution in front of every recurrent mixer here
(Gated DeltaNet, Kimi Delta Attention, Mamba-2): K shifted multiply-adds a
channel with a backward pass written the same way."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


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
    K arrays of the sequence's size nor the convolution's output are kept.
    There is no bias here: a caller whose convolution has one (Mamba-2,
    `models/mixers.py`) calls with `silu` False and applies SiLU(y + bias)
    itself, under a `jax.checkpoint` of its own."""
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
