"""Rotary position embeddings — one implementation, three formulations.

The reference contains two independent RoPE implementations:
  * complex-number rotation (llama3/LLaMA-jax.ipynb cells 16-17: interpret
    consecutive feature pairs as complex numbers, multiply by e^{i m θ_j});
  * explicit (seq, D, D) rotation matrices rebuilt per call
    (gemma/gemma.ipynb cell 7 — whose own markdown cell 21 complains about
    the resulting inference latency).

The TPU-native primary form here is the split cos/sin formulation
(`precompute_rope` + `apply_rope`): real-valued, static-shaped, fusable by
XLA, and cheap to slice for cached decode (one row per position). The
complex and matrix forms are kept as reference implementations so tests can
prove all three agree (SURVEY.md §4 test plan).

Pairing convention: features are split into interleaved (even, odd) pairs
(x[..., 0::2], x[..., 1::2]) — matching the complex-reshape convention of
the llama3 notebook.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def precompute_rope(
    head_dim: int,
    max_seq_len: int,
    theta: float = 10000.0,
    dtype: jnp.dtype = jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """Returns (cos, sin), each of shape (max_seq_len, head_dim // 2)."""
    if head_dim % 2:
        raise ValueError(f"head_dim must be even, got {head_dim}")
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = jnp.outer(jnp.arange(max_seq_len, dtype=jnp.float32), freqs)
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rope(
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    positions: jax.Array | None = None,
) -> jax.Array:
    """Rotate feature pairs of `x` by position-dependent angles.

    x:    (..., seq, num_heads, head_dim)  — seq is axis -3.
    cos/sin: (max_seq_len, head_dim // 2) tables from `precompute_rope`.
    positions: optional int array (..., seq) of absolute positions; defaults
        to arange(seq). Used for cached decode where seq==1 at offset p.
    """
    seq = x.shape[-3]
    if positions is None:
        cos_p = jax.lax.dynamic_slice_in_dim(cos, 0, seq, axis=0)
        sin_p = jax.lax.dynamic_slice_in_dim(sin, 0, seq, axis=0)
    else:
        cos_p = jnp.take(cos, positions, axis=0)
        sin_p = jnp.take(sin, positions, axis=0)
    # broadcast over the heads axis: (..., seq, 1, head_dim//2)
    cos_p = jnp.expand_dims(cos_p, axis=-2)
    sin_p = jnp.expand_dims(sin_p, axis=-2)
    x32 = x.astype(jnp.float32)
    x_even = x32[..., 0::2]
    x_odd = x32[..., 1::2]
    out_even = x_even * cos_p - x_odd * sin_p
    out_odd = x_even * sin_p + x_odd * cos_p
    # re-interleave: stack pairs on a trailing axis then flatten
    out = jnp.stack([out_even, out_odd], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Reference formulations (used by tests to cross-validate `apply_rope`).
# ---------------------------------------------------------------------------


def precompute_freqs_cis(head_dim: int, max_seq_len: int, theta: float = 10000.0) -> jax.Array:
    """Complex e^{i m θ} table, shape (max_seq_len, head_dim // 2), complex64.

    Mirrors llama3/LLaMA-jax.ipynb cell 16 semantics.
    """
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = jnp.outer(jnp.arange(max_seq_len, dtype=jnp.float32), freqs)
    return jax.lax.complex(jnp.cos(angles), jnp.sin(angles))


def apply_rotary_emb_complex(x: jax.Array, freqs_cis: jax.Array) -> jax.Array:
    """Complex-multiplication RoPE (llama3/LLaMA-jax.ipynb cell 17 semantics).

    x: (..., seq, num_heads, head_dim); freqs_cis: (seq, head_dim//2).
    """
    x32 = x.astype(jnp.float32)
    xc = jax.lax.complex(x32[..., 0::2], x32[..., 1::2])
    fc = freqs_cis.reshape((x.shape[-3], 1, x.shape[-1] // 2))
    out = xc * fc
    out = jnp.stack([jnp.real(out), jnp.imag(out)], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def partial_rotary(x: jax.Array, rotary_dim: int, theta: float) -> jax.Array:
    """Rotate-half rotary embedding (feature j paired with j + rotary_dim / 2,
    not `apply_rope`'s interleaved pairs) on the first `rotary_dim` features
    of x (B, S, heads, head_dim), positions 0..S-1; the rest pass through."""
    s = x.shape[1]
    half = rotary_dim // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:rotary_dim]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot, x32[..., rotary_dim:]], -1).astype(x.dtype)


def rope_rotation_matrix(head_dim: int, max_seq_len: int, theta: float = 10000.0) -> jax.Array:
    """Dense (max_seq_len, head_dim, head_dim) block-diagonal rotation matrices.

    The gemma/gemma.ipynb cell 7 formulation (built per call there; built
    once here). Only used in tests — O(T·D²) memory makes it a non-starter
    as a production op, which is exactly the latency bug the reference's
    own gemma markdown cell 21 reports.
    """
    cos, sin = precompute_rope(head_dim, max_seq_len, theta)
    mats = jnp.zeros((max_seq_len, head_dim, head_dim), dtype=jnp.float32)
    idx = jnp.arange(head_dim // 2)
    even, odd = 2 * idx, 2 * idx + 1
    mats = mats.at[:, even, even].set(cos)
    mats = mats.at[:, even, odd].set(-sin)
    mats = mats.at[:, odd, even].set(sin)
    mats = mats.at[:, odd, odd].set(cos)
    return mats


def sinusoidal_position_encoding(max_len: int, dim: int) -> jax.Array:
    """Classic sin/cos position table (deepseekv3/deepseekv3.ipynb cell 16):
    pe[p, 2i] = sin(p / 10000^(2i/dim)), pe[p, 2i+1] = cos(...). Returns
    (max_len, dim) float32, precomputed once and indexed by position."""
    pos = jnp.arange(max_len, dtype=jnp.float32)[:, None]
    i = jnp.arange(0, dim, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, i / dim)
    pe = jnp.zeros((max_len, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    pe = pe.at[:, 1::2].set(jnp.cos(angle[:, : dim // 2]))
    return pe
