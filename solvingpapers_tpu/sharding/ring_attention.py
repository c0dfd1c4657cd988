"""Ring attention: context parallelism over the `context` mesh axis.

Not present in the reference (max trained context is 256 tokens,
SURVEY.md §5 "Long-context — absent") — this is the capability the new
framework adds for sequences larger than one chip's HBM. Each device holds
a sequence shard of Q, K, V; K/V chunks rotate around the ring via
`lax.ppermute` over ICI while every device accumulates its queries' online
softmax (the blockwise/flash recurrence, so the full (S, S) score matrix
never exists anywhere).

Layout: BSNH shards inside shard_map. Causality is resolved from global
chunk positions (device i holds positions [i*S_loc, (i+1)*S_loc)); fully
masked chunks still traverse the ring (uniform schedule keeps the
collective static) but contribute zero mass.
"""

from __future__ import annotations

import functools

from solvingpapers_tpu.sharding.pipeline import shard_map_compat

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from solvingpapers_tpu.ops.attention import BIG_NEG, repeat_kv


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Per-shard ring attention body; call inside shard_map.

    q: local (B, S_loc, N, H) sequence shard; k, v: (B, S_loc, Nkv, H) with
    N % Nkv == 0 — GQA kv heads are repeated per ring step AFTER the
    transfer, so ppermute traffic carries only the Nkv heads. Returns the
    local (B, S_loc, N, H) output shard of exact softmax attention over the
    full sequence.
    """
    b, s_loc, n, h = q.shape
    n_kv = k.shape[2]
    if n % n_kv:
        raise ValueError(f"q heads {n} not a multiple of kv heads {n_kv}")
    group = n // n_kv
    if scale is None:
        scale = h**-0.5
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    q32 = q.astype(jnp.float32) * scale
    q_pos = my_idx * s_loc + jnp.arange(s_loc)

    def step(carry, i):
        m, l, acc, k_cur, v_cur = carry
        # ppermute sends to (j+1): after i steps we hold chunk (my_idx - i)
        src = (my_idx - i) % axis_size
        s_ = jnp.einsum(
            "bqnh,bknh->bnqk", q32, repeat_kv(k_cur, group).astype(jnp.float32)
        )
        if causal:
            k_pos = src * s_loc + jnp.arange(s_loc)
            mask = k_pos[None, None, None, :] <= q_pos[None, None, :, None]
            s_ = jnp.where(mask, s_, BIG_NEG)
        m_new = jnp.maximum(m, jnp.max(s_, axis=-1, keepdims=True))
        p = jnp.exp(s_ - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bnqk,bknh->bqnh", p, repeat_kv(v_cur, group).astype(jnp.float32)
        ).transpose(0, 2, 1, 3)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (m_new, l_new, acc_new, k_nxt, v_nxt), None

    # derive initial accumulators from q so they inherit its varying-axes
    # type (shard_map vma typing: plain zeros would be device-invariant)
    q_bnsh = jnp.moveaxis(q32, 1, 2)  # (B, N, S_loc, H)
    m0 = jnp.full_like(q_bnsh[..., :1], BIG_NEG)
    l0 = jnp.zeros_like(q_bnsh[..., :1])
    acc0 = jnp.zeros_like(q_bnsh)
    (m, l, acc, _, _), _ = jax.lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(axis_size)
    )
    out = acc / jnp.maximum(l, 1e-30)  # (B, N, S_loc, H)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: float | None = None,
    axis_name: str = "context",
) -> jax.Array:
    """Full-array entry point: shards the sequence axis over `axis_name`
    (batch over data/fsdp) and runs the ring. q, k, v: (B, S, N, H) with
    S divisible by the context axis size."""
    spec = P(("data", "fsdp"), axis_name, None, None)
    fn = functools.partial(
        ring_attention_local, axis_name=axis_name, causal=causal, scale=scale
    )
    return shard_map_compat(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def _ring_merge(m, l, acc, o_c, lse_c):
    """Online-softmax merge of one chunk's flash output into the running
    (m, l, acc): o_c is the chunk-normalized output, lse_c its per-row
    logsumexp, so o_c * exp(lse_c - m_new) recovers the unnormalized
    accumulator exactly."""
    m_new = jnp.maximum(m, lse_c)
    alpha = jnp.exp(m - m_new)
    beta = jnp.exp(lse_c - m_new)
    l_new = l * alpha + beta
    acc_new = acc * alpha[..., None] + o_c * beta[..., None]
    return m_new, l_new, acc_new


# Knuth multiplicative stride: distinct (owner, chunk) pairs land far apart
# in the kernel's seed space (the kernel already offsets by block uid within
# one call; the pair stride decorrelates masks ACROSS ring steps/devices).
# Plain python int — a module-level jnp constant would initialize the XLA
# backend at import time and break jax.distributed.initialize (multi-host).
_SEED_STRIDE = -1640531527


def _chunk_seed(seed, my_idx, src, axis_size):
    """Per-(q-owner, kv-chunk) dropout seed — the backward ring MUST derive
    the identical value for the same chunk so masks regenerate exactly."""
    pair = (my_idx * axis_size + src).astype(jnp.int32)
    return seed + pair * jnp.int32(_SEED_STRIDE)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _ring_flash(q3, k3, v3, seed, axis_name, heads, scale, causal, blocks,
                dropout_rate, interpret):
    out, _ = _ring_flash_fwd_scan(q3, k3, v3, seed, axis_name, heads, scale,
                                  causal, blocks, dropout_rate, interpret)
    return out


def _ring_flash_fwd_scan(q3, k3, v3, seed, axis_name, heads, scale, causal,
                         blocks, dropout_rate, interpret):
    """Forward ring: rotate kv chunks via ppermute, run the Pallas flash
    kernel per chunk, merge with the online softmax. The schedule is
    branch-free (a traced branch over pallas calls trips XLA's closed_call
    lowering cache): step 0 is statically the diagonal (causal kernel);
    all later steps run the non-causal kernel unconditionally and
    causally-invisible chunks are masked out of the merge — the same
    uniform schedule the jnp ring uses. Returns the normalized local
    output and its GLOBAL per-row lse (what the backward kernels need).

    Dropout (rate > 0, real TPU only): each (owner, chunk) pair gets its
    own kernel seed via _chunk_seed, so masks are independent across ring
    steps AND devices; the per-chunk outputs are normalized by the TRUE
    (pre-dropout) softmax masses, so the merged result is exactly
    dropout(P_full) @ V — the dense semantics."""
    from solvingpapers_tpu.kernels.flash_attention import _fwd

    n_heads, n_kv = heads
    block_q, block_k = blocks
    bn, s_loc, d = q3.shape
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    m0 = jnp.full_like(q3[..., 0], BIG_NEG, dtype=jnp.float32)  # (bn, s)
    l0 = jnp.zeros_like(m0)
    acc0 = jnp.zeros_like(q3, dtype=jnp.float32)

    # step 0: every device holds its own (diagonal) chunk
    o0, lse0 = _fwd(q3, k3, v3,
                    _chunk_seed(seed, my_idx, my_idx, axis_size),
                    n_heads, n_kv, scale, causal,
                    block_q, block_k, dropout_rate, interpret)
    m, l, acc = _ring_merge(m0, l0, acc0, o0.astype(jnp.float32),
                            lse0[:, 0, :])

    def step(carry, i):
        m, l, acc, k_cur, v_cur = carry
        src = (my_idx - i) % axis_size
        o_c, lse_c = _fwd(q3, k_cur, v_cur,
                          _chunk_seed(seed, my_idx, src, axis_size),
                          n_heads, n_kv, scale,
                          False, block_q, block_k, dropout_rate, interpret)
        lse_c = lse_c[:, 0, :]
        if causal:
            # chunk src = (my - i) % size is visible iff it is globally
            # earlier; invisible chunks contribute zero mass via lse
            lse_c = jnp.where(src < my_idx, lse_c, BIG_NEG)
        m, l, acc = _ring_merge(m, l, acc, o_c.astype(jnp.float32), lse_c)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (m, l, acc, k_nxt, v_nxt), None

    k1 = jax.lax.ppermute(k3, axis_name, perm)
    v1 = jax.lax.ppermute(v3, axis_name, perm)
    (m, l, acc, _, _), _ = jax.lax.scan(
        step, (m, l, acc, k1, v1), jnp.arange(1, axis_size)
    )
    # guard fully-masked rows (no visible kv anywhere) like the kernel does
    safe_l = jnp.where(l > 0.0, l, 1.0)
    out = (acc / safe_l[..., None]).astype(q3.dtype)
    lse_g = jnp.where(l > 0.0, m + jnp.log(safe_l), 0.0)[:, None, :]  # (bn,1,s)
    return out, lse_g


def _ring_flash_vjp_fwd(q3, k3, v3, seed, axis_name, heads, scale, causal,
                        blocks, dropout_rate, interpret):
    out, lse_g = _ring_flash_fwd_scan(q3, k3, v3, seed, axis_name, heads,
                                      scale, causal, blocks, dropout_rate,
                                      interpret)
    return out, (q3, k3, v3, seed, out, lse_g)


def _ring_flash_vjp_bwd(axis_name, heads, scale, causal, blocks,
                        dropout_rate, interpret, res, do):
    """Backward ring: rotate (k, v, dk, dv) together; each step runs the
    shared _bwd_chunk pallas sweeps against the resident chunk with the
    GLOBAL lse/delta, accumulating dq locally and dk/dv onto the traveling
    chunk. After a full cycle the dk/dv land back on their home device.
    With dropout, each chunk's _chunk_seed matches the forward's, so the
    backward kernels regenerate the exact forward masks."""
    from solvingpapers_tpu.kernels.flash_attention import _bwd_chunk

    q3, k3, v3, seed, out, lse_g = res
    n_heads, n_kv = heads
    group = n_heads // n_kv
    block_q, block_k = blocks
    bn, s_loc, d = q3.shape
    bkv = k3.shape[0]
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    do32 = do.astype(jnp.float32)
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1)[:, None, :]

    def rep(x):
        if group == 1:
            return x
        return jnp.repeat(
            x.reshape(bkv // n_kv, n_kv, s_loc, d), group, axis=1
        ).reshape(bn, s_loc, d)

    def fold(x):
        if group == 1:
            return x
        b = bn // n_heads
        return x.reshape(b, n_kv, group, s_loc, d).sum(axis=2).reshape(
            bkv, s_loc, d
        )

    def chunk_bwd(k_cur, v_cur, is_causal, lse_in, chunk_seed):
        dq, dk_r, dv_r = _bwd_chunk(
            q3, rep(k_cur), rep(v_cur), do, lse_in, delta, chunk_seed,
            scale=scale, causal=is_causal, block_q=block_q,
            block_k=block_k, dropout_rate=dropout_rate, interpret=interpret,
        )
        return (dq.astype(jnp.float32), fold(dk_r).astype(jnp.float32),
                fold(dv_r).astype(jnp.float32))

    # step 0: the diagonal chunk, statically causal — no masking needed
    dq_acc, dk_cur, dv_cur = chunk_bwd(
        k3, v3, causal, lse_g, _chunk_seed(seed, my_idx, my_idx, axis_size)
    )

    def step(carry, i):
        dq_acc, k_cur, v_cur, dk_cur, dv_cur = carry
        lse_in = lse_g
        src = (my_idx - i) % axis_size
        if causal:
            # invisible chunks (globally later than this q shard) must
            # contribute nothing. Mask BEFORE the kernel's exp(s - lse)
            # (push lse to +huge so p underflows to exactly 0): a post-hoc
            # grad * 0.0 would turn an exp overflow from unmasked outlier
            # scores into inf * 0 = NaN
            lse_in = jnp.where(src < my_idx, lse_g,
                               jnp.full_like(lse_g, -BIG_NEG))
        dq_c, dk_c, dv_c = chunk_bwd(
            k_cur, v_cur, False, lse_in,
            _chunk_seed(seed, my_idx, src, axis_size),
        )
        dq_acc = dq_acc + dq_c
        dk_cur = dk_cur + dk_c
        dv_cur = dv_cur + dv_c
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_nxt = jax.lax.ppermute(dv_cur, axis_name, perm)
        return (dq_acc, k_nxt, v_nxt, dk_nxt, dv_nxt), None

    # rotate (k, v) once so the scan sees chunks src = my-1, my-2, ...;
    # (dk, dv) ride along so each lands home after the full cycle
    k1 = jax.lax.ppermute(k3, axis_name, perm)
    v1 = jax.lax.ppermute(v3, axis_name, perm)
    dk1 = jax.lax.ppermute(dk_cur, axis_name, perm)
    dv1 = jax.lax.ppermute(dv_cur, axis_name, perm)
    (dq, _, _, dk, dv), _ = jax.lax.scan(
        step, (dq_acc, k1, v1, dk1, dv1), jnp.arange(1, axis_size)
    )
    # rotation count check: 1 pre-rotation + (size-1) end-of-step rotations
    # = size total, so every dk/dv chunk is back on its home device, with
    # the last contribution added before the final rotation
    import numpy as np

    seed_ct = np.zeros(seed.shape, jax.dtypes.float0)  # int arg: no tangent
    return (dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype),
            seed_ct)


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_flash_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    dropout_rate: float = 0.0,
    dropout_seed: jax.Array | int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Ring attention with the Pallas flash kernel as the per-chunk core
    (VERDICT r1 item 7): call inside shard_map with the sequence sharded
    over `axis_name`. Same layout contract as ring_attention_local —
    q: (B, S_loc, N, H), k/v: (B, S_loc, Nkv, H), GQA kv heads travel
    un-repeated (ppermute carries only Nkv heads; repetition happens per
    chunk inside the kernels). The (S, S) score matrix never exists on any
    device, and each chunk's inner loop is the MXU-tiled kernel instead of
    a jnp einsum."""
    from solvingpapers_tpu.kernels.flash_attention import (
        _pick_block,
        _pick_block_q,
        auto_block,
    )

    b, s_loc, n, h = q.shape
    n_kv = k.shape[2]
    if n % n_kv:
        raise ValueError(f"q heads {n} not a multiple of kv heads {n_kv}")
    if k.shape[1] != s_loc:
        # square per-shard chunks are the ring contract: the merge treats
        # the kernel's empty-row lse=0 sentinel as real unit mass, which
        # unequal shard lengths could trigger
        raise ValueError(
            f"ring chunks must be square: q shard seq {s_loc} != kv shard "
            f"seq {k.shape[1]}"
        )
    if scale is None:
        scale = h**-0.5
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    if dropout_rate > 0.0 and interpret:
        raise ValueError(
            "in-kernel dropout requires the hardware PRNG: interpret-mode "
            "pltpu.prng_random_bits is a zero stub (kernels/flash_attention)"
        )
    # seq-adaptive auto like flash_attention: an 8k+ CP shard gets the
    # long-sequence tile (the 16k sweep's 1.5-2x backward win applies to
    # each ring chunk too)
    bq = _pick_block_q(s_loc, auto_block(s_loc, block_q, h))
    bk = _pick_block(s_loc, auto_block(s_loc, block_k, h))

    q3 = q.transpose(0, 2, 1, 3).reshape(b * n, s_loc, h)
    k3 = k.transpose(0, 2, 1, 3).reshape(b * n_kv, s_loc, h)
    v3 = v.transpose(0, 2, 1, 3).reshape(b * n_kv, s_loc, h)
    seed = jnp.asarray(dropout_seed, jnp.int32).reshape(1)
    o3 = _ring_flash(
        q3, k3, v3, seed, axis_name, (n, n_kv), float(scale), bool(causal),
        (bq, bk), float(dropout_rate), interpret,
    )
    return o3.reshape(b, n, s_loc, h).transpose(0, 2, 1, 3)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: float | None = None,
    axis_name: str = "context",
    interpret: bool | None = None,
) -> jax.Array:
    """Full-array entry point for ring_flash_attention_local (tests/bench).

    check_vma=False: a pallas_call inside lax.scan under the jax-0.9 vma
    checker KeyErrors in the closed_call lowering cache; the computation is
    identical either way (verified against dense).
    """
    spec = P(("data", "fsdp"), axis_name, None, None)
    fn = functools.partial(
        ring_flash_attention_local, axis_name=axis_name, causal=causal,
        scale=scale, interpret=interpret,
    )
    return shard_map_compat(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def ulysses_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    attn_fn,
) -> jax.Array:
    """Ulysses sequence parallelism: all_to_all swaps the sequence shard for
    a head shard around the attention core (SURVEY.md §2.3 Ulysses row).

    q, k, v: local (B, S_loc, N, H); requires N % axis_size == 0. attn_fn
    receives full-sequence (B, S, N_loc, H) tensors — any attention core
    works (dense, flash kernel).
    """
    axis_size = jax.lax.psum(1, axis_name)
    if q.shape[2] % axis_size or k.shape[2] % axis_size:
        raise ValueError(
            f"Ulysses needs q heads ({q.shape[2]}) and kv heads "
            f"({k.shape[2]}) divisible by the '{axis_name}' axis size "
            f"({axis_size})"
        )
    # split heads across devices, gather sequence: (B, S, N/axis, H)
    q_g = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    k_g = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    v_g = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    o_g = attn_fn(q_g, k_g, v_g)
    # swap back: scatter sequence, gather heads
    return jax.lax.all_to_all(o_g, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    attn_fn,
    *,
    axis_name: str = "context",
) -> jax.Array:
    """Full-array Ulysses entry: sequence sharded over `axis_name`, heads
    resharded around `attn_fn` via all_to_all."""
    spec = P(("data", "fsdp"), axis_name, None, None)
    fn = functools.partial(
        ulysses_attention_local, axis_name=axis_name, attn_fn=attn_fn
    )
    return shard_map_compat(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def cp_halo_right(
    x: jax.Array,
    k: int,
    axis_name: str = "context",
    fill=0,
):
    """The first k sequence columns (dim 1) of the RIGHT neighbor's shard —
    a k-token halo exchange over the context axis via one ppermute. The
    last shard, whose halo would wrap around to shard 0, gets `fill`
    instead (the global sequence ends there).

    This is the collective that makes MTP's i+k target shift
    (deepseekv3.ipynb cell 46) local under context parallelism: shard-local
    `concat([x[:, k:], cp_halo_right(x, k)], 1)` equals the global
    left-shift-by-k of the full sequence, zero/fill-padded at the end.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    head = jax.lax.slice_in_dim(x, 0, k, axis=1)
    # source i delivers to dest i-1: every shard receives its RIGHT
    # neighbor's head
    perm = [(i, (i - 1) % n) for i in range(n)]
    halo = jax.lax.ppermute(head, axis_name, perm)
    return jnp.where(idx == n - 1, jnp.full_like(halo, fill), halo)


def cp_shift_left(
    x: jax.Array,
    k: int,
    axis_name: str = "context",
    fill=0,
) -> jax.Array:
    """Shard-local view of the GLOBAL left-shift-by-k of the sequence
    (dim 1): local columns [k:] followed by the right neighbor's first k
    columns (cp_halo_right), `fill` past the global end. The one shared
    implementation of MTP's i+k shift under context parallelism — used by
    the dense family's shifted-embedding stream, the staged family's MTP
    branch, and the loss's target stream."""
    return jnp.concatenate(
        [x[:, k:], cp_halo_right(x, k, axis_name, fill)], axis=1
    )
