"""Blockwise flash attention for TPU (Pallas/Mosaic).

New TPU-native code — the reference computes dense (S, S) score matrices in
every notebook (e.g. gpt/gpt-jax.ipynb cell 9, LLaMA-jax.ipynb cell 24) and
has no custom kernels to port (SURVEY.md §0). This kernel family provides:

  * forward: online-softmax blockwise attention, causal or bidirectional,
    never materializing the (S, S) score matrix in HBM
  * GQA/MQA without materializing repeated KV heads (the kv block index map
    folds the q-head -> kv-head mapping, replacing ops.repeat_kv)
  * backward: custom VJP with separate dq and dk/dv kernels recomputing
    probabilities from the saved log-sum-exp (FlashAttention-2 style)
  * in-kernel attention-prob dropout: masks generated from
    (seed, block id) by the TPU PRNG and regenerated identically in the
    backward kernels — no (S, S) mask tensor ever exists (validated by the
    linearity identity in tests/test_flash_dropout_tpu.py; interpret-mode
    prng is a zero stub, so dropout tests are hardware-gated)
  * a selection mask (PR 48): `flash_attention(..., mask=)` takes a (B, Sq,
    Skv) array, one for all heads of a batch row, a byte a pair, and the
    forward and both backward kernels read a (block_q, block_k) tile of it
    beside K and V and join it to the causal test before the softmax
    (`_seen`, one definition for the three); `selected_probs`, a fourth,
    forward-only kernel, makes the heads' mean probability a pair from the
    forward's log-sum-exp (`ops/dsa.py`'s target for its indexer). No tile
    is SKIPPED for a mask: only causality skips tiles (`_live`). The four
    are named `flash_masked_*`, not `flash_mla_*` (`_name`). `mask=None`
    is a Python branch and traces to the program there was before. Still
    not taken: a window, sinks, additive biases, a soft cap, segment ids
    (ROADMAP R-M13).

Numerics reference: ops.dot_product_attention (tests/test_flash_attention.py
asserts forward and gradient equality in interpret mode).

Layout: public API is BSNH (batch, seq, heads, head_dim) to match ops/;
kernels run on (batch*heads, seq, head_dim). The grid is 3-D — (batch*heads,
q-blocks, kv-blocks) with the kv axis 'arbitrary' (sequential) and the
online-softmax state carried in VMEM scratch — so VMEM holds only
O(block_q x block_k) tiles regardless of sequence length. (The earlier 2-D
formulation kept full-length K/V rows in VMEM and hit the 16 MB scoped-vmem
ceiling at seq 16k. What one chip's HBM cannot hold at all is the job
of context parallelism: ring attention or Ulysses resharding, in
`sharding/`.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG_NEG = -2.0**30
# The two BACKWARD kernels' tiles, one size for both sides (`auto_block`):
# 512 below LONG_SEQ, 1,024 from there on where the key width fits 128
# lanes (tools/sweep_flash_bwd.py, v5e, 16k: 1024/1024 beats 512/512 by
# 1.56x forward + backward on the MLA shape and 1.53x on GQA); at 256
# lanes the compiler refuses `flash_mla_bwd_dq` in 1,024-tiles (VMEM,
# tests/test_chip_compile.py). _pick_block still shrinks either side to
# fit a shorter sequence.
DEFAULT_BLOCK = 512
LONG_SEQ = 8192
LONG_SEQ_BLOCK = 1024
LONG_SEQ_MAX_HEAD_DIM = 128
# The FORWARD kernel's own tiles (`flash_blocks`): 1,024 x 1,024 at every
# sequence length, for keys and values up to 256 lanes wide. Measured alone
# on a v5e, pair by pair, at the benchmark's six shapes and two shorter ones
# (tools/sweep_flash_fwd.py; the table is in PERF.md section 6, PR 45): it
# is the fastest pair Mosaic takes in the default scoped VMEM at all six,
# 39% faster a call than 512 x 512 at (2, 4096, 16 on 16, 128) and at
# (1, 16384, 32 on 32, keys 192 / values 128), 12% at (1, 16384, 16 on 2,
# 256), and what `auto_block` already gave the three shapes at 16k and 8k
# in 128 lanes. A grid step's cost is mostly not its products: the
# (block_q, 1) columns m, l and alpha are broadcast over the lanes and the
# accumulator rescaled once a KEY block, so a wider key tile spreads that
# over more scores, until the masked half of a wider tile costs more
# (2,048 and 4,096 wide are slower everywhere). Wider heads than the sweep
# saw keep the backward's pair.
FWD_BLOCK = 1024
FWD_MAX_HEAD_DIM = 256
# The pairs of a call that has a selection mask, whose (block_q, block_k)
# int8 tile sits in VMEM beside K and V, and the scoped VMEM such a call
# asks for. Measured on a v5e at (1, 16384, 32 on 4, 128), keye_vl2_ep8's
# call (tools/sweep_flash_fwd.py, tools/sweep_flash_bwd.py with `--vmem-mib
# 64`; the tables are in PERF.md section 6, PR 48). Forward: 1,024 x 1,024
# (18.9 ms a call, 59% of its roofline; 2,048 x 1,024 the same, every other
# pair slower) fits Mosaic's default 16 MiB. Backward: 1,024 x 1,024 again
# (64.5 ms forward + backward in the backward sweep's count, 70.0 at 512 x
# 1,024, the fastest pair the default takes: `flash_masked_bwd_dkv` at 1,024
# x 1,024 passes it by 16 KiB), so the masked calls raise the limit; the
# v5e has 128 MiB. `selected_probs` holds a float32 OUTPUT tile besides:
# 2,048 x 2,048 (9.0 ms a call against 10.6 at 1,024 x 1,024, the fastest
# the default takes). Heads wider than the 128 lanes the sweep saw keep
# DEFAULT_BLOCK.
MASKED_FWD_BLOCKS = (1024, 1024)
MASKED_BWD_BLOCKS = (1024, 1024)
PROBS_BLOCKS = (2048, 2048)
MASKED_VMEM_BYTES = 64 << 20

_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _params(mask, semantics=_SEMANTICS):
    """A call's compiler parameters: a masked call asks for
    MASKED_VMEM_BYTES of scoped VMEM, any other takes Mosaic's default."""
    if mask is None:
        return pltpu.CompilerParams(dimension_semantics=semantics)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=MASKED_VMEM_BYTES)


# The forward kernel's two results that the backward reads, as
# `_flash_fwd` names them. A caller whose remat can afford them
# (B*N*S*(Dv bf16 + one float32) bytes a call) keeps them with
# `policy=jax.checkpoint_policies.save_only_these_names(*FLASH_RESIDUALS)`
# and the forward kernel runs once; under any other policy the names are
# identities and the kernel runs again in the remat.
FLASH_RESIDUALS = ("flash_o", "flash_lse")


def _dropout_keep(shape, seed_val, block_uid, rate):
    """Regenerable dropout keep-mask for one (q-block, k-block) score tile.

    Seeded by (seed, flat block id) so the forward and both backward kernels
    reproduce the identical mask regardless of their loop order. Returns a
    bool keep array; caller scales kept probs by 1/(1-rate).
    """
    pltpu.prng_seed(seed_val + block_uid)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    threshold = jnp.uint32(min(int((1.0 - rate) * 4294967296.0), 4294967295))
    return bits < threshold


def is_tpu_backend() -> bool:
    """True when the default device is a TPU — where the Mosaic kernel and
    its hardware PRNG run; False on the CPU test platform and any other
    backend."""
    return jax.devices()[0].platform == "tpu"


def auto_block(seq: int, requested: int | None, head_dim: int) -> int:
    """Resolve a caller's block request: None = seq-adaptive auto
    (LONG_SEQ_BLOCK past LONG_SEQ, DEFAULT_BLOCK below — the measured
    crossover, see the constants above); an explicit int is honored.
    Shared by flash_attention and the ring-flash per-chunk core so long
    CP shards get the long-sequence tile too. `head_dim` is the KEY width
    (q's and k's; a narrower value width changes nothing here). The
    long-sequence tile was measured at widths up to 128; at 256 the
    backward-dq kernel's 1024 x 1024 tiles no longer fit the v5e's VMEM
    (the compiler refuses them, tests/test_chip_compile.py), so wider
    heads keep DEFAULT_BLOCK. A width that is no multiple of 128 counts as
    the next multiple, the lanes its tiles take in VMEM: 192 is 256 there
    and keeps DEFAULT_BLOCK like 256."""
    if requested is not None:
        if requested <= 0:
            raise ValueError(f"block size must be positive, got {requested}")
        return requested
    if seq >= LONG_SEQ and head_dim <= LONG_SEQ_MAX_HEAD_DIM:
        return LONG_SEQ_BLOCK
    return DEFAULT_BLOCK


def flash_blocks(seq_q: int, seq_k: int, head_dim: int, value_dim: int,
                 dropout_rate: float = 0.0, block_q: int | None = None,
                 block_k: int | None = None, mask=None):
    """((block_q, block_k) of the forward kernel, (block_q, block_k) of the
    two backward kernels) for one call, from what the call can see: the two
    sequence lengths, the key and value widths, the dropout rate and the
    caller's own request. The backward pair is `auto_block`'s, as ever. The
    forward pair is its own (FWD_BLOCK x FWD_BLOCK, see the constants)
    unless the caller named a block, which sets both pairs, or dropout is
    on: the mask of a score tile is regenerated from `_uid`, the tile's
    number in ITS tiling, so forward and backward must then tile alike.
    `o` and `lse` are whole arrays in HBM: the backward reads them in its
    own blocks whatever tiles wrote them. Either side still shrinks to a
    divisor of its sequence (`_pick_block`, `_pick_block_q`). `mask` is the
    call's selection mask or None: a call that has one holds a (block_q,
    block_k) tile of it beside K and V, and where nobody named a block its
    pairs are MASKED_FWD_BLOCKS and MASKED_BWD_BLOCKS (see the constants)."""
    fit = lambda pair: (_pick_block_q(seq_q, pair[0]),  # noqa: E731
                        _pick_block(seq_k, pair[1]))
    named = block_q is not None or block_k is not None
    if mask is not None and not named:
        wide = max(head_dim, value_dim) > LONG_SEQ_MAX_HEAD_DIM
        default = (DEFAULT_BLOCK, DEFAULT_BLOCK)
        backward = fit(default if wide else MASKED_BWD_BLOCKS)
        if wide or dropout_rate > 0.0:
            return backward, backward
        return fit(MASKED_FWD_BLOCKS), backward
    backward = (_pick_block_q(seq_q, auto_block(seq_q, block_q, head_dim)),
                _pick_block(seq_k, auto_block(seq_k, block_k, head_dim)))
    if (named or dropout_rate > 0.0
            or max(head_dim, value_dim) > FWD_MAX_HEAD_DIM):
        return backward, backward
    return fit((FWD_BLOCK, FWD_BLOCK)), backward


def _pick_block(seq: int, requested: int) -> int:
    block = min(requested, seq)
    while seq % block:
        block //= 2
    return max(block, 1)


def _pick_block_q(seq: int, requested: int) -> int:
    """Q-side block: the lse output's block is (1, 1, block_q), and Mosaic
    requires its last dim be 128-divisible OR equal to the array dim. Seqs
    with no >=128 power-of-2 divisor (e.g. a ragged 2016-token prefill
    chunk) run as ONE q block (equal-to-array is always legal); VMEM bounds
    that fallback, so past 4096 the caller must pad/truncate to a multiple
    of 128 instead."""
    block = _pick_block(seq, requested)
    if block % 128 and block != seq:
        if seq > 4096:
            raise ValueError(
                f"seq_q {seq} has no 128-divisible block and is too long "
                "for a single q block; pad or truncate the q sequence to a "
                "multiple of 128"
            )
        return seq
    return block


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying `like`'s varying-axes metadata, so the
    pallas_calls here are usable directly inside shard_map under the vma
    checker (jax 0.9) — e.g. as the per-chunk core of ring attention."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _uid(i, j, kb, num_j, num_kb):
    """Flat (q-block, kv-block) id shared by fwd and both bwd kernels so
    dropout masks regenerate identically: (i*num_j + j)*num_kb + kb."""
    return (i * num_j + j) * num_kb + kb


def _live(jb, kb, block_q, block_k, offset, causal):
    """Whether q-block jb sees any of kv-block kb under the causal mask —
    one definition shared by fwd/dq/dkv so they can never disagree about
    which blocks contribute (the dropout-uid lesson, applied to liveness)."""
    if not causal:
        return True
    return kb * block_k <= (jb + 1) * block_q - 1 + offset


def _last_live_kb(jb, block_q, block_k, offset):
    """Largest kv block _live for q-block jb (the same diagonal as _live,
    solved for kb), floored at 0: with seq_q > seq_k the first q rows see
    no kv at all and an unfloored clamp would index before the array."""
    return jnp.maximum(((jb + 1) * block_q - 1 + offset) // block_k, 0)


def _first_live_jb(kb, block_q, block_k, offset):
    """Smallest q block _live for kv-block kb (_live solved for jb)."""
    return jnp.maximum(kb * block_k - offset, 0) // block_q


def _seen(s, jb, kb, offset, causal, mask_ref):
    """A score tile with every pair the softmax may not see at BIG_NEG: the
    pairs past the (end-aligned) diagonal when `causal`, the pairs whose
    byte of the caller's selection mask is 0 when there is one. One
    definition shared by fwd/dq/dkv, as `_live` is; with neither, `s` as it
    came."""
    block_q, block_k = s.shape
    seen = None
    if causal:
        rows = jb * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = cols <= rows + offset
    if mask_ref is not None:
        chosen = mask_ref[0, :, :].astype(jnp.int32) != 0
        seen = chosen if seen is None else seen & chosen
    return s if seen is None else jnp.where(seen, s, BIG_NEG)


def _operand(mask) -> tuple:
    return () if mask is None else (mask,)


def _name(kernel: str, mask) -> str:
    """A kernel's `name=`: what a device trace and `metrics/hlo_cost.py`
    know it by. The three unmasked kernels' names are a vocabulary there
    (`KERNEL_SCOPES`: their time is read as the flash kernels', whatever
    layer calls them); a masked call's are not, so its time is its
    caller's layer scope's (`ops/dsa.py`: `L_dsa_attend`)."""
    return f"flash_mla_{kernel}" if mask is None else f"flash_masked_{kernel}"


def _mask_spec(mask, block_q, block_k, index) -> tuple:
    """The selection mask's BlockSpec, if there is a mask: one (block_q,
    block_k) tile of a batch row for all its heads. `index` maps a grid
    step to (batch row, query block, key block) and clamps a dead causal
    step as its kernel's K or Q index does, so that no tile is fetched to
    be ignored."""
    if mask is None:
        return ()
    return (pl.BlockSpec((1, block_q, block_k), index),)


# --------------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, seed_ref, *rest, scale, causal, offset,
                dropout_rate, num_qb, num_kb):
    # rest: [the selection mask's tile,] o, lse, then the scratch m, l, acc
    mask_ref = rest[0] if len(rest) == 6 else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest[-5:]
    # q_ref: (1, block_q, D) resident across the kv sweep; k_ref/v_ref:
    # (1, block_k, D) for this kv step. `offset` end-aligns the causal mask
    # when seq_q != seq_k (ops.attention.causal_mask semantics: query i
    # attends to kv positions <= i + (seq_k - seq_q)).
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    i = pl.program_id(0)
    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, BIG_NEG, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    live = _live(j, kb, block_q, block_k, offset, causal)

    @pl.when(live)
    def _step():
        q = q_ref[0, :, :].astype(jnp.float32) * scale
        k_blk = k_ref[0, :, :].astype(jnp.float32)
        v_blk = v_ref[0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        s = _seen(s, j, kb, offset, causal, mask_ref)
        m_i, l_i, acc = m_scr[...], l_scr[...], acc_scr[...]
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1, keepdims=True))
        if mask_ref is not None:
            # a row may have no chosen key in this tile, or in none: with
            # its maximum held above BIG_NEG the unseen pairs' exp
            # underflows to 0 (no unit mass for them, as below), on a
            # (block_q, 1) column and not a select a score; a row with no
            # key at all then keeps l == 0 and meets _finish's guard
            m_new = jnp.maximum(m_new, BIG_NEG * 0.5)
        if causal and offset < 0:
            # seq_q > seq_k end-aligned causal only (e.g. a single-q-block
            # fallback): rows with r + offset < 0 see NO key in any block,
            # so m_new == BIG_NEG and exp(s - m_new) would be 1, crediting
            # unit mass to invisible keys. Zero masked entries so those rows
            # keep l == 0 and hit the empty-row guard at _finish. With
            # offset >= 0 every row is valid in kv block 0, after which
            # exp(BIG_NEG - m_new) underflows to 0 on its own — keep the
            # select off the seq_q == seq_k training hot path.
            p = jnp.where(s <= BIG_NEG * 0.5, 0.0, jnp.exp(s - m_new))
        else:
            p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_i - m_new)
        # l accumulates the UNdropped mass (the softmax denominator);
        # dropout applies to the normalized probs, i.e. to acc only
        l_new = alpha * l_i + jnp.sum(p, axis=1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _dropout_keep(
                p.shape, seed_ref[0], _uid(i, j, kb, num_qb, num_kb),
                dropout_rate,
            )
            p_use = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        else:
            p_use = p
        acc_scr[...] = acc * alpha + jax.lax.dot_general(
            p_use, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(kb == num_kb - 1)
    def _finish():
        # Rows that saw no kv (causal with seq_q > seq_k under the
        # end-aligned mask) have l == 0: emit o = 0 instead of 0/0 = NaN so a
        # caller summing over all rows isn't gradient-poisoned, and lse = 0
        # (not m = BIG_NEG) so the backward's exp(s - lse) = exp(BIG_NEG)
        # underflows to 0 for those rows instead of exp(0) = 1.
        l_i = l_scr[...]
        empty = l_i <= 0.0
        safe_l = jnp.where(empty, 1.0, l_i)
        o_ref[0, :, :] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0, :] = jnp.where(
            empty, 0.0, m_scr[...] + jnp.log(safe_l)
        )[:, 0]


def _fwd(q3, k3, v3, seed, n_heads, n_kv, scale, causal, block_q, block_k,
         dropout_rate, interpret, mask=None):
    """q3: (B*N, S, D); k3: (B*Nkv, Skv, D); v3: (B*Nkv, Skv, Dv); mask:
    None or (B, S, Skv) int8. Returns (o (B*N, S, Dv), lse)."""
    bn, seq_q, d = q3.shape
    seq_k, dv = k3.shape[1], v3.shape[2]
    group = n_heads // n_kv
    num_qb = seq_q // block_q
    num_kb = seq_k // block_k

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, offset=seq_k - seq_q,
        dropout_rate=dropout_rate, num_qb=num_qb, num_kb=num_kb,
    )
    offset = seq_k - seq_q

    def kv_index(i, j, kb):
        # flattened q index i = b*n_heads + h -> kv index b*n_kv + h//group,
        # which is exactly i // group since group | n_heads. For causal,
        # clamp dead past-diagonal steps to the last live kv block — the
        # block index then repeats, so Mosaic elides the DMA that pl.when
        # in the kernel would otherwise fetch-and-ignore (~2x bandwidth on
        # the causal sweep).
        if causal:
            kb = jnp.minimum(kb, _last_live_kb(j, block_q, block_k, offset))
        return (i // group, kb, 0)

    return pl.pallas_call(
        kernel,
        grid=(bn, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, dv), kv_index),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            *_mask_spec(mask, block_q, block_k, lambda i, j, kb: (
                i // n_heads, j, kv_index(i, j, kb)[1])),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j, kb: (i, 0, j)),
        ],
        out_shape=[
            _sds((bn, seq_q, dv), q3.dtype, q3),
            _sds((bn, 1, seq_q), jnp.float32, q3),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=_params(mask),
        interpret=interpret,
        name=_name("fwd", mask),
    )(q3, k3, v3, seed, *_operand(mask))


# -------------------------------------------------------------------- backward


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seed_ref,
                   *rest, scale, causal, offset, dropout_rate, num_qb, num_kb):
    # rest: [the selection mask's tile,] dq, then the scratch
    mask_ref = rest[0] if len(rest) == 3 else None
    dq_ref, dq_scr = rest[-2:]
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    i = pl.program_id(0)
    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, dq_scr.dtype)

    live = _live(j, kb, block_q, block_k, offset, causal)

    @pl.when(live)
    def _step():
        q = q_ref[0, :, :].astype(jnp.float32) * scale
        do = do_ref[0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, :][:, None]
        delta = delta_ref[0, 0, :][:, None]
        k_blk = k_ref[0, :, :].astype(jnp.float32)
        v_blk = v_ref[0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = _seen(s, j, kb, offset, causal, mask_ref)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if dropout_rate > 0.0:
            keep = _dropout_keep(
                p.shape, seed_ref[0], _uid(i, j, kb, num_qb, num_kb),
                dropout_rate,
            )
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        ds = p * (dp - delta)
        dq_scr[...] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kb == num_kb - 1)
    def _finish():
        dq_ref[0, :, :] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seed_ref,
                    *rest, scale, causal, offset, dropout_rate, num_qb,
                    num_kb):
    # grid is (bn, kv-blocks, q-blocks): the q axis is the sequential carry;
    # rest: [the selection mask's tile,] dk, dv, then the two scratch
    mask_ref = rest[0] if len(rest) == 5 else None
    dk_ref, dv_ref, dk_scr, dv_scr = rest[-4:]
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    i = pl.program_id(0)
    kb = pl.program_id(1)
    jb = pl.program_id(2)

    @pl.when(jb == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, dk_scr.dtype)
        dv_scr[...] = jnp.zeros(dv_scr.shape, dv_scr.dtype)

    live = _live(jb, kb, block_q, block_k, offset, causal)

    @pl.when(live)
    def _step():
        q = q_ref[0, :, :].astype(jnp.float32) * scale
        do = do_ref[0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, :][:, None]
        delta = delta_ref[0, 0, :][:, None]
        k_blk = k_ref[0, :, :].astype(jnp.float32)
        v_blk = v_ref[0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = _seen(s, jb, kb, offset, causal, mask_ref)
        p = jnp.exp(s - lse)  # (bq, bk)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if dropout_rate > 0.0:
            keep = _dropout_keep(
                p.shape, seed_ref[0], _uid(i, jb, kb, num_qb, num_kb),
                dropout_rate,
            )
            p_v = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        else:
            p_v = p
        dv_scr[...] += jax.lax.dot_general(
            p_v, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        # q was pre-scaled, so ds^T @ q_scaled already carries softmax scale
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(jb == num_qb - 1)
    def _finish():
        dk_ref[0, :, :] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, :, :] = dv_scr[...].astype(dv_ref.dtype)


# --------------------------------------------- the heads' mean probabilities


def _probs_kernel(q_ref, k_ref, lse_ref, mask_ref, p_ref, *, scale, causal,
                  offset, n_heads):
    # grid (B, q-blocks, kv-blocks, heads), the heads the sequential carry:
    # p_ref, a float32 (1, block_q, block_k) tile, stays in VMEM over them
    block_q, block_k = p_ref.shape[1:]
    j = pl.program_id(1)
    kb = pl.program_id(2)
    h = pl.program_id(3)

    @pl.when(h == 0)
    def _init():
        p_ref[...] = jnp.zeros(p_ref.shape, p_ref.dtype)

    @pl.when(_live(j, kb, block_q, block_k, offset, causal))
    def _step():
        # the forward kernel's scores, operation for operation: lse is theirs
        q = q_ref[0, :, :].astype(jnp.float32) * scale
        s = jax.lax.dot_general(
            q, k_ref[0, :, :].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = _seen(s, j, kb, offset, causal, mask_ref)
        p_ref[0, :, :] += jnp.exp(s - lse_ref[0, 0, :][:, None])

    @pl.when(h == n_heads - 1)
    def _finish():
        p_ref[...] = p_ref[...] * (1.0 / n_heads)


def selected_probs(q, k, lse, mask, *, causal: bool = False,
                   scale: float | None = None, block_q: int | None = None,
                   block_k: int | None = None,
                   interpret: bool | None = None) -> jax.Array:
    """mean over the N heads of exp(q_h . k_g(h) * scale - lse_h) at the pairs
    `mask` (and `causal`) let through, 0 elsewhere: (B, Sq, Skv) float32,
    the heads' mean attention probability, from the `lse` that
    `flash_attention(..., mask=mask, return_lse=True)` returned for the same
    q, k, mask, causal and scale. Forward only: a fourth kernel beside the
    three, QK^T and the exponential without the values, never the (N, Sq,
    Skv) probabilities in HBM. No gradient is defined (the caller's use is a
    detached target)."""
    b, seq_q, n_heads, d = q.shape
    seq_k, n_kv = k.shape[1], k.shape[2]
    group = n_heads // n_kv
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    if scale is None:
        scale = d**-0.5
    block_q = _pick_block_q(seq_q, block_q or PROBS_BLOCKS[0])
    block_k = _pick_block(seq_k, block_k or PROBS_BLOCKS[1])
    offset = seq_k - seq_q
    q3, k3, lse3, mask = jax.lax.stop_gradient((
        q.transpose(0, 2, 1, 3).reshape(b * n_heads, seq_q, d),
        k.transpose(0, 2, 1, 3).reshape(b * n_kv, seq_k, d),
        lse.reshape(b * n_heads, 1, seq_q), mask.astype(jnp.int8)))

    def key_block(j, kb):  # clamped on a dead causal step, as `_fwd`'s
        if causal:
            kb = jnp.minimum(kb, _last_live_kb(j, block_q, block_k, offset))
        return kb

    return pl.pallas_call(
        functools.partial(_probs_kernel, scale=float(scale),
                          causal=bool(causal), offset=offset,
                          n_heads=n_heads),
        grid=(b, seq_q // block_q, seq_k // block_k, n_heads),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda i, j, kb, h: (i * n_heads + h, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kb, h: (
                i * n_kv + h // group, key_block(j, kb), 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda i, j, kb, h: (i * n_heads + h, 0, j)),
            pl.BlockSpec((1, block_q, block_k),
                         lambda i, j, kb, h: (i, j, key_block(j, kb))),
        ],
        out_specs=pl.BlockSpec((1, block_q, block_k),
                               lambda i, j, kb, h: (i, j, kb)),
        out_shape=_sds((b, seq_q, seq_k), jnp.float32, q3),
        compiler_params=_params(mask, (
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_masked_probs",
    )(q3, k3, lse3, mask)


# ------------------------------------------------------------------ public API


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11)
)
def _flash(q3, k3, v3, seed, mask, heads, scale, causal, blocks, dropout_rate,
           interpret, with_lse):
    # mask: None (no leaf: the call traces as it did before there was one)
    # or (B, Sq, Sk) int8
    o, lse = _fwd(q3, k3, v3, seed, heads[0], heads[1], scale, causal,
                  *blocks[0], dropout_rate, interpret, mask)
    return (o, lse) if with_lse else o


def _flash_fwd(q3, k3, v3, seed, mask, heads, scale, causal, blocks,
               dropout_rate, interpret, with_lse):
    o, lse = _fwd(q3, k3, v3, seed, heads[0], heads[1], scale, causal,
                  *blocks[0], dropout_rate, interpret, mask)
    # named in the kernel's own (B*N, S, Dv) layout: what the backward's
    # delta and the caller's o_proj both start from (FLASH_RESIDUALS)
    o = checkpoint_name(o, FLASH_RESIDUALS[0])
    lse = checkpoint_name(lse, FLASH_RESIDUALS[1])
    return ((o, lse) if with_lse else o), (q3, k3, v3, seed, mask, o, lse)


def _flash_bwd(heads, scale, causal, blocks, dropout_rate, interpret,
               with_lse, res, do):
    q3, k3, v3, seed, mask, o, lse = res
    if with_lse:  # lse is handed out for reading: its cotangent is dropped
        do = do[0]
    n_heads, n_kv = heads
    bn = q3.shape[0]
    seq_k = k3.shape[1]
    group = n_heads // n_kv

    if group > 1:  # materialize repeated kv for the backward pass
        bkv = k3.shape[0]
        rep = lambda x: jnp.repeat(  # noqa: E731
            x.reshape(bkv // n_kv, n_kv, seq_k, x.shape[2]), group, axis=1
        ).reshape(bn, seq_k, x.shape[2])
        k3r, v3r = rep(k3), rep(v3)
    else:
        k3r, v3r = k3, v3

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, None, :]

    dq, dk_r, dv_r = _bwd_chunk(
        q3, k3r, v3r, do, lse, delta, seed, scale=scale, causal=causal,
        block_q=blocks[1][0], block_k=blocks[1][1], dropout_rate=dropout_rate,
        interpret=interpret, mask=mask,
    )

    if group > 1:  # reduce repeated-head grads back to kv heads
        b = bn // n_heads
        fold = lambda x: x.reshape(  # noqa: E731
            b, n_kv, group, seq_k, x.shape[2]
        ).sum(axis=2).reshape(b * n_kv, seq_k, x.shape[2])
        dk_r, dv_r = fold(dk_r), fold(dv_r)
    # seed and mask are integer-typed: no cotangent
    return dq, dk_r.astype(k3.dtype), dv_r.astype(v3.dtype), None, None


def _bwd_chunk(q3, k3r, v3r, do, lse, delta, seed, *, scale, causal,
               block_q, block_k, dropout_rate, interpret, mask=None):
    """dq/dk/dv pallas sweeps for one (q, kv) pair with kv already repeated
    to q heads. Shared by the full backward above and the ring-flash
    backward (sharding/ring_attention.py), which runs it once per rotating
    kv chunk with the GLOBAL lse/delta. v3r and do are Dv wide, as dv is.
    `mask`: None or the forward's (B, S, Skv) int8 selection mask."""
    bn, seq_q, d = q3.shape
    n_heads = 1 if mask is None else bn // mask.shape[0]
    seq_k, dv = k3r.shape[1], v3r.shape[2]
    num_qb = seq_q // block_q
    num_kb = seq_k // block_k
    offset = seq_k - seq_q

    def kv_index_rep(i, j, kb):
        # clamp dead causal steps to the last live kv block (repeated block
        # index -> Mosaic skips the DMA); kv here is pre-repeated per q-head
        if causal:
            kb = jnp.minimum(kb, _last_live_kb(j, block_q, block_k, offset))
        return (i, kb, 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          offset=offset, dropout_rate=dropout_rate,
                          num_qb=num_qb, num_kb=num_kb),
        grid=(bn, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), kv_index_rep),
            pl.BlockSpec((1, block_k, dv), kv_index_rep),
            pl.BlockSpec((1, block_q, dv), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j, kb: (i, 0, j)),
            pl.BlockSpec((1, 1, block_q), lambda i, j, kb: (i, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            *_mask_spec(mask, block_q, block_k, lambda i, j, kb: (
                i // n_heads, j, kv_index_rep(i, j, kb)[1])),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
        out_shape=_sds(q3.shape, q3.dtype, q3),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_params(mask),
        interpret=interpret,
        name=_name("bwd_dq", mask),
    )(q3, k3r, v3r, do, lse, delta, seed, *_operand(mask))

    def q_index(i, kb, jb):
        # mirror clamp for the dkv sweep: q blocks before the diagonal are
        # dead — pin them to the first live q block so the DMA is elided
        if causal:
            jb = jnp.maximum(jb, _first_live_jb(kb, block_q, block_k, offset))
        return (i, jb, 0)

    def q_row_index(i, kb, jb):
        if causal:
            jb = jnp.maximum(jb, _first_live_jb(kb, block_q, block_k, offset))
        return (i, 0, jb)

    dk_r, dv_r = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          offset=offset, dropout_rate=dropout_rate,
                          num_qb=num_qb, num_kb=num_kb),
        grid=(bn, num_kb, num_qb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_k, d), lambda i, kb, jb: (i, kb, 0)),
            pl.BlockSpec((1, block_k, dv), lambda i, kb, jb: (i, kb, 0)),
            pl.BlockSpec((1, block_q, dv), q_index),
            pl.BlockSpec((1, 1, block_q), q_row_index),
            pl.BlockSpec((1, 1, block_q), q_row_index),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            *_mask_spec(mask, block_q, block_k, lambda i, kb, jb: (
                i // n_heads, q_index(i, kb, jb)[1], kb)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, kb, jb: (i, kb, 0)),
            pl.BlockSpec((1, block_k, dv), lambda i, kb, jb: (i, kb, 0)),
        ],
        out_shape=[
            _sds((bn, seq_k, d), k3r.dtype, k3r),
            _sds((bn, seq_k, dv), v3r.dtype, k3r),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        compiler_params=_params(mask),
        interpret=interpret,
        name=_name("bwd_dkv", mask),
    )(q3, k3r, v3r, do, lse, delta, seed, *_operand(mask))

    return dq, dk_r, dv_r


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    dropout_rate: float = 0.0,
    dropout_seed: jax.Array | int = 0,
    interpret: bool | None = None,
    mask: jax.Array | None = None,
    return_lse: bool = False,
):
    """Flash attention over BSNH tensors (drop-in for ops.dot_product_attention
    when there is no cache).

    q: (B, Sq, N, D); k: (B, Skv, Nkv, D); v: (B, Skv, Nkv, Dv) with
    N % Nkv == 0; returns (B, Sq, N, Dv). Dv may differ from D (latent
    attention decompressed: keys [nope | rope] 192 wide, values 128): each
    operand crosses HBM at its own width, nothing is padded there. In VMEM a
    width that is no multiple of the 128 lanes takes the next multiple's
    (192 sits in 256), what `flash_blocks` sizes both tile pairs by.
    dropout_rate > 0 applies attention-prob dropout INSIDE the kernel
    (masks regenerated from (dropout_seed, block id) in the backward — no
    (S, S) mask tensor ever exists); same Bernoulli semantics as the dense
    reference, different random stream.
    mask: a selection mask (B, Sq, Skv), one for all heads of a batch row,
    nonzero where a query may see a key (bool or int8; it crosses HBM as
    int8, a byte a pair, tile by tile); with `causal` a pair must pass
    both. A row with no key comes out 0. It takes no gradient. None (the
    default) is a Python branch: the call traces to what it was before
    there was a mask. A window, sinks, biases and segment ids are not taken.
    return_lse: also return the rows' log-sum-exp (B, N, Sq) float32, for
    reading only (it passes no gradient): exp(q . k * scale - lse) is a
    pair's probability (`selected_probs`).
    """
    b, seq_q, n_heads, d = q.shape
    seq_k, n_kv = k.shape[1], k.shape[2]
    if n_heads % n_kv:
        raise ValueError(f"q heads {n_heads} not a multiple of kv heads {n_kv}")
    if interpret is None:
        # interpret only on CPU (the test platform), so use_flash configs
        # are testable there; any other non-TPU backend still fails loudly
        # at Mosaic lowering rather than silently crawling through the
        # interpreter
        interpret = jax.devices()[0].platform == "cpu"
    if interpret and dropout_rate > 0.0:
        raise ValueError(
            "in-kernel dropout requires the hardware PRNG: interpret-mode "
            "pltpu.prng_random_bits is a zero stub, which would silently "
            "keep every element scaled by 1/(1-rate)"
        )
    if scale is None:
        scale = d**-0.5
    dv = v.shape[3]
    if mask is not None:
        if mask.shape != (b, seq_q, seq_k):
            raise ValueError(f"mask {mask.shape} is not (B, Sq, Skv) = "
                             f"{(b, seq_q, seq_k)}")
        mask = mask.astype(jnp.int8)
    blocks = flash_blocks(seq_q, seq_k, d, dv, dropout_rate, block_q, block_k,
                          mask)

    q3 = q.transpose(0, 2, 1, 3).reshape(b * n_heads, seq_q, d)
    k3 = k.transpose(0, 2, 1, 3).reshape(b * n_kv, seq_k, d)
    v3 = v.transpose(0, 2, 1, 3).reshape(b * n_kv, seq_k, dv)
    seed = jnp.asarray(dropout_seed, jnp.int32).reshape(1)
    o3 = _flash(
        q3, k3, v3, seed, mask, (n_heads, n_kv), float(scale), bool(causal),
        blocks, float(dropout_rate), interpret, bool(return_lse),
    )
    if return_lse:
        o3, lse = o3
        lse = jax.lax.stop_gradient(lse).reshape(b, n_heads, seq_q)
    out = o3.reshape(b, n_heads, seq_q, dv).transpose(0, 2, 1, 3)
    return (out, lse) if return_lse else out
