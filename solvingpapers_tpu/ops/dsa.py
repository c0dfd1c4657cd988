"""Attention over the keys a lightning indexer picks (DeepSeek Sparse
Attention, as `Keye-VL-2.0`'s `sa_config` words it): plain XLA, the masked
dense form.

    I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s])          s <= t
    S_t     = the keys of the `topk` largest I[t, 0..t] (all t + 1 of them
              while t < topk; ties go to the lower s)
    A_h[t]  = softmax over s in S_t of (q_h[t] . k_g(h)[s] * scale)
    out[t]  = concat_h sum_{s in S_t} A_h[t, s] v_g(h)[s]
    p_t     = stop_gradient(mean_h A_h[t, S_t])
    KL_t    = KL(p_t || softmax over s in S_t of I[t, s])

One S_t serves every head of a query. The selection takes no gradient, the
output none from I, and the KL none from p: what trains the indexer is the
KL alone, and the KL trains nothing else (the caller detaches qi, ki, w's
input).

How it runs. The selection is XLA's, the attention over it the flash
kernels' (`kernels/flash_attention.py`), the indexer's loss XLA's again.
  * `selection_mask` (no gradient, forward only): queries go in blocks of
    `Q_BLOCK`; a block's keys end at the next multiple of `KEY_STEP` past
    its last query (a static length, shared by the blocks of one such
    span). A block's index scores, `lax.top_k` over its row, and from the
    k-th value and the k-th index the row's set; the blocks are unrolled,
    so a device trace tells the scores (`L_dsa_index`) from the sort
    (`L_dsa_select`). A span that ends at or before `topk` selects every
    causal key and is not scored at all. The sets go into ONE (B, S, S)
    int8 array, a byte a pair, 1 where query t selected key s (256 MiB a
    layer at 16,384 tokens).
  * the attention: ONE `flash_attention` call a layer over the whole
    sequence with that array as its selection mask, 32 heads on 4: the
    scores, the float32 softmax and the values never leave VMEM, forward
    or backward. It hands out the rows' log-sum-exp, and
    `selected_probs`, a fourth kernel, takes the mean of the 32 heads'
    probabilities exp(s - lse) a pair: the KL's target, (B, S, S)
    float32, a transient of the layer. All under `L_dsa_attend`. On a
    mesh of more than one device (the caller hands it in) the two calls
    run inside one `shard_map` over the batch axes: a `pallas_call` is
    opaque to GSPMD.
  * `index_kl`: a `lax.map` over a span's blocks, each block
    rematerialised: the block's index scores again, their masked
    log-softmax, the KL against the block's rows of the target. One loop
    is ONE device event, under `L_dsa_loss`.
The mask, the forward kernel's output and log-sum-exp and the two sums
carry names (`DSA_RESIDUALS`) so that a layer's remat can keep them: then
neither the sort nor the attention's forward runs again in the layer's
second forward (the target does: its kernel is forward only and cheap).

`live_tile_fraction` counts the (query tile, key tile) pairs of the
backward kernels' tiling that hold a selected pair: what a kernel that
skipped the others could save (ROADMAP R-M13; no kernel here skips one).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from solvingpapers_tpu.kernels.flash_attention import (
    FLASH_RESIDUALS, flash_attention, flash_blocks, selected_probs,
)

# read at call time: tests shrink them with `monkeypatch.setattr`
Q_BLOCK = 512
KEY_STEP = 2048
NEG = -1e30
# what a layer's remat keeps of this mechanism: the selection mask (a byte a
# pair, 256 MiB a layer at 16,384 tokens), the forward kernel's output (S x
# heads x width in the compute dtype) and log-sum-exp, and the two sums
DSA_RESIDUALS = ("dsa_mask", *FLASH_RESIDUALS, "dsa_kl", "dsa_selected")


def spans(seq: int) -> list[tuple[int, int, int]]:
    """[(first query, end, queries a block)] of the spans a sequence is cut
    into: the span's keys are [0, end). A length the constants do not
    divide runs as one span, or a span as one block."""
    step = KEY_STEP if seq % KEY_STEP == 0 else seq
    block = Q_BLOCK if step % Q_BLOCK == 0 else step
    return [(start, start + step, block) for start in range(0, seq, step)]


def index_scores(qi, w, ki):
    """qi (B, Q, J, D), w (B, Q, J) float32, ki (B, K, D) -> I (B, Q, K)
    float32: the products accumulate in float32, ReLU, weights and the sum
    over the J heads are float32."""
    with jax.named_scope("L_dsa_index"):
        dots = jnp.einsum("bqjd,bkd->bjqk", qi, ki,
                          preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(dots) * jnp.swapaxes(w, 1, 2)[..., None],
                       axis=1)


def _causal(start, n_q: int, n_k: int):
    rows = start + jnp.arange(n_q)
    return jnp.arange(n_k)[None, :] <= rows[:, None]


def select(scores, causal, topk: int):
    """The mask (B, Q, K) of each row's `topk` largest causal scores; ties
    go to the lower key. `lax.top_k` puts the lower index first among
    equals, so a row's set is every score above its k-th value and, of
    those equal to it, the ones at or before the k-th's own place."""
    with jax.named_scope("L_dsa_select"):
        masked = jnp.where(causal, scores, NEG)
        vals, idx = jax.lax.top_k(masked, topk)
        kth, at = vals[..., -1:], idx[..., -1:]
        cols = jnp.arange(scores.shape[-1])
        return causal & ((masked > kth) | ((masked == kth) & (cols <= at)))


def selection_mask(qi, w, ki, topk: int):
    """(B, S, S) int8: 1 where query t selected key s. The rows of a span of
    `spans(S)` are 0 past the span's end; a span in which every causal key
    is selected holds the causal mask itself. Forward only: nothing here
    takes or passes a gradient."""
    qi, w, ki = jax.lax.stop_gradient((qi, w, ki))
    b, s = qi.shape[:2]
    rows = []
    for start, end, block in spans(s):
        if end <= topk:
            with jax.named_scope("L_dsa_select"):
                rows.append(jnp.broadcast_to(
                    _causal(start, end - start, s), (b, end - start, s)))
            continue
        for lo in range(start, end, block):
            with jax.named_scope("L_dsa_index"):
                scores = index_scores(qi[:, lo:lo + block],
                                      w[:, lo:lo + block], ki[:, :end])
            chosen = select(scores, _causal(lo, block, end), topk)
            with jax.named_scope("L_dsa_select"):
                rows.append(jnp.pad(chosen, ((0, 0), (0, 0), (0, s - end))))
    with jax.named_scope("L_dsa_select"):
        return checkpoint_name(
            jnp.concatenate(rows, 1).astype(jnp.int8), "dsa_mask")


def live_tile_fraction(mask, tile_q: int, tile_k: int):
    """Of the causal (tile_q, tile_k) tiles of a selection mask (B, S, S),
    the share that holds a selected pair, a batch row: (B,). A selected
    pair is a causal one, so only causal tiles can hold any."""
    b, s, _ = mask.shape
    with jax.named_scope("L_dsa_select"):
        held = jnp.max(mask.reshape(b, s // tile_q, tile_q, s // tile_k,
                                    tile_k), axis=(2, 4)) > 0
        first = jnp.arange(s // tile_k) * tile_k  # a key tile's first key
        last = (jnp.arange(s // tile_q) + 1) * tile_q - 1
        causal = jnp.sum(first[None, :] <= last[:, None])
        return jnp.sum(held, axis=(1, 2), dtype=jnp.float32) / causal


def _kl_block(qi, w, mask, target, ki):
    """One block of queries against a span's keys: qi (B, Q, J, D), w (B,
    Q, J), mask and target (B, Q, K), ki (B, K, D) float32 -> the block's
    sum of KL_t a batch row, (B,)."""
    scores = index_scores(qi, w, ki.astype(qi.dtype))
    with jax.named_scope("L_dsa_loss"):
        mask = mask > 0
        log_q = jax.nn.log_softmax(jnp.where(mask, scores, NEG), axis=-1)
        live = mask & (target > 0)
        log_p = jnp.log(jnp.where(live, target, 1.0))
        return jnp.sum(jnp.where(live, target * (log_p - log_q), 0.0),
                       axis=(1, 2))


def index_kl(qi, w, ki, mask, target):
    """sum over a batch row's queries of KL(p_t || softmax over S_t of I[t]),
    (B,): qi (B, S, J, D) in the compute dtype, w (B, S, J) float32, ki (B, S, D) float32
    (cast to the compute dtype a block, so that its gradient adds up over
    the blocks in float32), mask (B, S, S) int8, target (B, S, S) float32,
    p_t where selected (detached here)."""
    b, s = qi.shape[:2]
    target = jax.lax.stop_gradient(target)
    kl = 0.0
    with jax.named_scope("L_dsa_loss"):
        for start, end, block in spans(s):
            n_blocks = (end - start) // block
            cut = lambda a: jnp.moveaxis(a[:, start:end].reshape(  # noqa: E731
                (b, n_blocks, block) + a.shape[2:]), 1, 0)
            one = lambda xs, ki=ki[:, :end]: jax.checkpoint(  # noqa: E731
                _kl_block)(*xs, ki)
            kl = kl + jnp.sum(jax.lax.map(one, (
                cut(qi), cut(w), cut(mask[:, :, :end]),
                cut(target[:, :, :end]))), axis=0)
    return kl


def _attend(q, k, v, mask, *, scale: float):
    """(attention over the selected keys, the heads' mean probability a
    pair): the masked flash call and the kernel that reads its
    log-sum-exp."""
    out, lse = flash_attention(q, k, v, causal=True, scale=scale, mask=mask,
                               return_lse=True)
    return out, selected_probs(q, k, lse, mask, causal=True, scale=scale)


def selected_attention(q, k, v, qi, ki, w, *, topk: int, scale: float,
                       mesh=None):
    """q (B, S, N, W) in the compute dtype; k, v (B, S, G, W), ki (B, S, D)
    float32 (k and v are cast to the compute dtype once, here); qi (B, S, J,
    D) in the compute dtype; w (B, S, J) float32. Returns (out (B, S, N, W),
    sum over all queries of KL_t, count of selected pairs, the share of the
    backward kernels' causal tiles that hold a selected pair). `mesh`: the
    caller's device mesh, if it has one of more than one device: a
    `pallas_call` is opaque to GSPMD, so the kernels then run inside a
    `shard_map` over the batch axes (as `kernels/sharded_flash.py` runs the
    unmasked call; every head on every device of a `model` axis, because
    the mask and the probabilities are all heads')."""
    s = q.shape[1]
    mask = selection_mask(qi, w, ki.astype(qi.dtype), topk)
    attend = functools.partial(_attend, scale=scale)
    if mesh is not None and mesh.devices.size > 1:
        rows = jax.sharding.PartitionSpec(("data", "fsdp"))
        attend = jax.shard_map(
            attend, mesh=mesh, in_specs=(rows,) * 4, out_specs=(rows, rows),
            check_vma=False)
    with jax.named_scope("L_dsa_attend"):
        out, target = attend(q, k.astype(q.dtype), v.astype(q.dtype), mask)
    kl = index_kl(qi, w, ki, mask, target)
    live = live_tile_fraction(mask, *flash_blocks(
        s, s, q.shape[3], v.shape[3], mask=mask)[1])
    with jax.named_scope("L_dsa_loss"):
        # the three a batch row up to here, and ONE sum over the rows: on a
        # mesh that shards the batch that is one collective, none inside
        # the loops
        count = jnp.sum(mask, axis=(1, 2), dtype=jnp.float32)
        kl, count, live = jnp.sum(jnp.stack([kl, count, live]), axis=1)
        return (out, checkpoint_name(kl, "dsa_kl"),
                checkpoint_name(count, "dsa_selected"), live / q.shape[0])
