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

How it runs. Queries go in blocks of `Q_BLOCK`; a block's keys end at the
next multiple of `KEY_STEP` past its last query (a static length: the
blocks of one such span share it, so causality skips 44% of a 16,384-token
square where a tile-exact skip would skip 50%).
  * `selection_masks` (no gradient, forward only): a block's index scores,
    `lax.top_k` over its row, and from the k-th value and the k-th index the
    boolean mask of the row's set; the blocks are unrolled, so a device
    trace tells the scores (`L_dsa_index`) from the sort (`L_dsa_select`).
    A span that ends at or before `topk` selects every causal key and is
    not scored at all.
  * `selected_attention`: a `lax.map` over a span's blocks, each block
    rematerialised: 32-on-4 scores against the span's keys, the mask, a
    float32 softmax, the values, the heads' mean, and the block's index
    scores again for the KL. One loop is ONE device event, under
    `L_dsa_attend` where it is called.
The masks, the output and the two sums carry names (`DSA_RESIDUALS`) so
that a layer's remat can keep them: then neither the sort nor the
attention's forward runs again in the layer's second forward.

A kernel that skipped the tiles no query of a block selected would need the
mask (or the indices) a tile and a count of live tiles; none here does
(ROADMAP R-M13).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# read at call time: tests shrink them with `monkeypatch.setattr`
Q_BLOCK = 512
KEY_STEP = 2048
NEG = -1e30
# what a layer's remat keeps of this mechanism: the boolean masks (140 MiB a
# layer at 16,384 tokens), the attention's output (S x heads x width in the
# compute dtype) and the two sums
DSA_RESIDUALS = ("dsa_mask", "dsa_ctx", "dsa_kl", "dsa_selected")


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


def selection_masks(qi, w, ki, topk: int) -> list:
    """A mask (blocks, B, Q, K) for every span of `spans(S)`, None for a span
    in which every causal key is selected. Forward only: nothing here takes
    or passes a gradient."""
    qi, w, ki = jax.lax.stop_gradient((qi, w, ki))
    b, s = qi.shape[:2]
    out = []
    for start, end, block in spans(s):
        if end <= topk:
            out.append(None)
            continue
        masks = []
        for lo in range(start, end, block):
            with jax.named_scope("L_dsa_index"):
                rows = (qi[:, lo:lo + block], w[:, lo:lo + block])
                scores = index_scores(*rows, ki[:, :end])
            masks.append(select(scores, _causal(lo, block, end), topk))
        with jax.named_scope("L_dsa_select"):
            out.append(checkpoint_name(jnp.stack(masks), "dsa_mask"))
    return out


def _attend_block(q, qi, w, mask, k, v, ki, scale):
    """One block of queries against a span's keys. q (B, Q, N, W), k, v (B,
    K, G, W), mask (B, Q, K) -> (out (B, Q, N, W) in q's dtype, the block's
    sum of KL_t, its count of selected pairs)."""
    b, n_q, n, width = q.shape
    g = k.shape[2]
    dt = q.dtype
    with jax.named_scope("L_dsa_attend"):
        sc = jnp.einsum(
            "bqgrw,bkgw->bgrqk", q.reshape(b, n_q, g, n // g, width),
            k.astype(dt), preferred_element_type=jnp.float32)
        sc = jnp.where(mask[:, None, None], sc * scale, NEG)
        p = jax.nn.softmax(sc, axis=-1)
        out = jnp.einsum("bgrqk,bkgw->bqgrw", p.astype(dt), v.astype(dt),
                         preferred_element_type=jnp.float32)
        out = out.reshape(b, n_q, n, width).astype(dt)
        # the heads' probabilities, summed, L1-normalised: the KL's target
        target = jax.lax.stop_gradient(jnp.mean(p, axis=(1, 2)))
    scores = index_scores(qi, w, ki.astype(qi.dtype))
    with jax.named_scope("L_dsa_loss"):
        log_q = jax.nn.log_softmax(jnp.where(mask, scores, NEG), axis=-1)
        live = mask & (target > 0)
        log_p = jnp.log(jnp.where(live, target, 1.0))
        kl = jnp.sum(jnp.where(live, target * (log_p - log_q), 0.0))
        return out, kl, jnp.sum(mask, dtype=jnp.float32)


def selected_attention(q, k, v, qi, ki, w, *, topk: int, scale: float):
    """q (B, S, N, W) in the compute dtype; k, v (B, S, G, W), ki (B, S, D)
    float32 (cast to the compute dtype a block, so that their gradients add
    up over the blocks in float32); qi (B, S, J, D) in the compute dtype; w
    (B, S, J) float32. Returns (out (B, S, N, W), sum over all queries of
    KL_t, count of selected pairs)."""
    b, s = q.shape[:2]
    masks = selection_masks(qi, w, ki.astype(qi.dtype), topk)
    outs, kl, count = [], 0.0, 0.0
    for (start, end, block), mask in zip(spans(s), masks):
        n_blocks = (end - start) // block
        cut = lambda a: jnp.moveaxis(  # noqa: E731
            a[:, start:end].reshape((b, n_blocks, block) + a.shape[2:]), 1, 0)

        with jax.named_scope("L_dsa_attend"):
            keys = (k[:, :end], v[:, :end], ki[:, :end])

            def one(q_, qi_, w_, m, keys=keys, end=end, block=block,
                    causal_only=mask is None):
                if causal_only:  # every causal key selected; m: first query
                    m = jnp.broadcast_to(_causal(m, block, end),
                                         (b, block, end))
                return _attend_block(q_, qi_, w_, m, *keys, scale)

            xs = (cut(q), cut(qi), cut(w),
                  start + block * jnp.arange(n_blocks) if mask is None
                  else mask)
            o, kl_b, n_b = jax.lax.map(
                lambda xs, one=one: jax.checkpoint(one)(*xs), xs)
            outs.append(jnp.moveaxis(o, 0, 1).reshape(
                (b, end - start) + o.shape[3:]))
        with jax.named_scope("L_dsa_loss"):
            kl, count = kl + jnp.sum(kl_b), count + jnp.sum(n_b)
    with jax.named_scope("L_dsa_attend"):
        out = checkpoint_name(jnp.concatenate(outs, 1), "dsa_ctx")
    with jax.named_scope("L_dsa_loss"):
        return (out, checkpoint_name(kl, "dsa_kl"),
                checkpoint_name(count, "dsa_selected"))
