"""KV cache pools for the serving engine: contiguous per-slot lanes
(`KVSlotPool`) and block-paged cache blocks (`PagedKVPool`).

Continuous batching needs slot-granular cache reuse: when one sequence
finishes, its cache storage must be handed to the next queued request
immediately, without waiting for the rest of the batch. `KVSlotPool`
applies that at lane granularity — one `max_seq` lane per slot, HBM
booked for the worst case. `PagedKVPool` (second half of this module)
is the full vLLM-PagedAttention layout: one physical pool of fixed-size
KV pages, per-slot page tables, and refcounted zero-copy prefix sharing
(`ServeConfig.paged`); the lane pool remains the default.

The pool is carved out of the existing cache machinery unchanged: the
pooled pytrees come from ``model.init_caches(n_slots, max_len)``
(`infer/cache.py` KVCache / LatentCache — any family works), so the batch
dimension IS the slot dimension. Lane extraction/insertion are pytree
``dynamic_slice`` helpers meant to be traced inside the engine's jitted
programs (`serve/engine.py`); acquire/release bookkeeping is host-side.

Stale-data contract: a freed lane is NOT zeroed. Reuse is safe because
(a) prefill overwrites slots ``[0, P)`` of the lane before any attention
over it, and (b) decode masks with ``kv_index <= position`` (the cache
masking contract of `infer/cache.py`), so slots beyond the current length
never contribute — and every stale value is finite (written by a real
forward), so masked-softmax zeros annihilate it exactly.

Prefix reuse (`serve/prefix_cache.py`): `splice_prefix` copies a cached
batch-1 KV segment into a lane's leading slots before the suffix prefill
(copy-on-acquire — the lane owns its copy, so tree eviction can never
corrupt an in-flight stream), and `extract_prefix` snapshots a freshly
prefilled prompt span back out for the radix tree to keep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from solvingpapers_tpu.ops.quant import (
    dequantize,
    dequantize_tree,
    quantize,
    quantize_tree,
    scale_shape,
)


# ======================================================================
# Quantized storage (`ServeConfig.kv_quant`, ops/quant.py)
# ======================================================================
#
# Both pools can hold their cache bytes as symmetric int8 with per-block
# absmax scales instead of the model's compute dtype: `QuantStore`
# replaces the plain cache pytree as the pool's device payload, and the
# jitted serving programs DEQUANTIZE ON READ (the gather/extract sites
# materialize the familiar compute-dtype lane view, so the models serve
# unmodified) and QUANTIZE ON WRITE (the store/scatter sites requantize
# exactly the blocks/pages the program wrote — untouched blocks are
# never re-read-modify-written, and within a touched block committed
# positions outside the written window re-encode from their own
# f32-dequantized codes rather than the lossy compute-dtype lane view,
# so old entries cannot drift step to step on any compute dtype; see
# ops/quant.py's fixed-point note).
#
# Exact traffic shares the same store: `exact` is a small sidecar lane
# pool in the ORIGINAL dtype ((kv_exact_lanes + 1) lanes; lane 0 is a
# trash lane, mirroring the paged pool's trash page). A slot serving a
# `SamplingParams.kv_exact` request carries a nonzero exact-lane index
# on the packed control rows: reads substitute its full-precision lane
# for the dequantized view (`jnp.where` per slot — one compiled program
# for mixed exact/quantized batches), writes land in BOTH (the int8
# shadow is harmless; the exact lane is authoritative), and quantized
# slots' exact-lane writes fall into trash lane 0. Exact streams are
# byte-identical to the unquantized engine's because the values the
# model ever reads for them are bit-equal.


@struct.dataclass
class QuantStore:
    """Quantized pool payload: int8 cache pytree + f32 scale sidecar
    (same tree structure, `ops.quant.scale_shape` leaves) + the optional
    exact-lane sidecar. `block`/`dtype` are static aux data (part of the
    jit signature): the time-block length scales tile and the compute
    dtype dequantized views materialize in."""

    q: object
    scale: object
    exact: object
    block: int = struct.field(pytree_node=False)
    dtype: object = struct.field(pytree_node=False)


@struct.dataclass
class QuantSegment:
    """Quantized prefix-cache segment (lane pools): the batch-1 int8 +
    scale slices `extract_prefix` snapshots and `splice_prefix` writes
    back. Cached prefixes stay quantized at rest — the radix tree's
    byte budget buys ~2x the cached tokens."""

    q: object
    scale: object
    block: int = struct.field(pytree_node=False)

    @property
    def length(self) -> int:
        return jax.tree_util.tree_leaves(self.q)[0].shape[1]

    @property
    def nbytes(self) -> int:
        return sum(
            leaf.size * leaf.dtype.itemsize
            for tree in (self.q, self.scale)
            for leaf in jax.tree_util.tree_leaves(tree)
        )

    def time_slice(self, start: int, end: int) -> "QuantSegment":
        """Token-axis sub-segment [start, end); bounds must be block
        multiples (they are page multiples, and the engine pins
        page % block == 0)."""
        if start % self.block or end % self.block:
            raise ValueError(
                f"quantized segment slice [{start}, {end}) is not "
                f"aligned to the quant block {self.block}"
            )
        b = self.block
        return QuantSegment(
            q=jax.tree_util.tree_map(lambda a: a[:, start:end], self.q),
            scale=jax.tree_util.tree_map(
                lambda a: a[:, start // b:end // b], self.scale
            ),
            block=b,
        )


def _leaf_dtype(caches):
    """The single compute dtype of a cache pytree (quantization keys its
    dequantized view on ONE static dtype; mixed-dtype caches would need
    a per-leaf aux tree nothing in the repo produces)."""
    dtypes = {leaf.dtype for leaf in jax.tree_util.tree_leaves(caches)}
    if len(dtypes) != 1:
        raise ValueError(
            f"kv_quant needs a single cache dtype, got {sorted(map(str, dtypes))}"
        )
    return dtypes.pop()


def make_quant_store(model, batch: int, time: int, block: int,
                     exact_lanes: int = 0,
                     exact_time: int | None = None) -> QuantStore:
    """Build a pool's quantized payload: int8 zeros + zero scales shaped
    like ``model.init_caches(batch, time)``, plus the exact-lane sidecar
    (``exact_lanes + 1`` full-precision lanes of `exact_time`; lane 0 is
    the trash lane). Zero scales dequantize to exact zeros, so a fresh
    quantized pool reads back bit-identical to a fresh plain one."""
    base = model.init_caches(batch, time)
    dtype = _leaf_dtype(base)
    q = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.int8), base
    )
    scale = jax.tree_util.tree_map(
        lambda a: jnp.zeros(scale_shape(a.shape, block), jnp.float32), base
    )
    exact = None
    if exact_lanes > 0:
        exact = model.init_caches(exact_lanes + 1, exact_time or time)
    return QuantStore(q=q, scale=scale, exact=exact, block=block,
                      dtype=dtype)


def quant_pool_bytes(store: QuantStore) -> tuple[int, int, int, int]:
    """(payload+scale bytes, scale bytes, exact sidecar bytes, baseline
    bytes) — the analytic byte split the HBM ledger and the kv_quant
    gauges report. `baseline` is what the same pool would hold
    unquantized (int8 element count x the compute dtype's width)."""
    itemsize = np.dtype(store.dtype).itemsize
    q_bytes = sum(leaf.size for leaf in jax.tree_util.tree_leaves(store.q))
    s_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(store.scale)
    )
    e_bytes = 0
    if store.exact is not None:
        e_bytes = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(store.exact)
        )
    return q_bytes + s_bytes, s_bytes, e_bytes, q_bytes * itemsize


# --------------------------------------------------- traced read helpers


def _exact_select1(lane, store: QuantStore, eidx):
    """Batch-1 exact override: substitute the `eidx` exact lane when
    eidx > 0 (a kv_exact slot); eidx == 0 keeps the dequantized view."""
    if store.exact is None:
        return lane
    ex = extract_lane(store.exact, eidx)
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(eidx > 0, b, a), lane, ex
    )


def _exact_select(lanes, store: QuantStore, eidx_row):
    """Batched exact override for the (S, ...) lane view."""
    if store.exact is None:
        return lanes

    def sel(a, ex_pool):
        ex = ex_pool[eidx_row]
        mask = (eidx_row > 0).reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(mask, ex, a)

    return jax.tree_util.tree_map(sel, lanes, store.exact)


def quant_lane_view(store: QuantStore, slot, eidx):
    """Batch-1 compute-dtype lane view of a quantized LANE pool slot
    (traced) — `extract_lane` + dequantize + the exact override."""
    lane = dequantize_tree(
        extract_lane(store.q, slot), extract_lane(store.scale, slot),
        store.dtype,
    )
    return _exact_select1(lane, store, eidx)


def quant_lanes_view(store: QuantStore, eidx_row):
    """All-slot (S, max_len, ...) view of a quantized lane pool (traced)
    — what the decode programs carry through their scan."""
    lanes = dequantize_tree(store.q, store.scale, store.dtype)
    return _exact_select(lanes, store, eidx_row)


def quant_gather_lane(store: QuantStore, row, eidx):
    """Batch-1 lane view of a quantized PAGE pool: gather the int8
    pages and their per-page scale rows through the same page-table row,
    dequantize, apply the exact override (traced)."""

    def g(qleaf, sleaf):
        pages = qleaf[row].astype(jnp.float32)   # (PPL, page, ...)
        sc = sleaf[row][..., None]               # (PPL, 1[, H], 1)
        x = (pages * sc).astype(store.dtype)
        ppl, page = x.shape[:2]
        return x.reshape((1, ppl * page) + x.shape[2:])

    lane = jax.tree_util.tree_map(g, store.q, store.scale)
    return _exact_select1(lane, store, eidx)


def quant_gather_lanes(store: QuantStore, table, eidx_row):
    """(S, max_len, ...) view of a quantized page pool through the
    (S, pages_per_lane) page table (traced). The int8 gather moves half
    the bytes of the plain pool's — the paged full-lane-gather tax
    shrinks with the payload."""

    def g(qleaf, sleaf):
        pages = qleaf[table].astype(jnp.float32)  # (S, PPL, page, ...)
        sc = sleaf[table][..., None]              # (S, PPL, 1[, H], 1)
        x = (pages * sc).astype(store.dtype)
        s, ppl, page = x.shape[:3]
        return x.reshape((s, ppl * page) + x.shape[3:])

    lanes = jax.tree_util.tree_map(g, store.q, store.scale)
    return _exact_select(lanes, store, eidx_row)


# -------------------------------------------------- traced write helpers


def quant_store_lane(store: QuantStore, lane, slot, eidx,
                     t0: int, t1: int, hi=None) -> QuantStore:
    """Quantize-on-write for a batch-1 lane (the prefill store site):
    requantize ONLY the written span [t0, t1) (static; `t0` block-aligned
    — prefix-hit starts are page multiples and page % block == 0, `t1`
    rounds up to the block) into the slot's int8 + scale rows, and mirror
    the full-precision lane into the exact sidecar at `eidx` (trash lane
    0 for quantized slots). Blocks below t0 hold spliced prefix data the
    prefill never touched — not rewriting them is what keeps the
    quantized prefix cache's contents stable under reuse. `hi` (traced)
    is the end of the REAL tokens: prompts are right-padded, and a
    padding activation sharing the tail block would otherwise inflate
    its absmax and coarsen the last committed tokens' codes — positions
    past `hi` are zeroed before quantizing instead (they sit beyond
    `attend_len`, are never attended, and decode overwrites them; zeros
    can never widen a scale)."""
    b = store.block
    t_max = jax.tree_util.tree_leaves(store.q)[0].shape[1]
    if t0 % b:
        raise ValueError(f"write start {t0} is not a multiple of the "
                         f"quant block {b}")
    t1 = min(-(-t1 // b) * b, t_max)
    span = jax.tree_util.tree_map(lambda a: a[:, t0:t1], lane)
    if hi is not None:
        tcol = jnp.arange(t0, t1)

        def _zero_pads(a):
            m = (tcol < hi).reshape((1, t1 - t0) + (1,) * (a.ndim - 2))
            return jnp.where(m, a, jnp.zeros_like(a))

        span = jax.tree_util.tree_map(_zero_pads, span)
    q_span, s_span = quantize_tree(span, b)
    q = jax.tree_util.tree_map(
        lambda a, s: jax.lax.dynamic_update_slice(
            a, s, (slot, t0) + (0,) * (a.ndim - 2)),
        store.q, q_span,
    )
    scale = jax.tree_util.tree_map(
        lambda a, s: jax.lax.dynamic_update_slice(
            a, s, (slot, t0 // b) + (0,) * (a.ndim - 2)),
        store.scale, s_span,
    )
    exact = store.exact
    if exact is not None:
        exact = store_lane(exact, lane, eidx)
    return store.replace(q=q, scale=scale, exact=exact)


def quant_store_written(store: QuantStore, lanes, pos0, span: int,
                        eidx_row, hi=None,
                        tail_garbage: bool = False) -> QuantStore:
    """Quantize-on-write for the decode programs' (S, max_len, ...) lane
    view: each slot wrote positions ``[pos0[s], pos0[s] + span)`` (span
    static — `decode_block`, or rounds x chunk for speculation), which
    touches a static number of quant blocks per slot; requantize exactly
    those blocks and leave the rest of the pool's payload byte-identical
    (clipped duplicate windows rewrite the same block with the same
    content — idempotent). Within a rewritten block, only positions
    inside the written window take the compute-dtype lane view; the rest
    re-encode from their OWN f32-dequantized codes. That merge matters
    twice over: (1) on bf16 pools the lane view is a lossy cast, and
    requantizing committed entries through it would walk their codes
    step to step (ops/quant.py's fixed-point note only holds in f32);
    (2) when the caller knows the lane past a per-slot `hi` holds
    REJECTED-draft garbage (`tail_garbage=True`, the speculative
    write-back — `hi` is the device-committed end, default pos0 + span),
    excluding it keeps a garbage outlier from inflating the block absmax
    and permanently coarsening the committed entries that share the
    block. On f32 pools with a trustworthy tail the merge reproduces
    the lane bit-for-bit, so it is skipped at trace time (dtype and
    `tail_garbage` are static) and the plain-decode f32 write site keeps
    its pre-merge cost. The exact sidecar takes each slot's full lane at
    its `eidx` (duplicate trash-lane writes are garbage-on-garbage)."""
    b = store.block
    t_max = jax.tree_util.tree_leaves(store.q)[0].shape[1]
    nb = t_max // b
    n_slots = pos0.shape[0]
    rows = jnp.arange(n_slots)
    q_tree, s_tree = store.q, store.scale
    end = (pos0 + span) if hi is None else hi
    merge = tail_garbage or jnp.dtype(store.dtype) != jnp.float32
    for w in range((span - 1) // b + 2):
        bidx = jnp.clip((pos0 + w * b) // b, 0, nb - 1)  # (S,)
        tcol = bidx[:, None] * b + jnp.arange(b)[None, :]  # (S, b)

        def one(qleaf, sleaf, lane_leaf, bidx=bidx, tcol=tcol):
            vals = jax.vmap(
                lambda lane, i: jax.lax.dynamic_slice_in_dim(
                    lane, i * b, b, axis=0)
            )(lane_leaf, bidx)                       # (S, b, ...)
            if merge:
                old_q = jax.vmap(
                    lambda qrow, i: jax.lax.dynamic_slice_in_dim(
                        qrow, i * b, b, axis=0)
                )(qleaf, bidx)                       # (S, b, ...) int8
                old = dequantize(old_q, sleaf[rows, bidx][:, None],
                                 jnp.float32)
                wr = ((tcol >= pos0[:, None])
                      & (tcol < end[:, None]))
                wr = wr.reshape(wr.shape + (1,) * (vals.ndim - 2))
                vals = jnp.where(wr, vals.astype(jnp.float32), old)
            qv, sv = quantize(vals, b)               # scale (S, 1[, H])
            qleaf = qleaf.at[rows[:, None], tcol].set(qv)
            sleaf = sleaf.at[rows, bidx].set(
                jnp.squeeze(sv, axis=1))
            return qleaf, sleaf

        pairs = [one(ql, sl, ll) for ql, sl, ll in zip(
            jax.tree_util.tree_leaves(q_tree),
            jax.tree_util.tree_leaves(s_tree),
            jax.tree_util.tree_leaves(lanes))]
        treedef = jax.tree_util.tree_structure(q_tree)
        q_tree = jax.tree_util.tree_unflatten(
            treedef, [q for q, _ in pairs])
        s_tree = jax.tree_util.tree_unflatten(
            treedef, [s for _, s in pairs])
    return quant_store_exact_lanes(
        store.replace(q=q_tree, scale=s_tree), lanes, eidx_row)


def quant_scatter_lane_pages(store: QuantStore, lane, row,
                             start_page: int, eidx, hi=None) -> QuantStore:
    """`scatter_lane_pages` for a quantized page pool (the paged prefill
    write site): quantize the batch-1 lane's pages [start_page:] —
    one absmax scale row per (page, head) — and scatter payload + scales
    to the physical ids; mirror the lane into the exact sidecar. `hi`
    (traced) zeroes right-padding positions before quantizing, exactly
    as `quant_store_lane` documents — a pad activation must not widen
    the scale of the page holding the last real tokens."""
    ids = row[start_page:]

    def sc(qleaf, sleaf, lane_leaf):
        page = qleaf.shape[1]
        ppl = row.shape[0]
        pages = lane_leaf.reshape((ppl, page) + lane_leaf.shape[2:])
        pages = pages[start_page:]
        if hi is not None:
            tcol = (start_page * page
                    + jnp.arange((ppl - start_page) * page)).reshape(
                        (ppl - start_page, page))
            m = (tcol < hi).reshape(tcol.shape + (1,) * (pages.ndim - 2))
            pages = jnp.where(m, pages, jnp.zeros_like(pages))
        qv, sv = quantize(pages, page)
        return qleaf.at[ids].set(qv), sleaf.at[ids].set(sv)

    pairs = [sc(ql, sl, ll) for ql, sl, ll in zip(
        jax.tree_util.tree_leaves(store.q),
        jax.tree_util.tree_leaves(store.scale),
        jax.tree_util.tree_leaves(lane))]
    treedef = jax.tree_util.tree_structure(store.q)
    q = jax.tree_util.tree_unflatten(treedef, [a for a, _ in pairs])
    scale = jax.tree_util.tree_unflatten(treedef, [b for _, b in pairs])
    exact = store.exact
    if exact is not None:
        exact = store_lane(exact, lane, eidx)
    return store.replace(q=q, scale=scale, exact=exact)


def quant_scatter_written_pages(store: QuantStore, lanes, table,
                                pos, lo=None, hi=None,
                                tail_garbage: bool = False) -> QuantStore:
    """`scatter_written_pages` for a quantized page pool: gather each
    slot's written page out of the compute-dtype lane view, quantize it
    (fresh per-(page, head) scales), scatter payload + scale rows to the
    physical ids. `lo`/`hi` (per-slot logical positions, hi exclusive)
    bound the window the program actually wrote: positions outside it
    re-encode from their OWN f32-dequantized physical codes — needed on
    lossy compute dtypes (the bf16 drift `quant_store_written`
    documents) and, with `tail_garbage=True` (the speculative
    write-back), on EVERY dtype: there the lane past `hi` holds
    rejected-draft values whose outliers would otherwise inflate the
    page absmax and permanently coarsen the committed entries sharing
    the page. An f32 pool with a trustworthy tail skips the merge at
    trace time (the lane view is bit-for-bit the dequantized codes).
    Exact lanes are written separately, once per program
    (`quant_store_exact_lanes`) — this runs in a loop over page
    windows."""
    ppl = table.shape[1]
    merge = (lo is not None
             and (tail_garbage or jnp.dtype(store.dtype) != jnp.float32))

    def sc(qleaf, sleaf, lane_leaf):
        page = qleaf.shape[1]
        pg = jnp.clip(pos.astype(jnp.int32) // page, 0, ppl - 1)
        ids = jnp.take_along_axis(table, pg[:, None], axis=1)[:, 0]
        pages = jax.vmap(
            lambda lane, i: jax.lax.dynamic_slice_in_dim(
                lane, i * page, page, axis=0
            )
        )(lane_leaf, pg)
        if merge:
            tcol = pg[:, None] * page + jnp.arange(page)[None, :]
            old = dequantize(qleaf[ids], sleaf[ids], jnp.float32)
            wr = (tcol >= lo[:, None]) & (tcol < hi[:, None])
            wr = wr.reshape(wr.shape + (1,) * (pages.ndim - 2))
            pages = jnp.where(wr, pages.astype(jnp.float32), old)
        qv, sv = quantize(pages, page)  # (S, page, ...), (S, 1[, H])
        return qleaf.at[ids].set(qv), sleaf.at[ids].set(sv)

    pairs = [sc(ql, sl, ll) for ql, sl, ll in zip(
        jax.tree_util.tree_leaves(store.q),
        jax.tree_util.tree_leaves(store.scale),
        jax.tree_util.tree_leaves(lanes))]
    treedef = jax.tree_util.tree_structure(store.q)
    q = jax.tree_util.tree_unflatten(treedef, [a for a, _ in pairs])
    scale = jax.tree_util.tree_unflatten(treedef, [b for _, b in pairs])
    return store.replace(q=q, scale=scale)


def quant_scatter_window_pages(store: QuantStore, lanes, table, start,
                               last, span: int) -> QuantStore:
    """`scatter_window_pages` for a quantized page pool — the
    speculative decode write-back (same clamped page walk, quantized
    payload). [start, last] is the device-committed window: those
    positions take the lane's draws; committed pages below `start` keep
    their own codes, and the stale tail past `last` keeps old codes
    instead of rejected draws on EVERY dtype (`tail_garbage` — a
    rejected outlier would otherwise coarsen the whole page's scale;
    the tail itself stays overwrite-before-attend garbage either
    way)."""
    page = jax.tree_util.tree_leaves(store.q)[0].shape[1]
    limit = table.shape[1] * page - 1
    last = jnp.maximum(last, start)
    for w in range((span - 1) // page + 2):
        pos_w = jnp.clip(jnp.minimum(start + w * page, last), 0, limit)
        store = quant_scatter_written_pages(store, lanes, table, pos_w,
                                            lo=start, hi=last + 1,
                                            tail_garbage=True)
    return store


def quant_store_exact_lanes(store: QuantStore, lanes,
                            eidx_row) -> QuantStore:
    """Write every slot's full-precision lane view into its exact lane
    (paged decode/spec programs; trash-lane duplicates are benign)."""
    if store.exact is None:
        return store
    exact = jax.tree_util.tree_map(
        lambda ex, ln: ex.at[eidx_row].set(ln.astype(ex.dtype)),
        store.exact, lanes,
    )
    return store.replace(exact=exact)


def _require_same_dtype(pool_leaf, seg_leaf, op: str) -> None:
    """Lane/segment writes never cast: a silent `astype` would down-cast
    an fp32 segment into a bf16 pool (or vice versa) and quietly change
    every stream decoded over it. Trace-time error instead — the caller
    casts explicitly if a conversion is really intended."""
    if seg_leaf.dtype != pool_leaf.dtype:
        raise TypeError(
            f"{op}: segment dtype {seg_leaf.dtype} != pool dtype "
            f"{pool_leaf.dtype}; implicit casts are not performed (a "
            "silent astype would corrupt precision) — cast explicitly "
            "before the write"
        )


def extract_lane(caches, slot):
    """Slice slot `slot`'s batch-1 lane out of pooled caches (traced)."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0), caches
    )


def store_lane(caches, lane, slot):
    """Write a batch-1 lane back into the pooled caches at `slot` (traced).
    Dtypes must match exactly — see `_require_same_dtype`."""

    def upd(a, lane_leaf):
        _require_same_dtype(a, lane_leaf, "store_lane")
        return jax.lax.dynamic_update_slice_in_dim(a, lane_leaf, slot, axis=0)

    return jax.tree_util.tree_map(upd, caches, lane)


@functools.partial(jax.jit, donate_argnames=("caches",))
def _splice_program(caches, segment, ctl):
    """Copy-on-acquire: write a batch-1 prefix `segment` (time length L,
    static per compiled program) into lane `ctl[0]` at time offset
    `ctl[1]`. One fused program — every layer's `dynamic_update_slice`
    lands in a single dispatch, and donation reuses the pool's buffers.
    Program inventory is bounded because segment lengths are multiples of
    the prefix cache's page size."""
    slot, offset = ctl[0], ctl[1]

    def upd(a, s):
        _require_same_dtype(a, s, "splice_prefix")
        starts = (slot, offset) + (0,) * (a.ndim - 2)
        return jax.lax.dynamic_update_slice(a, s, starts)

    return jax.tree_util.tree_map(upd, caches, segment)


@functools.partial(jax.jit, static_argnames=("length",))
def _extract_program(caches, ctl, length):
    """Snapshot lane `ctl[0]`'s time span [ctl[1], ctl[1]+length) as a
    batch-1 segment pytree (a COPY — the lane can be overwritten or
    released without invalidating it)."""
    slot, offset = ctl[0], ctl[1]

    def ext(a):
        starts = (slot, offset) + (0,) * (a.ndim - 2)
        sizes = (1, length) + a.shape[2:]
        return jax.lax.dynamic_slice(a, starts, sizes)

    return jax.tree_util.tree_map(ext, caches)


def _zero_batch_entry(a, idx):
    """Zero batch entry `idx` of one cache leaf (traced `idx` — one
    compiled scrub program per tree structure, not per slot)."""
    return jax.lax.dynamic_update_slice_in_dim(
        a, jnp.zeros((1,) + a.shape[1:], a.dtype), idx, axis=0
    )


@functools.partial(jax.jit, donate_argnames=("caches",))
def scrub_lane_program(caches, slot, eidx):
    """Quarantine decontamination (lane pools): zero slot `slot`'s lane
    — and, on a quantized pool, its scale rows plus exact sidecar lane
    `eidx` (0 = the trash lane, harmless to clear). The stale-data
    contract above ("masked-softmax zeros annihilate stale values")
    only holds for FINITE stale values: ``0 * NaN`` is NaN, so a lane a
    NaN/Inf-poisoned forward wrote into would contaminate the next
    stream admitted into it through the masked attention tail. Compiled
    only when a quarantine actually fires — a fault-free engine never
    traces it."""
    if isinstance(caches, QuantStore):
        exact = caches.exact
        if exact is not None:
            exact = jax.tree_util.tree_map(
                lambda a: _zero_batch_entry(a, eidx), exact
            )
        return caches.replace(
            q=jax.tree_util.tree_map(
                lambda a: _zero_batch_entry(a, slot), caches.q),
            scale=jax.tree_util.tree_map(
                lambda a: _zero_batch_entry(a, slot), caches.scale),
            exact=exact,
        )
    return jax.tree_util.tree_map(
        lambda a: _zero_batch_entry(a, slot), caches
    )


@functools.partial(jax.jit, donate_argnames=("phys",))
def scrub_pages_program(phys, row, eidx):
    """Quarantine decontamination (paged pools): zero the physical pages
    listed in `row` — a fixed-length id vector holding the quarantined
    slot's exclusively-owned pages padded with the trash page, which is
    therefore ALWAYS scrubbed too (the poisoned slot's masked overshoot
    writes land there, and a non-finite trash page would leak into every
    slot's masked gather tail). Duplicate ids are idempotent zero
    writes. Shared (refcount > 1) pages are excluded by the caller: they
    hold prompt-prefix KV written strictly before the poisoned step and
    other holders still read them."""
    if isinstance(phys, QuantStore):
        exact = phys.exact
        if exact is not None:
            exact = jax.tree_util.tree_map(
                lambda a: _zero_batch_entry(a, eidx), exact
            )
        return phys.replace(
            q=jax.tree_util.tree_map(lambda a: a.at[row].set(0), phys.q),
            scale=jax.tree_util.tree_map(
                lambda a: a.at[row].set(0), phys.scale),
            exact=exact,
        )
    return jax.tree_util.tree_map(lambda a: a.at[row].set(0), phys)


@functools.partial(jax.jit, donate_argnames=("caches",))
def _quant_splice_program(caches, segment, ctl):
    """Quantized splice: the segment's int8 payload lands at
    ``(ctl[0], ctl[1])`` and its scale rows at ``offset // block`` —
    cached prefixes stay quantized end to end (no dequant/requant on the
    reuse path, so the spliced bytes are bitwise the producer's)."""
    slot, offset = ctl[0], ctl[1]
    b = caches.block

    def upd(a, s, off):
        _require_same_dtype(a, s, "splice_prefix")
        starts = (slot, off) + (0,) * (a.ndim - 2)
        return jax.lax.dynamic_update_slice(a, s, starts)

    return caches.replace(
        q=jax.tree_util.tree_map(
            lambda a, s: upd(a, s, offset), caches.q, segment.q),
        scale=jax.tree_util.tree_map(
            lambda a, s: upd(a, s, offset // b), caches.scale,
            segment.scale),
    )


@functools.partial(jax.jit, static_argnames=("length",))
def _quant_extract_program(caches, ctl, length):
    """Quantized snapshot: slice lane `ctl[0]`'s int8 span plus the
    matching scale rows into an independent `QuantSegment` — the
    prefix-cache insert path at HALF the copy (and tree budget) bytes."""
    slot, offset = ctl[0], ctl[1]
    b = caches.block

    def ext(a, off, ln):
        starts = (slot, off) + (0,) * (a.ndim - 2)
        sizes = (1, ln) + a.shape[2:]
        return jax.lax.dynamic_slice(a, starts, sizes)

    return QuantSegment(
        q=jax.tree_util.tree_map(
            lambda a: ext(a, offset, length), caches.q),
        scale=jax.tree_util.tree_map(
            lambda a: ext(a, offset // b, length // b), caches.scale),
        block=b,
    )


class _SlotBook:
    """Shared slot bookkeeping for both pool layouts: a LIFO free list
    (the freshest slot is reused while its buffers / table row are
    warm) plus an O(1) membership mask — the double-release guard must
    never scan the list on the hot release path. Subclasses call
    `_init_slots` at construction and compose `_guard_release` /
    `_finish_release` around their own teardown."""

    def _init_slots(self, n_slots: int) -> None:
        if n_slots < 1:
            raise ValueError(f"need at least one slot, got {n_slots}")
        self.n_slots = n_slots
        self.positions = np.zeros(n_slots, np.int32)
        self._free = list(range(n_slots - 1, -1, -1))
        self._free_mask = np.ones(n_slots, bool)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return self.n_slots - len(self._free)

    @property
    def occupancy(self) -> float:
        return self.n_active / self.n_slots

    def acquire(self) -> int | None:
        """Claim a free slot (or None when all are taken)."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._free_mask[slot] = False
        self.positions[slot] = 0
        return slot

    def _guard_release(self, slot: int) -> None:
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range [0, {self.n_slots})")
        if self._free_mask[slot]:
            raise ValueError(f"slot {slot} is already free (double release)")

    def _finish_release(self, slot: int) -> None:
        self.positions[slot] = 0
        self._free.append(slot)
        self._free_mask[slot] = True


class KVSlotPool(_SlotBook):
    """`n_slots` cache lanes + free-list bookkeeping.

    `caches` is the pooled pytree (list of per-layer caches, batch dim =
    slot); the engine reassigns it after every jitted step (functional
    updates, donated buffers). `positions[slot]` is the pool's public
    per-lane fill level — how many cache slots hold real KV entries:
    prompt plus every emitted token except the newest (a sampled token's
    KV is only written when it is fed back on the next step) — for
    introspection and capacity accounting. It is deliberately distinct
    from the engine's private device-carry mirror, which also counts the
    discarded overshoot of full-block decode steps. Freed lanes reset
    to 0.
    """

    def __init__(self, model, n_slots: int, max_len: int,
                 quant: str | None = None, quant_block: int = 16,
                 exact_lanes: int = 0):
        self._init_slots(n_slots)
        self.max_len = max_len
        self.quant = quant
        self.quant_block = quant_block
        self.exact_lanes = exact_lanes if quant else 0
        if quant:
            if max_len % quant_block:
                raise ValueError(
                    f"max_len {max_len} is not a multiple of the quant "
                    f"block {quant_block} — scale rows must tile the lane"
                )
            self.caches = make_quant_store(
                model, n_slots, max_len, quant_block,
                exact_lanes=exact_lanes,
            )
        else:
            self.caches = model.init_caches(n_slots, max_len)
        # optional metrics.xla_obs.CompileRegistry (set by the engine
        # when the observatory is on): splice/extract program calls are
        # routed through it so their compilations and run seconds are
        # accounted like the engine's own programs; None = direct jit
        self.registry = None

    @property
    def nbytes(self) -> int:
        """Device bytes the pooled cache pytree holds (all lanes; for a
        quantized pool: int8 payload + scale sidecar + exact lanes) —
        the HBM ledger's kv_pool gauge."""
        from solvingpapers_tpu.metrics.xla_obs import pytree_bytes

        return pytree_bytes(self.caches)

    @property
    def token_capacity(self) -> int:
        """Cache slots the pool books (the kv_bytes_per_token gauge's
        denominator): every lane's full length."""
        return self.n_slots * self.max_len

    def release(self, slot: int) -> None:
        """Return a lane to the pool; it is immediately reusable."""
        self._guard_release(slot)
        self._finish_release(slot)

    # --------------------------------------------------- prefix segments

    def _check_quant_span(self, offset: int, length: int, op: str) -> None:
        b = self.quant_block
        if offset % b or length % b:
            raise ValueError(
                f"{op} span [{offset}, {offset + length}) is not aligned "
                f"to the quant block {b} — quantized segments carry "
                "whole scale rows (prefix pages must be block multiples)"
            )

    def splice_prefix(self, slot: int, segment, offset: int = 0) -> None:
        """Copy-on-acquire: splice a cached batch-1 prefix `segment` into
        lane `slot` at time offset `offset` (one fused jitted program; the
        lane owns the copy, so the source node may be evicted freely
        afterwards). Must run before the suffix prefill that continues at
        `offset + segment length`."""
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range [0, {self.n_slots})")
        if self.quant:
            if not isinstance(segment, QuantSegment):
                raise TypeError(
                    "a quantized pool splices QuantSegment payloads "
                    f"(int8 + scales), got {type(segment).__name__} — "
                    "the prefix cache and the pool must agree on kv_quant"
                )
            length = segment.length
        else:
            length = jax.tree_util.tree_leaves(segment)[0].shape[1]
        if offset < 0 or offset + length > self.max_len:
            raise ValueError(
                f"segment span [{offset}, {offset + length}) exceeds the "
                f"lane capacity {self.max_len}"
            )
        if self.quant:
            self._check_quant_span(offset, length, "splice_prefix")
        prog = _quant_splice_program if self.quant else _splice_program
        ctl = jnp.asarray([slot, offset], jnp.int32)
        if self.registry is not None:
            # segment layout is fixed per model (one pool, one model), so
            # the static time length is the whole varying signature
            self.caches = self.registry.call(
                "splice_program", (length,), prog,
                (self.caches, segment, ctl),
            )
        else:
            self.caches = prog(self.caches, segment, ctl)

    def extract_prefix(self, slot: int, offset: int, length: int):
        """Snapshot lane `slot`'s KV span [offset, offset+length) as an
        independent batch-1 segment (the prefix cache's insert path)."""
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range [0, {self.n_slots})")
        if offset < 0 or length < 1 or offset + length > self.max_len:
            raise ValueError(
                f"extract span [{offset}, {offset + length}) exceeds the "
                f"lane capacity {self.max_len}"
            )
        if self.quant:
            self._check_quant_span(offset, length, "extract_prefix")
        prog = _quant_extract_program if self.quant else _extract_program
        ctl = jnp.asarray([slot, offset], jnp.int32)
        if self.registry is not None:
            return self.registry.call(
                "extract_program", (length,), prog,
                (self.caches, ctl, length), static_argnums=(2,),
            )
        return prog(self.caches, ctl, length)


# ======================================================================
# Paged pool: block-paged cache lanes + refcounted zero-copy sharing
# ======================================================================
#
# The lane pool above books `max_len` cache slots per engine slot — HBM
# reserved for the worst case, slot count coupled to max_seq, and every
# prefix hit paying a device copy (splice). `PagedKVPool` is the vLLM
# PagedAttention layout instead: ONE physical pool of fixed-size KV
# blocks ("pages"), carved from `model.init_caches(n_pages, page_size)`
# so the batch dimension IS the page id, plus a host-side per-slot page
# table mapping logical page index -> physical page id. The jitted
# prefill/decode programs translate logical->physical with a gather
# (`gather_lanes`) that materializes the familiar (S, max_len, ...)
# lane view, run the models UNMODIFIED on it, and scatter only the
# written page(s) back — so all four decoder families serve paged with
# zero model changes, and the page table rides the engine's existing
# packed control-array transfer.
#
# Sharing: the radix prefix cache holds PHYSICAL PAGE IDS with
# refcounts instead of snapshot copies (the SGLang RadixAttention
# move). A prefix hit is a host-side page-table append + incref — zero
# device copies — and inserting a freshly prefilled prompt is an incref
# of the slot's own fully-filled pages. This is sound because cached
# pages are never rewritten by their producer: the engine only caches
# prompt positions [0, aligned) with aligned <= len(prompt)-1
# page-aligned, and the owning slot's future writes land at positions
# >= len(prompt), i.e. in pages strictly AFTER every cached one; decode
# scatters exactly the one page containing the written position, and
# prefill scatters only pages >= the (page-aligned) match length. So a
# shared page is immutable for as long as anything references it — no
# copy-on-write machinery needed.
#
# Page 0 is a reserved TRASH page, never allocated: page-table entries
# beyond a slot's allocation (and every entry of an idle slot) point at
# it, so gathers always read valid (finite, masked-away) memory and
# masked dummy writes / discarded overshoot land harmlessly there. The
# stale-data contract is the lane pool's, per page: freed pages are not
# zeroed, reuse is safe because prefill/decode overwrite before any
# attention and position masking annihilates slack beyond the fill.


def gather_lanes(phys, table):
    """Logical lane view of the physical pool (traced): `table` is the
    (S, pages_per_lane) page-table block; returns the (S, max_len, ...)
    pytree the lane-pool programs operate on. One gather per leaf — the
    logical->physical translation the paged programs do up front."""

    def g(leaf):
        pages = leaf[table]  # (S, PPL, page, ...)
        s, ppl, page = pages.shape[:3]
        return pages.reshape((s, ppl * page) + leaf.shape[2:])

    return jax.tree_util.tree_map(g, phys)


def gather_lane(phys, row):
    """Batch-1 lane view for one slot: `row` is its (pages_per_lane,)
    page-table row (traced)."""

    def g(leaf):
        pages = leaf[row]  # (PPL, page, ...)
        ppl, page = pages.shape[:2]
        return pages.reshape((1, ppl * page) + leaf.shape[2:])

    return jax.tree_util.tree_map(g, phys)


def scatter_lane_pages(phys, lane, row, start_page: int):
    """Write a batch-1 lane's pages [start_page:] back to the pool at
    `row[start_page:]` (traced; `start_page` static). The pages BELOW
    `start_page` are deliberately untouched — on a prefix hit they are
    shared, refcounted pages the prefill never wrote, and not rewriting
    them is what makes the hit zero-copy. Unallocated tail entries point
    at the trash page, so their (unchanged, garbage) lane pages land
    there; duplicate trash indices are benign (.at[].set last-writer)."""
    ppl = row.shape[0]
    ids = row[start_page:]

    def sc(p_leaf, lane_leaf):
        page = p_leaf.shape[1]
        pages = lane_leaf.reshape((ppl, page) + lane_leaf.shape[2:])
        return p_leaf.at[ids].set(pages[start_page:])

    return jax.tree_util.tree_map(sc, phys, lane)


def scatter_written_pages(phys, lanes, table, pos):
    """Per-slot single-page write-back for one decode step (traced):
    slot s wrote exactly one token at position `pos[s]`, so exactly one
    page — index pos[s] // page — of its gathered lane changed. Gather
    that page per slot and scatter the batch to the physical ids. Active
    slots' write pages are exclusively owned (see the module comment:
    shared pages always precede the write frontier), so the batched
    scatter indices never collide except on the trash page, where
    garbage overwriting garbage is fine."""
    ppl = table.shape[1]

    def sc(p_leaf, lane_leaf):
        page = p_leaf.shape[1]
        pg = jnp.clip(pos.astype(jnp.int32) // page, 0, ppl - 1)
        ids = jnp.take_along_axis(table, pg[:, None], axis=1)[:, 0]
        pages = jax.vmap(
            lambda lane, i: jax.lax.dynamic_slice_in_dim(
                lane, i * page, page, axis=0
            )
        )(lane_leaf, pg)
        return p_leaf.at[ids].set(pages)

    return jax.tree_util.tree_map(sc, phys, lanes)


def pad_time(tree, extra: int):
    """Append `extra` zeroed slots along the TIME axis (axis 1) of every
    cache leaf (traced). The speculative decode programs (serve/engine.py)
    pad each lane with ``spec_k + 1`` scratch slots before their
    draft-verify rounds: a chunk write at time offset p spans
    ``[p, p + k]``, and XLA's `dynamic_update_slice` CLAMPS an
    out-of-range start — which would SHIFT the whole chunk left and
    silently overwrite committed KV. With the scratch tail, every chunk
    whose start is inside the real lane fits, and overshoot (post-EOS /
    post-budget rounds, frozen at the lane end) lands in slack that
    `strip_time` drops before the lanes go back to the pool."""
    return jax.tree_util.tree_map(
        lambda a: jnp.concatenate(
            [a, jnp.zeros((a.shape[0], extra) + a.shape[2:], a.dtype)],
            axis=1,
        ),
        tree,
    )


def strip_time(tree, extra: int):
    """Drop the trailing `extra` time slots `pad_time` appended (traced)."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.slice_in_dim(a, 0, a.shape[1] - extra, axis=1),
        tree,
    )


def scatter_window_pages(phys, lanes, table, start, last, span: int):
    """Scatter each slot's written page window back to the pool (traced):
    slot s wrote positions ``[start[s], last[s]]`` of its gathered lane
    view — the speculative decode block's ACCEPTED window (``last`` is
    the final committed position; rejected-draft garbage beyond it never
    reaches the physical pool, so a paged spec engine's pool holds only
    committed KV). `span` is the static per-slot window bound in tokens
    (rounds x chunk width for the spec block); the page walk advances in
    page-size steps clamped to ``last``, so trailing windows re-write the
    last committed page with its own final content — idempotent. Slots
    with nothing committed (``last < start``, inactive lanes) clamp to
    `start`, whose table entry rests at the trash page."""
    page = jax.tree_util.tree_leaves(phys)[0].shape[1]
    limit = table.shape[1] * page - 1
    last = jnp.maximum(last, start)
    for w in range((span - 1) // page + 2):
        pos_w = jnp.clip(jnp.minimum(start + w * page, last), 0, limit)
        phys = scatter_written_pages(phys, lanes, table, pos_w)
    return phys


TRASH_PAGE = 0  # physical page 0: reserved write sink, never allocated


class PagedKVPool(_SlotBook):
    """Block-paged KV pool: `page_budget` allocatable fixed-size pages +
    per-slot page tables + refcounts (host-side bookkeeping; the traced
    side is the gather/scatter helpers above).

    `phys` is the physical pytree — `model.init_caches(page_budget + 1,
    page_size)`, batch dim = page id, page 0 the trash page — and is
    NEVER reallocated: `nbytes` is constant for the pool's lifetime,
    which is the point (HBM booked once, up front, independent of slot
    count and max_seq). `table` is the (n_slots, pages_per_lane) int32
    page-table mirror shipped to the device inside the engine's packed
    control arrays; entries [0, n_alloc[slot]) are live (refcounted),
    the rest rest at the trash page.

    Refcount protocol: an owned page (fresh `ensure` allocation) starts
    at 1; every additional holder — a slot appending shared prefix pages
    (`append_shared`) or the radix tree taking a reference
    (`share_range`) — increfs; `release`/`decref` decrement and a page
    returns to the free list at zero. The tree and the slots are
    symmetric holders: either can outlive the other.

    `positions[slot]` keeps the lane pool's fill-level semantics (prompt
    + emitted - newest), for introspection and the fragmentation gauge.
    """

    def __init__(self, model, n_slots: int, max_len: int, page_size: int,
                 page_budget: int | None = None, quant: str | None = None,
                 exact_lanes: int = 0):
        self._init_slots(n_slots)
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if max_len % page_size:
            raise ValueError(
                f"max_len {max_len} is not a multiple of page_size "
                f"{page_size} — page tables need whole pages per lane"
            )
        self.max_len = max_len
        self.page_size = page_size
        self.pages_per_lane = max_len // page_size
        self.quant = quant
        self.quant_block = page_size  # one scale row per (page, head)
        self.exact_lanes = exact_lanes if quant else 0
        if page_budget is None:
            # lane-pool-equivalent capacity: every slot can hold a full
            # lane at once (callers shrink it to trade worst-case room
            # for more slots — that is the capacity win)
            page_budget = n_slots * self.pages_per_lane
        if page_budget < self.pages_per_lane:
            raise ValueError(
                f"page_budget {page_budget} cannot cover even one full "
                f"lane ({self.pages_per_lane} pages) — a single max-length "
                "request could never be scheduled"
            )
        self.page_budget = page_budget
        self.n_pages = page_budget + 1  # + the trash page
        if quant:
            # exact lanes are LANE-shaped (max_len): a kv_exact stream
            # never allocates pages at all — its table rests at trash
            # and its KV lives wholly in the full-precision sidecar
            self.phys = make_quant_store(
                model, self.n_pages, page_size, page_size,
                exact_lanes=exact_lanes, exact_time=max_len,
            )
        else:
            self.phys = model.init_caches(self.n_pages, page_size)
        self.table = np.full((n_slots, self.pages_per_lane), TRASH_PAGE,
                             np.int32)
        self.n_alloc = np.zeros(n_slots, np.int32)
        self.refcount = np.zeros(self.n_pages, np.int32)
        self.refcount[TRASH_PAGE] = 1  # permanently held, never freed
        # LIFO free list: recently-freed pages are reused warm
        self._free_pages = list(range(self.n_pages - 1, TRASH_PAGE, -1))

    # ------------------------------------------------------------ gauges

    @property
    def nbytes(self) -> int:
        """Device bytes of the physical pool — CONSTANT by construction
        (the pool never grows or shrinks); the HBM ledger's kv_pool
        gauge."""
        from solvingpapers_tpu.metrics.xla_obs import pytree_bytes

        return pytree_bytes(self.phys)

    @property
    def page_nbytes(self) -> int:
        """Device bytes one page holds across every cache leaf (for a
        quantized pool: int8 payload + its scale rows, excluding the
        exact-lane sidecar, which no page reference pins) — what a
        radix-tree page reference costs in the prefix cache's budget."""
        if self.quant:
            pool_bytes, _, _, _ = quant_pool_bytes(self.phys)
            return pool_bytes // self.n_pages
        return self.nbytes // self.n_pages

    @property
    def token_capacity(self) -> int:
        """Allocatable cache slots (the kv_bytes_per_token gauge's
        denominator): every budgeted page, trash excluded."""
        return self.page_budget * self.page_size

    @property
    def pages_free(self) -> int:
        return len(self._free_pages)

    @property
    def pages_active(self) -> int:
        return self.page_budget - len(self._free_pages)

    @property
    def fragmentation(self) -> float:
        """Internal fragmentation: the fraction of slot-allocated page
        capacity not (yet) holding live KV — decode reservations and
        trailing-page slack. 0.0 with nothing allocated; paged pools
        have no EXTERNAL fragmentation (any free page serves any slot),
        which is the property the gauge exists to contrast with the
        lane pool's whole-lane booking."""
        alloc_tokens = int(self.n_alloc.sum()) * self.page_size
        if alloc_tokens == 0:
            return 0.0
        used = int(np.minimum(self.positions,
                              self.n_alloc * self.page_size).sum())
        return 1.0 - used / alloc_tokens

    # ------------------------------------------------------------- slots
    #
    # acquire() is the shared _SlotBook one; pages are NOT reserved at
    # acquire — `append_shared`/`ensure` populate the table as the
    # request's footprint becomes known.

    def release(self, slot: int) -> None:
        """Free a slot: decref every table entry it holds (owned pages
        free immediately; shared ones survive under their other
        holders), park the row at the trash page."""
        self._guard_release(slot)
        n = int(self.n_alloc[slot])
        self.decref(self.table[slot, :n].tolist())
        self.table[slot, :n] = TRASH_PAGE
        self.n_alloc[slot] = 0
        self._finish_release(slot)

    # ------------------------------------------------------------- pages

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to cover token positions [0, n_tokens)."""
        return -(-n_tokens // self.page_size)

    def ensure(self, slot: int, n_tokens: int) -> bool:
        """Grow `slot`'s table to cover positions [0, n_tokens) with
        freshly-owned pages. False when the free list runs dry — the
        allocation KEEPS what it got (the pages stay booked to the slot;
        the caller reclaims — prefix-tree eviction, then preemption —
        and retries). Shared prefix pages must already be appended:
        `ensure` only ever extends the tail."""
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range [0, {self.n_slots})")
        target = self.pages_for(min(n_tokens, self.max_len))
        if target > self.pages_per_lane:
            raise ValueError(
                f"coverage of {n_tokens} tokens exceeds the lane capacity "
                f"{self.max_len}"
            )
        while int(self.n_alloc[slot]) < target:
            if not self._free_pages:
                return False
            pid = self._free_pages.pop()
            self.refcount[pid] = 1
            self.table[slot, self.n_alloc[slot]] = pid
            self.n_alloc[slot] += 1
        return True

    def append_shared(self, slot: int, page_ids) -> None:
        """Zero-copy prefix hit: extend `slot`'s page table with already-
        populated shared pages (incref'd — the radix tree keeps its own
        references). Must precede any `ensure` for the slot: shared
        prefix pages are logically the lane's leading pages."""
        if not page_ids:
            return
        n = int(self.n_alloc[slot])
        if n + len(page_ids) > self.pages_per_lane:
            raise ValueError(
                f"shared append of {len(page_ids)} pages at table offset "
                f"{n} exceeds the lane capacity {self.pages_per_lane}"
            )
        for pid in page_ids:
            if not TRASH_PAGE < pid < self.n_pages:
                raise ValueError(f"page id {pid} out of range")
        self.incref(page_ids)
        self.table[slot, n:n + len(page_ids)] = page_ids
        self.n_alloc[slot] += len(page_ids)

    def share_range(self, slot: int, offset: int, length: int) -> list[int]:
        """Take references on the pages covering `slot`'s token span
        [offset, offset + length) — the prefix cache's insert path
        (page-aligned span; the lane-pool `extract_prefix` analogue,
        minus the device copy). The returned ids are INCREF'D: the
        caller owns one reference per page and must `decref` to drop
        them (the radix tree does, on eviction)."""
        if offset % self.page_size or length % self.page_size:
            raise ValueError(
                f"share span [{offset}, {offset + length}) is not "
                f"page-aligned (page_size {self.page_size})"
            )
        if length < 1:
            raise ValueError(f"length must be >= 1, got {length}")
        first = offset // self.page_size
        last = (offset + length) // self.page_size
        if last > int(self.n_alloc[slot]):
            raise ValueError(
                f"share span [{offset}, {offset + length}) exceeds slot "
                f"{slot}'s allocated coverage "
                f"{int(self.n_alloc[slot]) * self.page_size}"
            )
        ids = self.table[slot, first:last].tolist()
        self.incref(ids)
        return ids

    def incref(self, page_ids) -> None:
        """Take one reference per id (the single bump path —
        `append_shared`/`share_range` route through it). Per-element on
        purpose: a numpy fancy-index `+= 1` silently under-counts
        duplicate ids."""
        for pid in page_ids:
            if self.refcount[pid] < 1:
                raise ValueError(f"page {pid} is free — cannot incref")
        for pid in page_ids:
            self.refcount[pid] += 1

    def decref(self, page_ids) -> None:
        """Drop one reference per id; pages hitting zero return to the
        free list (LIFO). Over-release raises — a negative refcount
        means a page was freed while someone still held it, the exact
        bug the counts exist to make loud."""
        for pid in page_ids:
            if pid == TRASH_PAGE:
                raise ValueError("the trash page is never released")
            if self.refcount[pid] < 1:
                raise ValueError(
                    f"page {pid} over-released (refcount already 0)"
                )
            self.refcount[pid] -= 1
            if self.refcount[pid] == 0:
                self._free_pages.append(pid)
