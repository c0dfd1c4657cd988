"""Mixture-of-Experts routing + dispatch primitives.

Capability target: deepseekv3/deepseekv3.ipynb cell 23 (`MoeLayer`) — linear
gate, optional softplus-noise top-k, learned routing bias added before
selection (aux-free load balancing), top-k -inf-masked softmax over all
experts, weighted expert combine, shared expert, and the no-grad bias update
`bias += rate * sign(mean(load) - load)`.

TPU-first: the reference's python loop over experts with boolean gather/
scatter becomes static-shape dispatch into (E, C, D) expert capacity slots.
The slot assignment is two small integer maps (token -> its slots, slot ->
its token) and rows move through them by gather, forward and backward: the
maps are one partial permutation read from both ends, so no scatter and no
product with a (T, E, C) one-hot is needed, and the MXU runs the experts'
products only. An expert's filled slots are a prefix of its C rows, and
the dispatch hands the E fill counts on (`_Routes.fill`: `moe_dispatch_combine`
with `pass_fill`, `moe_held_dispatch_combine` always), so that the
experts' product can skip the rows behind them: on one TPU
`kernels/moe_grouped.py` does, in tiles of rows; elsewhere the caller's
einsums run over every slot. A dense all-experts path is kept as the
numerics reference (exact — no capacity drops) and for tiny configs.
Expert weights are stacked (E, ...) arrays so an `expert` mesh axis shards
them directly and GSPMD inserts the all_to_alls (SURVEY.md §2.3 EP row).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from solvingpapers_tpu.ops.attention import BIG_NEG


def topk_gate_probs(gate_logits: jax.Array, k: int) -> jax.Array:
    """(T, E) logits -> (T, E) probs: softmax over the top-k entries per row,
    zero elsewhere (deepseekv3 cell 23's masked-scatter softmax; computed in
    float32)."""
    with jax.named_scope("L_moe_gate"):
        logits32 = gate_logits.astype(jnp.float32)
        kth = jax.lax.top_k(logits32, k)[0][..., -1:]
        masked = jnp.where(logits32 >= kth, logits32, BIG_NEG)
        return jax.nn.softmax(masked, axis=-1)


@jax.named_scope("L_moe_stats")
def aux_free_bias_update(
    probs: jax.Array, bias: jax.Array, rate: float, axis_names=None, ci=None
) -> jax.Array:
    """New routing bias per deepseekv3 cell 23: load c_i = sum of routed
    probabilities per expert; bias += rate * sign(mean(c) - c). Run under
    stop_gradient (the reference wraps it in torch.no_grad).

    `axis_names`: mesh axes to psum the per-expert load over — REQUIRED
    inside shard_map (context/data-parallel steps), where each shard sees
    only its tokens and a local update would silently diverge per shard.
    `ci`: precomputed (already psum'd) per-expert load, to share one
    reduction/collective with load_balance_stats."""
    if ci is None:
        ci = expert_load(probs, axis_names)
    err = jnp.mean(ci) - ci
    return bias + rate * jnp.sign(err).astype(bias.dtype)


def _psum_axes(x: jax.Array, axis_names) -> tuple:
    """Restrict a psum to the axes `x` actually varies over — under the
    shard_map vma checker a psum over an invariant axis is a type error
    (e.g. CP x PP meshes where 'data' has size 1)."""
    vma = jax.typeof(x).vma
    return tuple(a for a in axis_names if a in vma)


@jax.named_scope("L_moe_stats")
def expert_load(probs: jax.Array, axis_names=None) -> jax.Array:
    """(E,) routed probability mass per expert under stop_gradient,
    psum'd over `axis_names` when inside shard_map."""
    ci = jax.lax.stop_gradient(jnp.sum(probs.astype(jnp.float32), axis=0))
    if axis_names:
        axes = _psum_axes(ci, axis_names)
        if axes:
            ci = jax.lax.psum(ci, axes)
    return ci


def expert_capacity(
    n_tokens: int, n_experts: int, top_k: int, capacity_factor: float
) -> int:
    """Per-expert slot count for dispatch: ceil(T*k/E * cf), 8-aligned."""
    c = int(n_tokens * top_k / n_experts * capacity_factor)
    return max(8, -(-c // 8) * 8)


def _dispatch_slots(probs: jax.Array, capacity: int):
    """Slot assignment shared by dispatch and the drop metric (they must
    never disagree about what is dropped): sel = routed (token, expert)
    pairs, pos = slot index within the expert queue (ordered by token id),
    keep = pairs inside capacity."""
    sel = probs > 0.0
    pos = jnp.cumsum(sel.astype(jnp.int32), axis=0) - 1  # (T, E)
    keep = sel & (pos < capacity)
    return sel, pos, keep


def _slot_fill(pos: jax.Array, capacity: int) -> jax.Array:
    """(E,) int32 slots each expert fills: min(its routed pairs, C), the
    pairs counted by the last row of `pos`. The filled slots are the PREFIX
    [0, fill) of an expert's C: `pos` numbers its pairs from 0 in token
    order and `_routes` sorts them into slots in that order, the empty
    slots behind them."""
    return jnp.minimum(pos[-1] + 1, capacity)


def _vary_alike(*xs: jax.Array) -> tuple:
    """Inside shard_map, widen every argument's varying axes to their union
    (outside it, nothing): a `custom_vjp` rule returns cotangents of its
    inputs' own types, so the cast, whose transpose is the psum, has to
    happen before the call and not inside the rule."""
    axes = frozenset().union(*(jax.typeof(x).vma for x in xs))

    def widen(x):
        missing = tuple(axes - jax.typeof(x).vma)
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return tuple(widen(x) for x in xs)


def _take_rows(rows: jax.Array, idx: jax.Array) -> jax.Array:
    """`rows[idx]` along axis 0; the sentinel `len(rows)` reads a zero row."""
    return jnp.take(rows, idx, axis=0, mode="fill", fill_value=0)


def _token_rows(slots: jax.Array, tok_map: jax.Array, flat: bool) -> list[jax.Array]:
    """The token side of the slot assignment: for each column of `tok_map`,
    in float32, the (T, D) rows of the (E, C, D) slots that the tokens' kept
    pairs point at, zeros for the other tokens.

    `flat` False: `tok_map` is (T, E), a pair's slot within its expert
    (else C), a gather an expert: it has room for every expert of a row of
    tied logits (`topk_gate_probs` selects them all) and the `shard_map`
    paths slice it by column. On the v5e it is the slower of the two for
    DeepSeekV3 as well (PERF.md, PR 28; ROADMAP S1b).
    `flat` True: `tok_map` is (T, k), the flat slot e*C + pos of each of a
    token's k routed pairs (else E*C): k gathers of T rows however many
    experts are held, for a device that holds a share of a wide router's
    experts."""
    if flat:
        slots = slots.reshape(-1, slots.shape[-1])
    return [
        _take_rows(slots if flat else slots[j], tok_map[:, j]).astype(jnp.float32)
        for j in range(tok_map.shape[1])
    ]


class _Routes(NamedTuple):
    """The slot assignment as index maps. The kept (token, expert) pairs
    and the filled slots are one partial permutation read from both ends,
    so rows move by gather in either direction, forward and backward."""

    tok_pos: jax.Array  # (T, E) slot of a kept pair within its expert, else C
    slot_tok: jax.Array  # (E, C) token held by the slot, else T
    slot_w: jax.Array  # (E, C) gate weight of the slot's pair, float32
    fill: jax.Array  # (E,) slots filled: slot_tok[e, :fill[e]] < T, the rest T


def _routes(probs: jax.Array, capacity: int) -> _Routes:
    t = probs.shape[0]
    with jax.named_scope("L_moe_gate"):
        probs = jax.lax.stop_gradient(probs).astype(jnp.float32)
        _, pos, keep = _dispatch_slots(probs, capacity)
        # `pos` counts an expert's tokens in token order, so its kept token
        # ids in ascending order ARE its slots in order; every other token
        # sorts behind them as the sentinel T
        tok = jnp.where(keep.T, jnp.arange(t, dtype=jnp.int32), t)
        slot_tok, slot_w = jax.lax.sort((tok, probs.T), dimension=1, num_keys=1)
        if capacity <= t:
            slot_tok, slot_w = slot_tok[:, :capacity], slot_w[:, :capacity]
        else:
            pad = ((0, 0), (0, capacity - t))
            slot_tok = jnp.pad(slot_tok, pad, constant_values=t)
            slot_w = jnp.pad(slot_w, pad)
        return _Routes(jnp.where(keep, pos, capacity), slot_tok, slot_w,
                       _slot_fill(pos, capacity))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_rows(x, tok_map, slot_tok, flat):
    return _take_rows(x, slot_tok)


def _dispatch_rows_fwd(x, tok_map, slot_tok, flat):
    return _take_rows(x, slot_tok), tok_map


def _dispatch_rows_bwd(flat, tok_map, dxe):
    # each token reads back the slots it was copied to: the transpose of a
    # gather through a partial permutation is the gather through its inverse
    dx = functools.reduce(jnp.add, _token_rows(dxe, tok_map, flat))
    return dx.astype(dxe.dtype), None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _combine_rows(ye, w, tok_map, slot_tok, slot_w, flat):
    """`w` holds a weight for each column of `tok_map`: the (T, E) gate
    probabilities, or the (T, k) weights of a token's routed pairs."""
    w = w.astype(jnp.float32)
    out = functools.reduce(jnp.add, [
        rows * w[:, j, None]
        for j, rows in enumerate(_token_rows(ye, tok_map, flat))
    ])
    return out.astype(ye.dtype)


def _combine_rows_fwd(ye, w, tok_map, slot_tok, slot_w, flat):
    out = _combine_rows(ye, w, tok_map, slot_tok, slot_w, flat)
    return out, (ye, w, tok_map, slot_tok, slot_w)


def _combine_rows_bwd(flat, res, dout):
    ye, w, tok_map, slot_tok, slot_w = res
    dye = _take_rows(dout, slot_tok).astype(jnp.float32) * slot_w[..., None]
    dout = dout.astype(jnp.float32)
    dw = jnp.stack(
        [jnp.sum(rows * dout, axis=-1)
         for rows in _token_rows(ye, tok_map, flat)],
        axis=1,
    )
    return dye.astype(ye.dtype), dw.astype(w.dtype), None, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def _dispatch(x: jax.Array, probs: jax.Array, capacity: int):
    """The index maps of the kept (token, expert) pairs and the tokens
    gathered through them into (E, C, D) expert slots; an empty slot holds
    zeros."""
    routes = _routes(probs, capacity)
    with jax.named_scope("L_moe_dispatch"):
        xe = _dispatch_rows(
            *_vary_alike(x, routes.tok_pos, routes.slot_tok), False)
    return routes, xe


def _weighted_combine(routes: _Routes, probs: jax.Array, ye: jax.Array):
    """Expert outputs (E, C, D) back to (T, D): every token gathers the
    slots of its kept pairs and sums them weighted by the gate, in
    float32, cast once to the experts' dtype."""
    with jax.named_scope("L_moe_combine"):
        return _combine_rows(*_vary_alike(
            ye, probs, routes.tok_pos, routes.slot_tok, routes.slot_w), False)


def moe_dispatch_combine(
    x: jax.Array,
    probs: jax.Array,
    expert_fn,
    capacity: int,
    *,
    pass_fill: bool = False,
) -> jax.Array:
    """Static-shape MoE: route (T, D) tokens to (E, C, D) slots, run
    `expert_fn((E, C, D)) -> (E, C, D)`, combine back weighted by probs.
    With `pass_fill` the call is `expert_fn((E, C, D), fill)`, fill (E,)
    int32: expert e's tokens are the rows [0, fill[e]) of its slots and the
    rows behind them are zero, so a product that maps zero rows to zero rows
    may skip them.

    Tokens beyond an expert's capacity are dropped for that expert (their
    probability mass contributes nothing) — set capacity_factor high enough
    that drops are rare; the dense path below is drop-free.
    """
    routes, xe = _dispatch(x, probs, capacity)
    with jax.named_scope("L_moe_experts"):
        ye = expert_fn(xe, routes.fill) if pass_fill else expert_fn(xe)
    return _weighted_combine(routes, probs, ye)


def topk_renorm_weights(
    logits: jax.Array, k: int, renorm: bool = True
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Softmax over ALL experts in float32, the k largest a token, their
    weights divided by their sum when `renorm` (the published
    `norm_topk_prob`). Returns (weights (T, k), expert ids (T, k), the full
    softmax (T, E)): the routed pairs themselves, not a (T, E) map, since a
    router may be far wider than the experts a device holds."""
    with jax.named_scope("L_moe_gate"):
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        top_w, top_idx = jax.lax.top_k(probs, k)
        if renorm:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        return top_w, top_idx, probs


def topk_sigmoid_weights(
    logits: jax.Array, select_bias: jax.Array, k: int, renorm: bool = True,
    scale: float = 1.0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sigmoid scores over ALL experts in float32; the k chosen a token are
    the largest of score + `select_bias` (E,), which steers the choice and
    nothing else: it takes no gradient, and the weights are the scores
    themselves, divided by their sum when `renorm` (the published
    `moe_renormalize`), times `scale` (`routed_scaling_factor`). With one
    expert group of which one is kept, the group-limited choice some
    families make is this plain top-k. Returns (weights (T, k), expert ids
    (T, k), the scores (T, E)), as `topk_renorm_weights` does."""
    with jax.named_scope("L_moe_gate"):
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, top_idx = jax.lax.top_k(
            scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32)),
            k)
        top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
        if renorm:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        return top_w * scale, top_idx, scores


def held_pair_probs(
    pair_w: jax.Array, pair_idx: jax.Array, first: int, held: int
) -> jax.Array:
    """The routed pairs that fall on the experts [first, first + held) as a
    (T, held) map of their weights, zero elsewhere: what `_dispatch_slots`
    and the drop counter read, so that one rule decides what is dropped
    whether a device holds every expert or a share."""
    with jax.named_scope("L_moe_gate"):
        local = pair_idx - first  # (T, k)
        hit = local[..., None] == jnp.arange(held, dtype=local.dtype)
        return jnp.sum(
            jnp.where(hit, pair_w.astype(jnp.float32)[..., None], 0.0), axis=1
        )


class _PairRoutes(NamedTuple):
    """The slot assignment of a device that holds a share of the experts.
    The token side follows the routed pairs, k a token, and not the experts
    held (`_token_rows` with `flat`)."""

    pair_slot: jax.Array  # (T, k) flat slot e*C + pos of a kept pair, else E*C
    slot_tok: jax.Array  # (E, C) token held by the slot, else T
    slot_w: jax.Array  # (E, C) weight of the slot's pair, float32


def _pair_routes(
    pair_w: jax.Array, pair_idx: jax.Array, first: int, held: int, capacity: int
) -> tuple[_PairRoutes, jax.Array, jax.Array]:
    """(the maps, the (held,) slots each expert fills, the (T, held) weights
    they were made from)."""
    probs = held_pair_probs(jax.lax.stop_gradient(pair_w), pair_idx, first, held)
    routes = _routes(probs, capacity)
    with jax.named_scope("L_moe_gate"):
        local = pair_idx - first
        on_held = (local >= 0) & (local < held)
        local = jnp.clip(local, 0, held - 1)
        pos = jnp.take_along_axis(routes.tok_pos, local, axis=1)
        pair_slot = jnp.where(
            on_held & (pos < capacity), local * capacity + pos, held * capacity
        ).astype(jnp.int32)
    return (_PairRoutes(pair_slot, routes.slot_tok, routes.slot_w),
            routes.fill, probs)


def moe_held_dispatch_combine(
    x: jax.Array,
    pair_w: jax.Array,
    pair_idx: jax.Array,
    expert_fn,
    capacity: int,
    first: int,
    held: int,
) -> tuple[jax.Array, jax.Array]:
    """One expert-parallel rank's part of an MoE layer, with no exchange:
    of the routed pairs `(pair_w, pair_idx)` (T, k) over ALL experts, those
    on the experts [first, first + held) go through the same slots as
    `moe_dispatch_combine` (`_dispatch_slots` decides what is dropped),
    `expert_fn((held, C, D), fill) -> (held, C, D)` runs (fill (held,)
    int32, as `moe_dispatch_combine` hands it on with `pass_fill`), and
    each token sums its kept pairs. What the other experts would add is
    left out. Returns (the partial output (T, D), the (T, held) weights of
    the pairs routed here, for the drop counter)."""
    routes, fill, probs = _pair_routes(pair_w, pair_idx, first, held, capacity)
    with jax.named_scope("L_moe_dispatch"):
        xe = _dispatch_rows(
            *_vary_alike(x, routes.pair_slot, routes.slot_tok), True)
    with jax.named_scope("L_moe_experts"):
        ye = expert_fn(xe, fill)
    with jax.named_scope("L_moe_combine"):
        out = _combine_rows(*_vary_alike(ye, pair_w, *routes), True)
    return out, probs


@jax.named_scope("L_moe_stats")
def dispatch_drop_fraction(
    probs: jax.Array, capacity: int, axis_names=None
) -> jax.Array:
    """Fraction of routed (token, expert) assignments that
    moe_dispatch_combine drops at this capacity (same cumsum slot
    assignment), under stop_gradient. 0.0 = no dropped probability mass —
    the load-balance observability SURVEY.md hard part #1 calls for;
    silent drops were VERDICT r1 missing item 5. `axis_names`: psum counts
    across shards (each shard dispatches its local tokens independently)."""
    sel, _, keep = _dispatch_slots(jax.lax.stop_gradient(probs), capacity)
    kept = jnp.sum(keep.astype(jnp.float32))
    routed = jnp.sum(sel.astype(jnp.float32))
    if axis_names:
        axes = _psum_axes(kept, axis_names)
        if axes:
            kept = jax.lax.psum(kept, axes)
            routed = jax.lax.psum(routed, axes)
    return (routed - kept) / jnp.maximum(routed, 1.0)


@jax.named_scope("L_moe_stats")
def live_tile_fraction(probs: jax.Array, capacity: int, tile: int) -> jax.Array:
    """Share of the (E, C) slots' row tiles of `tile` rows that hold a
    token, from the same slot assignment as the dispatch, under
    stop_gradient: what a product that skips the tiles behind each expert's
    fill still multiplies (`kernels/moe_grouped.py`)."""
    _, pos, _ = _dispatch_slots(jax.lax.stop_gradient(probs), capacity)
    live = jnp.sum((_slot_fill(pos, capacity) + (tile - 1)) // tile)
    return live.astype(jnp.float32) / (probs.shape[1] * (capacity // tile))


@jax.named_scope("L_moe_stats")
def load_balance_stats(
    probs: jax.Array, axis_names=None, ci=None
) -> dict[str, jax.Array]:
    """Routing-load summary from (T, E) gate probs, under stop_gradient:
    load_entropy (normalized to [0, 1]; 1 = perfectly balanced),
    load_max_fraction (1/E = balanced, 1 = collapsed). `axis_names`: psum
    the per-expert load across shards first; `ci`: precomputed load."""
    if ci is None:
        ci = expert_load(probs, axis_names)
    e = probs.shape[-1]
    load = ci / jnp.maximum(jnp.sum(ci), 1e-9)
    entropy = -jnp.sum(load * jnp.log(load + 1e-9)) / jnp.log(float(e))
    return {"load_entropy": entropy, "load_max_fraction": jnp.max(load)}


def moe_expert_sliced_combine(
    x: jax.Array,
    probs: jax.Array,
    expert_fn,
    capacity: int,
    axis_name: str = "expert",
) -> jax.Array:
    """Expert-parallel MoE for shard_map bodies: the caller's expert
    weights are SHARDED over `axis_name` (each member holds E/ep experts)
    while tokens/probs are replicated across it. Each member dispatches its
    local expert columns (identical slot assignment to the unsharded
    dispatch, per-column independent), runs
    ``expert_fn((E_local, C, D), start)`` — `start` is the member's first
    global expert index, so callers slice their weight stacks by the SAME
    convention this op slices probs (contiguous blocks) — and the partial
    combines psum over the axis. No all_to_all needed — token replication
    over 'expert' makes EP a slice + reduce, composing freely with the
    data/context axes of the same shard_map. The experts run over every
    slot here (no fill count is handed on): a `pallas_call` inside this
    `shard_map` is not wired."""
    t, e = probs.shape
    ep = jax.lax.psum(1, axis_name)
    if e % ep:
        raise ValueError(f"{e} experts not divisible by '{axis_name}' axis {ep}")
    e_local = e // ep
    start = jax.lax.axis_index(axis_name) * e_local
    probs_local = jax.lax.dynamic_slice(probs, (0, start), (t, e_local))
    partial = moe_dispatch_combine(
        x, probs_local, lambda xe: expert_fn(xe, start), capacity
    )
    with jax.named_scope("L_moe_combine"):
        return jax.lax.psum(partial, axis_name)


def moe_all_to_all_combine(
    x: jax.Array,
    probs: jax.Array,
    expert_fn,
    capacity: int,
    axis_name: str = "expert",
) -> jax.Array:
    """Token-dispatch expert parallelism: tokens physically move to their
    experts over `axis_name` (SURVEY.md §2.3 EP row; the communication
    pattern the reference's distributed MoE would use, rebuilt on XLA
    collectives instead of NCCL).

    Contract (differs from moe_expert_sliced_combine, which replicates
    tokens): `x` (T_local, D) / `probs` (T_local, E) are this member's
    TOKEN SHARD over `axis_name`; expert weights are sharded over the same
    axis. Each member gathers its local tokens into per-expert capacity
    slots (E, C, D) through the slot maps, one tiled `all_to_all` ships
    each expert's slot block to its owner — landing as (E/ep, ep*C, D),
    slot blocks ordered by source member — the local expert matmul runs via
    ``expert_fn((E/ep, ep*C, D), start)`` (same `start` slicing convention
    as the sliced op), a second `all_to_all` ships results back to the
    slots' owners, and each member combines into its own (T_local, D).

    Bytes on the wire per member (one direction, elements): the two
    all_to_alls move 2*(ep-1)/ep * E*C*D ≈ 2*(ep-1)/ep * k*cf*T_local*D,
    i.e. only the routed capacity — vs the replicate+psum path whose
    combine all-reduce moves 2*(ep-1)/ep * T_full*D with T_full = ep *
    T_local. See `ep_comm_elements` for the accounting used by dryrun/bench.

    Capacity (and therefore dropping) is decided per member from its local
    token count — the standard distributed-MoE semantics, identical to how
    the sliced path decides drops per CP shard. In the drop-free regime the
    result equals `moe_dispatch_combine` over the gathered tokens exactly.

    The (E/ep, ep*C, D) layout holds one filled prefix PER SOURCE MEMBER, so
    a single fill count an expert would be wrong here: `expert_fn` is handed
    none and runs over every slot (ROADMAP S1).
    """
    t, e = probs.shape
    ep = jax.lax.psum(1, axis_name)
    if e % ep:
        raise ValueError(f"{e} experts not divisible by '{axis_name}' axis {ep}")
    e_local = e // ep
    start = jax.lax.axis_index(axis_name) * e_local

    routes, xe = _dispatch(x, probs, capacity)  # my tokens
    # ship: split the expert dim across members, concat received blocks
    # along the slot dim (source-member order) -> (E/ep, ep*C, D)
    with jax.named_scope("L_moe_dispatch"):
        xe = jax.lax.all_to_all(
            xe, axis_name, split_axis=0, concat_axis=1, tiled=True
        )
    with jax.named_scope("L_moe_experts"):
        ye = expert_fn(xe, start)  # (E/ep, ep*C, D) through MY experts
    # ship back: split the slot dim by destination member, concat along the
    # expert dim -> (E, C, D) with exactly my original slot layout
    with jax.named_scope("L_moe_combine"):
        ye = jax.lax.all_to_all(
            ye, axis_name, split_axis=1, concat_axis=0, tiled=True
        )
    return _weighted_combine(routes, probs, ye)


def ep_comm_elements(
    t_local: int, d: int, capacity: int, n_experts: int, ep: int
) -> dict[str, float]:
    """Per-member elements on the wire for one MoE layer's combine, for the
    two EP strategies (ring-collective model, one direction):

    * ``all_to_all``: two tiled all_to_alls of the (E, C, D) slot tensor —
      each ships (ep-1)/ep of it.
    * ``replicate_psum``: `moe_expert_sliced_combine`'s psum of the full
      (T_full, D) partial combine, T_full = ep * t_local (tokens are
      replicated across the axis), costing 2*(ep-1)/ep*T_full*D as a ring
      all-reduce (reduce-scatter + all-gather).

    Used by the dryrun/bench notes; ratios < 1 mean all_to_all moves less.
    """
    a2a = 2 * (ep - 1) / ep * n_experts * capacity * d
    psum = 2 * (ep - 1) / ep * (ep * t_local) * d
    return {
        "all_to_all": a2a,
        "replicate_psum": psum,
        "ratio": a2a / max(psum, 1.0),
    }


def moe_dense_combine(x: jax.Array, probs: jax.Array, expert_fn_all) -> jax.Array:
    """Drop-free reference path: run every expert on every token.

    `expert_fn_all((T, D)) -> (E, T, D)`. Exact semantics of the reference's
    per-expert loop; costs E/k times the dispatch path's FLOPs.
    """
    with jax.named_scope("L_moe_experts"):
        ye = expert_fn_all(x)  # (E, T, D)
    with jax.named_scope("L_moe_combine"):
        return jnp.einsum("te,etd->td", probs.astype(x.dtype), ye)
