"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

Not in the reference (SURVEY.md §2.3 lists PP as a TPU-native capability to
add; its parallelism ceiling is single-process DataParallel). Design: each
device along the `pipe` axis holds ONE stage's parameters (stacked arrays
with a leading stage dimension, sharded over the axis). Microbatches enter
at stage 0 and hop stage-to-stage via `lax.ppermute` over ICI; the schedule
runs `n_micro + n_stages - 1` ticks, every device computing each tick
(bubbles compute garbage that is masked out at collection). The classic
collective-permute pipelining recipe — compute and neighbor-transfer
overlap, no host involvement.

Capability scope: stage_fn is any pure function (params_stage, x) -> x with
matching input/output activation shapes (transformer blocks, MLP stacks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def vma_axes(x) -> frozenset:
    """Varying-manual-axes of `x` under the shard_map vma checker (empty
    outside shard_map and for axis-invariant values; every pcast in the
    schedules is gated on a nonempty result)."""
    return frozenset(jax.typeof(x).vma)


def shard_map_compat(fn, mesh, in_specs, out_specs, check_vma=None):
    """`jax.shard_map`, passing `check_vma` only when given (None keeps
    jax's default). The one spelling every shard_map in this repo goes
    through — exported from `solvingpapers_tpu.sharding`."""
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


# short internal aliases (the schedule bodies below use them heavily)
_vma = vma_axes
_shard_map = shard_map_compat


# ------------------------------------------------------ schedule algebra
#
# The tick math the schedules below implement, exposed as plain functions
# so the mesh observatory (metrics/mesh_obs.py) can label per-tick trace
# spans and compute bubble fractions without re-deriving (and drifting
# from) the schedule internals.


def schedule_ticks(n_microbatches: int, n_stages: int, n_virtual: int = 1,
                   schedule: str = "gpipe") -> int:
    """Scan length of one pipeline pass. GPipe/interleaved forward:
    m*v + P - 1 ticks; 1F1B (forward AND backward units interleaved):
    2(m + P) - 2 ticks, i.e. ~m + P - 1 full F+B unit-pairs."""
    if schedule == "1f1b":
        if n_virtual != 1:
            raise ValueError("1f1b does not compose with virtual stages")
        return 2 * (n_microbatches + n_stages) - 2
    return n_microbatches * n_virtual + n_stages - 1


def analytic_bubble_fraction(n_microbatches: int, n_stages: int,
                             n_virtual: int = 1) -> float:
    """The balanced-stage bubble fraction (P-1)/(m*v + P - 1): the share
    of a pipeline pass spent ramping/draining when every stage costs the
    same. Holds for the forward schedules tick-for-tick and for 1F1B in
    F+B unit-pairs (its steady state is bubble-free, the ramp is the
    same P-1 units)."""
    return (n_stages - 1) / (n_microbatches * n_virtual + n_stages - 1)


def tick_unit(t: int, device: int, n_microbatches: int, n_stages: int,
              n_virtual: int = 1, schedule: str = "gpipe") -> str:
    """Which unit device `device` computes at tick `t`: "F<i>" (forward,
    microbatch i), "B<i>" (1F1B backward), "F<i>.v<j>" (interleaved,
    virtual slice j), or "bubble" (ramp/drain garbage compute — this
    implementation's bubbles BURN a tick computing masked-out garbage,
    they do not idle). Mirrors the schedule bodies above exactly."""
    m, P, v = n_microbatches, n_stages, n_virtual
    if schedule == "1f1b":
        rel_f = t - device
        if rel_f >= 0 and rel_f % 2 == 0 and rel_f // 2 < m:
            return f"F{rel_f // 2}"
        rel_b = t - (2 * P - 1 - device)
        if rel_b >= 0 and rel_b % 2 == 0 and rel_b // 2 < m:
            return f"B{rel_b // 2}"
        return "bubble"
    rel = t - device
    if rel < 0 or rel >= m * v:
        return "bubble"
    if v == 1:
        return f"F{rel}"
    g = rel // (v * P)
    i = rel % P
    j = (rel % (v * P)) // P
    return f"F{g * P + i}.v{j}"


def _pipeline_local(stage_params, microbatches, stage_fn, axis_name,
                    with_aux: bool = False, rng=None):
    """Per-device body. stage_params: this stage's params (leading stage
    axis already stripped to size 1 by shard_map — squeezed here).
    microbatches: (n_micro, mb, ...) full input, replicated.

    with_aux: stage_fn returns (y, aux_pytree) and the schedule SUMS aux
    over this device's VALID ticks only (stage s computes real microbatches
    at ticks [s, s + n_micro); bubble ticks compute garbage that must not
    pollute statistics). Returns (out, aux_sum) — aux_sum covers exactly
    the full batch as seen by THIS device's stage (e.g. MoE routing loads
    for its layers); callers reduce across other mesh axes themselves.

    rng: when given, stage_fn is called as stage_fn(params, x, unit_rng)
    with unit_rng = fold_in(fold_in(rng, stage_id), microbatch_index) —
    the regenerable-seed recipe that makes DROPOUT well-defined under the
    schedule: at tick t stage s processes microbatch t - s, so the mask a
    (stage, microbatch) unit sees is a pure function of the fold chain and
    regenerates identically in the backward/remat replay (the same salting
    idea as the CP ring's per-(owner, chunk) kernel seeds).
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage_id = jax.lax.axis_index(axis_name)
    params = jax.tree.map(lambda a: a[0], stage_params)
    n_micro = microbatches.shape[0]
    ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    stage_rng = None if rng is None else jax.random.fold_in(rng, stage_id)

    # shard_map vma typing: carriers and the replicated input must be marked
    # varying over the pipe axis before mixing with per-device values — but
    # only when vma tracking is active; under check_vma=False the pcast's
    # TRANSPOSE (a psum over the axes) fails in the backward pass. Probe
    # tracking via the stage params, which enter sharded over the pipe axis
    # and therefore read as pipe-varying exactly when tracking is on.
    probe = jax.tree.leaves(stage_params)[0]
    tracking = axis_name in _vma(probe)
    if tracking and axis_name not in _vma(microbatches):
        microbatches = jax.lax.pcast(microbatches, (axis_name,), to="varying")
    buf = jnp.zeros_like(microbatches[0])  # current activation on this device
    out = jnp.zeros_like(microbatches)     # collected at the last stage

    def run_stage(params, incoming, unit_rng=None):
        if rng is None:
            res = stage_fn(params, incoming)
        else:
            res = stage_fn(params, incoming, unit_rng)
        return res if with_aux else (res, None)

    # aux structure probe (shapes only) for the scan carry init
    aux_shapes = (
        jax.eval_shape(
            lambda p, x: run_stage(p, x, stage_rng)[1], params, buf
        )
        if with_aux else None
    )

    def tick(carry, t):
        buf, out, aux_acc = carry
        # stage 0 ingests microbatch t (when in range); others use the
        # activation received from the previous stage
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        incoming = jnp.where(
            stage_id == 0,
            microbatches[mb_idx].astype(buf.dtype),
            buf,
        )
        unit_rng = None
        if rng is not None:
            # the microbatch THIS stage processes at tick t is t - stage_id
            # (bubble ticks clip to a valid index; their output is garbage
            # and masked at collection regardless)
            mb_cur = jnp.clip(t - stage_id, 0, n_micro - 1)
            unit_rng = jax.random.fold_in(stage_rng, mb_cur)
        y, aux = run_stage(params, incoming, unit_rng)
        if with_aux:
            # stage s holds real data at ticks [s, s + n_micro)
            valid = (t >= stage_id) & (t < stage_id + n_micro)
            aux_acc = jax.tree.map(
                lambda acc, a: acc + jnp.where(valid, a, 0.0).astype(acc.dtype),
                aux_acc, aux,
            )
        # the microbatch finishing at the last stage this tick is t-(S-1)
        done_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        is_valid = (stage_id == n_stages - 1) & (t >= n_stages - 1)
        updated = jax.lax.dynamic_update_index_in_dim(
            out, y.astype(out.dtype), done_idx, 0
        )
        out = jnp.where(is_valid, updated, out)
        # rotate activations one stage forward (last->0 wraps; ignored)
        buf = jax.lax.ppermute(y, axis_name, perm)
        return (buf, out, aux_acc), None

    def zero_like_shape(s):
        # the scan carry's vma type must match what run_stage produces
        # (varying over the pipe axis via stage params, and over the data
        # axes via the batch) — eval_shape carries the vma when tracking
        z = jnp.zeros(s.shape, jnp.float32)
        vma = tuple(getattr(s, "vma", ()) or ())
        return jax.lax.pcast(z, vma, to="varying") if vma else z

    aux0 = jax.tree.map(zero_like_shape, aux_shapes) if with_aux else None
    (_, out, aux_sum), _ = jax.lax.scan(
        tick, (buf, out, aux0), jnp.arange(ticks)
    )
    # only the last stage holds real outputs; psum broadcasts them (others zero)
    out = jnp.where(stage_id == n_stages - 1, out, jnp.zeros_like(out))
    out = jax.lax.psum(out, axis_name)
    return (out, aux_sum) if with_aux else out


def _pipeline_local_interleaved(stage_params, microbatches, stage_fn,
                                axis_name, n_virtual, rng=None,
                                with_aux: bool = False):
    """Interleaved (virtual-stage) schedule: device d holds `n_virtual`
    THIN stages (global stage j*P + d stored at local row j), microbatches
    enter in groups of P and loop the ring v times consecutively — the
    Megatron-style bubble shrink, forward-only form. Ticks = m*v + P - 1
    with every device busy except the P-1 ramp ticks, so the bubble
    fraction is (P-1)/(m*v + P - 1) — v times smaller than GPipe's at
    equal microbatch count (each tick does 1/v of a GPipe stage's work).

    Schedule algebra (conflict-free by construction): group g member i
    enters device 0 at tick g*v*P + i; after s total hops it sits on
    device s mod P running virtual slice s // P, i.e. device d at tick
    t holds the unit with (t - d) >= 0, g = (t-d) // (v*P),
    i = (t-d) % P, slice j = ((t-d) % (v*P)) // P. Device 0's ingest
    ticks (t % (v*P) < P) never collide with wrapped units, and group
    g+1's ingest lands exactly as group g's last loop leaves.

    stage_fn(stage_params_slice_j, x[, rng][, virtual_idx]) -> y (or
    (y, aux) with `with_aux`); requires n_micro % P == 0.

    with_aux: aux is accumulated into a leading (n_virtual,) stack — row j
    sums virtual slice j's n_micro VALID ticks (device d's row j covers
    global stage j*P + d; bubble ticks are masked out). Each (global
    stage, microbatch) unit runs exactly once across all valid ticks, so
    the stacked sums have the same per-stage coverage as the GPipe
    schedule's aux (callers scatter rows j -> storage row d*v + j).
    """
    n_stages = jax.lax.psum(1, axis_name)  # P devices
    d_id = jax.lax.axis_index(axis_name)
    params_v = stage_params  # already this device's (v, ...) local rows
    n_micro = microbatches.shape[0]
    vP = n_virtual * n_stages
    ticks = n_micro * n_virtual + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    probe = jax.tree.leaves(stage_params)[0]
    tracking = axis_name in _vma(probe)
    if tracking and axis_name not in _vma(microbatches):
        microbatches = jax.lax.pcast(microbatches, (axis_name,), to="varying")
    buf = jnp.zeros_like(microbatches[0])
    out = jnp.zeros_like(microbatches)

    def run_virtual(j, incoming, unit_rng):
        res = _apply_virtual(params_v, j, incoming, stage_fn, n_virtual,
                             unit_rng, rng_used=rng is not None)
        return res if with_aux else (res, None)

    aux_shapes = (
        jax.eval_shape(
            lambda p, x: run_virtual(jnp.zeros((), jnp.int32), x, rng)[1],
            params_v, buf,
        )
        if with_aux else None
    )

    def tick(carry, t):
        buf, out, aux_acc = carry
        rel = t - d_id  # hops since this device's current unit entered
        g = jnp.maximum(rel, 0) // vP
        i = jnp.maximum(rel, 0) % n_stages
        j = (jnp.maximum(rel, 0) % vP) // n_stages  # virtual slice index
        # device 0 ingests a NEW microbatch whenever its unit is at hop 0
        ingest = (d_id == 0) & (t % vP < n_stages)
        mb_idx = jnp.clip(g * n_stages + i, 0, n_micro - 1)
        incoming = jnp.where(
            ingest, microbatches[mb_idx].astype(buf.dtype), buf
        )
        unit_rng = None
        if rng is not None:
            # global stage of virtual slice j on device d is j*P + d;
            # fold (global stage, microbatch) exactly like _pipeline_local
            unit_rng = jax.random.fold_in(
                jax.random.fold_in(rng, j * n_stages + d_id), mb_idx
            )
        y, aux = run_virtual(j, incoming, unit_rng)
        if with_aux:
            # this device's unit is real for the first m*v ticks after its
            # ramp (rel in [0, m*v)) — every (slice, microbatch) pair once
            valid = (rel >= 0) & (rel < n_micro * n_virtual)

            def acc_row(acc, a):
                row = jax.lax.dynamic_index_in_dim(acc, j, 0, keepdims=False)
                row = row + jnp.where(valid, a, 0.0).astype(acc.dtype)
                return jax.lax.dynamic_update_index_in_dim(acc, row, j, 0)

            aux_acc = jax.tree.map(acc_row, aux_acc, aux)
        # unit completes at device P-1 on its last slice
        done = (
            (d_id == n_stages - 1)
            & (rel >= 0)
            & (rel % vP >= (n_virtual - 1) * n_stages)
            & (g * n_stages + i < n_micro)
        )
        updated = jax.lax.dynamic_update_index_in_dim(
            out, y.astype(out.dtype), mb_idx, 0
        )
        out = jnp.where(done, updated, out)
        buf = jax.lax.ppermute(y, axis_name, perm)
        return (buf, out, aux_acc), None

    def zero_stack_shape(s):
        # (n_virtual, *aux shape) accumulator matching run_virtual's vma
        z = jnp.zeros((n_virtual, *s.shape), jnp.float32)
        vma = tuple(getattr(s, "vma", ()) or ())
        return jax.lax.pcast(z, vma, to="varying") if vma else z

    aux0 = jax.tree.map(zero_stack_shape, aux_shapes) if with_aux else None
    (_, out, aux_sum), _ = jax.lax.scan(
        tick, (buf, out, aux0), jnp.arange(ticks)
    )
    out = jnp.where(d_id == n_stages - 1, out, jnp.zeros_like(out))
    out = jax.lax.psum(out, axis_name)
    return (out, aux_sum) if with_aux else out


def _apply_virtual(params_v, j, x, stage_fn, n_virtual, unit_rng=None,
                   rng_used=None):
    """Run stage_fn with this device's virtual-slice-j params. j is traced,
    so slice with lax.switch over the (python-static) v rows — a dynamic
    gather of a whole param subtree would copy it; switch lets XLA keep
    each branch's weights in place. Each branch passes its python-static
    slice index as `virtual_idx` so stage_fns that need the GLOBAL stage id
    (j*P + d — e.g. the flagship's routing-bias slicing) can derive it.
    `rng_used` distinguishes 'no rng this call' (None key) from 'schedule
    has no rng arg at all' (2-arg stage_fn); default: keyed iff unit_rng."""
    if rng_used is None:
        rng_used = unit_rng is not None
    if not rng_used:
        branches = [
            lambda x, jj=jj: stage_fn(
                jax.tree.map(lambda a: a[jj], params_v), x, virtual_idx=jj
            )
            for jj in range(n_virtual)
        ]
        return jax.lax.switch(j, branches, x)
    branches = [
        lambda x, r, jj=jj: stage_fn(
            jax.tree.map(lambda a: a[jj], params_v), x, r, virtual_idx=jj
        )
        for jj in range(n_virtual)
    ]
    return jax.lax.switch(j, branches, x, unit_rng)


def pipeline_local_apply(
    stage_params,
    x: jax.Array,
    stage_fn,
    *,
    n_microbatches: int,
    axis_name: str = "pipe",
    with_aux: bool = False,
    rng=None,
):
    """Per-device GPipe entry for callers already inside shard_map (e.g. a
    pipeline-parallel model's forward): splits x (batch, ...) into
    microbatches, runs the schedule, and restores the batch shape.
    stage_params is this device's stage slice (leading stage dim 1).
    With `with_aux`, stage_fn returns (y, aux) and this returns
    (out, aux_summed_over_valid_ticks) — see _pipeline_local.
    With `rng`, stage_fn is called as (params, x, unit_rng) — per-(stage,
    microbatch) dropout keys (see _pipeline_local)."""
    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError(f"batch {b} not divisible by {n_microbatches} microbatches")
    micro = x.reshape(n_microbatches, b // n_microbatches, *x.shape[1:])
    res = _pipeline_local(stage_params, micro, stage_fn, axis_name,
                          with_aux=with_aux, rng=rng)
    if with_aux:
        out, aux = res
        return out.reshape(b, *x.shape[1:]), aux
    return res.reshape(b, *x.shape[1:])


def pipeline_local_apply_interleaved(
    stage_params,
    x: jax.Array,
    stage_fn,
    *,
    n_microbatches: int,
    n_virtual: int,
    axis_name: str = "pipe",
    rng=None,
    with_aux: bool = False,
):
    """Per-device interleaved-schedule entry (see
    _pipeline_local_interleaved). stage_params: this device's (v, ...)
    virtual-slice rows. Does not compose with collectives inside stage_fn
    (slice selection is a data-dependent branch), so CP x interleaved is
    rejected at the model layer. With `rng`, stage_fn is called as
    (params, x, unit_rng, virtual_idx=j) keyed by (global stage,
    microbatch). With `with_aux`, stage_fn returns (y, aux) and this
    returns (out, aux stacked per virtual slice)."""
    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError(f"batch {b} not divisible by {n_microbatches} microbatches")
    micro = x.reshape(n_microbatches, b // n_microbatches, *x.shape[1:])
    res = _pipeline_local_interleaved(
        stage_params, micro, stage_fn, axis_name, n_virtual, rng=rng,
        with_aux=with_aux,
    )
    if with_aux:
        out, aux = res
        return out.reshape(b, *x.shape[1:]), aux
    return res.reshape(b, *x.shape[1:])


def pipeline_1f1b_value_and_grad(
    stage_params,
    head_params,
    microbatches: jax.Array,
    targets: jax.Array,
    stage_fn,
    loss_fn,
    axis_name: str = "pipe",
    rng=None,
    with_aux: bool = False,
):
    """One-forward-one-backward schedule (SURVEY.md §2.3 PP row): loss AND
    gradients in a single pass whose live activation memory is bounded by
    the PIPE DEPTH, not the microbatch count.

    With `rng`, stage_fn is called as (params, x, unit_rng) with
    unit_rng = fold_in(fold_in(rng, stage_id), microbatch) — the same
    regenerable-key recipe as the GPipe schedule, and because the
    backward unit derives the IDENTICAL key before its recompute-vjp,
    dropout masks regenerate exactly and the grads are the true grads of
    the masked forward.

    With `with_aux`, stage_fn returns (y, aux_pytree) and the schedule
    SUMS aux over this device's valid FORWARD units only (each (stage,
    microbatch) counted once; the backward recompute's aux is discarded)
    — the same per-stage coverage as `_pipeline_local`'s aux channel, for
    the flagship's MoE routing loads. An extra aux_sum is appended to the
    return tuple.

    GPipe (jax.grad over `_pipeline_local`'s scan) must stash every tick's
    residuals — activation memory grows with n_micro, which is exactly what
    `pp_grad_groups` works around by paying one fill+drain bubble per
    group. 1F1B instead schedules each microbatch's backward as soon as its
    loss exists: stage s runs forward i at tick s + 2i and backward i at
    tick 2P - 1 - s + 2i (the classic schedule in tick-synchronous SPMD
    form — F and B strictly alternate per device, so each device holds at
    most P stashed INPUTS and nothing else; the backward recomputes its
    stage forward from the stashed input, the same recompute GPipe-remat
    pays). Ticks total 2(m + P) - 3; the steady state is bubble-free.

    Per tick, uniformly on every device: one `lax.cond` (forward unit OR
    backward unit — dynamic branch, collective-free inside) then two
    ppermutes (activations downstream, cotangents upstream). The backward
    unit takes one vjp of

        where(is_last_stage, loss_fn(head, y, target), vdot(y, cot_in))

    so the LAST stage seeds the chain from its per-microbatch loss while
    the others pull the incoming cotangent through — and grads w.r.t.
    `head_params` are exactly zero on non-last stages (where-masked), so
    the pipe-psum recovers the true head gradient.

    Args: stage_params — this device's stage slice, leading dim 1 (same
    contract as `_pipeline_local`); head_params — the replicated loss head
    (e.g. final norm + lm head), threaded to `loss_fn`; microbatches
    (m, mb, ...) replicated inputs; targets (m, mb, ...) replicated;
    stage_fn(params, x) -> y shape-preserving; loss_fn(head_params, y,
    target) -> scalar MEAN loss of one microbatch (note: evaluated on
    every stage's backward unit and where-masked, so keep the head small
    relative to a stage — true for norm+vocab heads vs transformer
    stages at scale, and the price of a uniform SPMD program).

    Returns (loss, dstage_params, dhead_params, dmicrobatches): loss is
    the mean over microbatches; dstage_params has the input's leading-1
    stage dim (this device's stage); dhead_params is psum'd over the pipe
    (replicated, ready for the optimizer); dmicrobatches (m, mb, ...) is
    the cotangent w.r.t. `microbatches` (backprop it into the embedding
    outside), psum-broadcast from stage 0.

    Equality vs jax.grad over the sequential stage loop is pinned by
    tests/test_pipeline.py::test_1f1b_matches_sequential_grads.
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage_id = jax.lax.axis_index(axis_name)
    params = jax.tree.map(lambda a: a[0], stage_params)
    n_micro, mb = microbatches.shape[0], microbatches.shape[1:]
    # last backward is stage 0's B(0, m-1) at tick 2(m + P) - 3 inclusive
    ticks = 2 * (n_micro + n_stages) - 2
    down = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    up = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    is_last = stage_id == n_stages - 1

    probe = jax.tree.leaves(stage_params)[0]
    tracking = axis_name in _vma(probe)
    # the schedule's carries must be varying over the pipe axis AND over
    # whatever batch axes the inputs already vary over (under the Trainer
    # the microbatches enter data-sharded), or the cond branches/scan
    # carry would type-mismatch under the vma checker
    _target_vma = {axis_name}
    for _x in (microbatches, targets, *jax.tree.leaves(head_params)):
        _target_vma |= set(_vma(_x))

    def mark(x):
        if not tracking:
            return x
        missing = tuple(_target_vma - set(_vma(x)))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    microbatches = mark(microbatches)
    targets = mark(targets)
    head_params = jax.tree.map(mark, head_params)

    f32 = jnp.float32
    # the whole carry is inherently per-device data — mark it varying up
    # front so the two cond branches (and the scan) type-match under vma
    fwd_buf = mark(jnp.zeros(mb, f32))       # activation arriving from s-1
    bwd_buf = mark(jnp.zeros(mb, f32))       # cotangent arriving from s+1
    stash = mark(jnp.zeros((n_stages, *mb), f32))  # in-flight unit inputs
    dparams = jax.tree.map(lambda a: mark(jnp.zeros(a.shape, f32)), params)
    dhead = jax.tree.map(
        lambda a: mark(jnp.zeros(a.shape, f32)), head_params
    )
    dmicro = mark(jnp.zeros((n_micro, *mb), f32))
    loss_acc = mark(jnp.zeros((), f32))

    stage_rng = None if rng is None else jax.random.fold_in(rng, stage_id)

    def call_stage(p, x, mb_idx):
        if rng is None:
            res = stage_fn(p, x)
        else:
            res = stage_fn(p, x, jax.random.fold_in(stage_rng, mb_idx))
        return res if with_aux else (res, None)

    aux_shapes = (
        jax.eval_shape(
            lambda p, x: call_stage(p, x, jnp.zeros((), jnp.int32))[1],
            params, mark(jnp.zeros(mb, f32)).astype(probe.dtype),
        )
        if with_aux else None
    )
    aux0 = (
        jax.tree.map(
            lambda sh: mark(jnp.zeros(sh.shape, f32)), aux_shapes
        )
        if with_aux else None
    )

    def tick(carry, t):
        (fwd_buf, bwd_buf, stash, dparams, dhead, dmicro, loss_acc,
         aux_acc) = carry
        rel_f = t - stage_id
        i_f = rel_f // 2
        do_f = (rel_f >= 0) & (rel_f % 2 == 0) & (i_f < n_micro)
        rel_b = t - (2 * n_stages - 1 - stage_id)
        i_b = rel_b // 2
        do_b = (rel_b >= 0) & (rel_b % 2 == 0) & (i_b < n_micro)

        i_f_c = jnp.clip(i_f, 0, n_micro - 1)
        i_b_c = jnp.clip(i_b, 0, n_micro - 1)

        def fwd_unit(op):
            (fwd_buf, bwd_buf, stash, dparams, dhead, dmicro, loss_acc,
             aux_acc) = op
            x_in = jnp.where(
                stage_id == 0, microbatches[i_f_c].astype(f32), fwd_buf
            )
            # idle (ramp) ticks also land here with a clipped index — they
            # must NOT clobber a live slot another microbatch's backward
            # still needs
            stash = jnp.where(
                do_f,
                jax.lax.dynamic_update_index_in_dim(
                    stash, x_in, i_f_c % n_stages, 0
                ),
                stash,
            )
            y, aux = call_stage(params, x_in.astype(probe.dtype), i_f_c)
            y = y.astype(f32)
            if with_aux:
                # each real (stage, microbatch) forward counted once;
                # idle-tick garbage masked out
                aux_acc = jax.tree.map(
                    lambda acc, a: acc + jnp.where(do_f, a, 0.0).astype(
                        acc.dtype
                    ),
                    aux_acc, aux,
                )
            return jax.tree.map(mark, (
                y, jnp.zeros(mb, f32), stash, dparams, dhead, dmicro,
                loss_acc, aux_acc,
            ))

        def bwd_unit(op):
            (fwd_buf, bwd_buf, stash, dparams, dhead, dmicro, loss_acc,
             aux_acc) = op
            x_in = jax.lax.dynamic_index_in_dim(
                stash, i_b_c % n_stages, 0, keepdims=False
            )
            target = targets[i_b_c]

            def unit_scalar(p, hp, x, cot, target):
                # same key as the forward unit -> identical dropout masks
                # in the recompute, so the vjp is exact; the recompute's
                # aux is discarded (already counted at the forward unit)
                y, _ = call_stage(p, x.astype(probe.dtype), i_b_c)
                y = y.astype(f32)
                per_mb = loss_fn(hp, y, target)
                pulled = jnp.vdot(y, cot)
                return jnp.where(is_last, per_mb, pulled), (y, per_mb)

            primal, vjp, (_, per_mb) = jax.vjp(
                unit_scalar, params, head_params, x_in, bwd_buf, target,
                has_aux=True,
            )
            # the cotangent's varying-axes type must match the primal's
            ct = jnp.ones((), f32)
            vma = tuple(_vma(primal))
            if vma:
                ct = jax.lax.pcast(ct, vma, to="varying")
            dp, dh, dx, _, _ = vjp(ct)
            dparams = jax.tree.map(lambda a, b: a + b.astype(f32),
                                   dparams, dp)
            dhead = jax.tree.map(lambda a, b: a + b.astype(f32), dhead, dh)
            # stage 0's dx is the microbatch-input cotangent
            dmicro = jnp.where(
                stage_id == 0,
                jax.lax.dynamic_update_index_in_dim(dmicro, dx, i_b_c, 0),
                dmicro,
            )
            loss_acc = loss_acc + jnp.where(is_last, per_mb, 0.0)
            return jax.tree.map(mark, (
                jnp.zeros(mb, f32), dx, stash, dparams, dhead, dmicro,
                loss_acc, aux_acc,
            ))

        # F and B ticks strictly alternate per device, so exactly one (or
        # neither, in the ramp) runs; idle ticks take the fwd branch with a
        # clipped index and the result is never consumed
        res = jax.lax.cond(do_b, bwd_unit, fwd_unit,
                           (fwd_buf, bwd_buf, stash, dparams, dhead,
                            dmicro, loss_acc, aux_acc))
        (y_send, cot_send, stash, dparams, dhead, dmicro, loss_acc,
         aux_acc) = res
        y_send = jnp.where(do_f, y_send, jnp.zeros(mb, f32))
        cot_send = jnp.where(do_b, cot_send, jnp.zeros(mb, f32))
        fwd_buf = jax.lax.ppermute(y_send, axis_name, down)
        bwd_buf_new = jax.lax.ppermute(cot_send, axis_name, up)
        # a device KEEPS its pending cotangent until its B tick consumes
        # it: the sender's B tick is exactly 1 before ours, so overwrite
        # only when fresh data arrived (sender did B at tick t)
        sender_did_b = ((t - (2 * n_stages - 2 - stage_id)) >= 0) & (
            ((t - (2 * n_stages - 2 - stage_id)) % 2 == 0)
        )
        bwd_buf = jnp.where(sender_did_b, bwd_buf_new, bwd_buf)
        return (fwd_buf, bwd_buf, stash, dparams, dhead, dmicro,
                loss_acc, aux_acc), None

    carry0 = (fwd_buf, bwd_buf, stash, dparams, dhead, dmicro, loss_acc,
              aux0 if with_aux else mark(jnp.zeros(())))
    (fwd_buf, bwd_buf, stash, dparams, dhead, dmicro, loss_acc,
     aux_sum), _ = jax.lax.scan(tick, carry0, jnp.arange(ticks))
    loss = jax.lax.psum(
        jnp.where(is_last, loss_acc, 0.0), axis_name
    ) / n_micro
    dhead = jax.lax.psum(jax.tree.map(lambda a: a / n_micro, dhead),
                         axis_name)
    dmicro = jax.lax.psum(
        jnp.where(stage_id == 0, dmicro, jnp.zeros_like(dmicro)), axis_name
    ) / n_micro
    dstage = jax.tree.map(lambda a: (a / n_micro)[None], dparams)
    if with_aux:
        return loss, dstage, dhead, dmicro, aux_sum
    return loss, dstage, dhead, dmicro


def pipeline_apply(
    stage_params,
    x: jax.Array,
    stage_fn,
    mesh: Mesh,
    *,
    n_microbatches: int,
    axis_name: str = "pipe",
) -> jax.Array:
    """Run x (batch, ...) through n_stages sequential stages, pipelined.

    stage_params: pytree of stacked arrays with leading dim n_stages
    (sharded over `axis_name`). stage_fn(params_one_stage, x_mb) -> y_mb
    must preserve the activation shape. Batch must divide n_microbatches.
    Semantics: stage_{S-1}(...stage_1(stage_0(x))...) — verified against the
    sequential loop in tests/test_pipeline.py.
    """
    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError(f"batch {b} not divisible by {n_microbatches} microbatches")
    mb = b // n_microbatches
    micro = x.reshape(n_microbatches, mb, *x.shape[1:])

    params_spec = jax.tree.map(lambda _: P(axis_name), stage_params)
    fn = functools.partial(
        _pipeline_local, stage_fn=stage_fn, axis_name=axis_name
    )
    out = _shard_map(
        fn,
        mesh=mesh,
        in_specs=(params_spec, P()),
        out_specs=P(),
    )(stage_params, micro)
    return out.reshape(b, *x.shape[1:])


def stack_stage_params(per_stage_params: list) -> object:
    """[stage0_params, stage1_params, ...] -> stacked pytree with a leading
    stage axis (shard over the pipe axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)
