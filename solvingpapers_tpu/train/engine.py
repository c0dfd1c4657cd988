"""The training engine — one implementation of the loop every reference
notebook hand-rolls (gpt cell 18, llama3 cell 31, gemma cell 18,
deepseekv3 cell 54, kd.py:85-142, ViT cell 14, autoencoder cell 7).

Features (capability superset of deepseekv3's `train()`):
  * jitted, sharded train/eval steps over a ('data','fsdp','model','expert')
    mesh — DataParallel's replacement is a PartitionSpec, not a wrapper class
  * bf16 compute policy (replaces torch AMP/GradScaler — no loss scaling
    needed in bf16), grad accumulation (optax.MultiSteps), global-norm clip
  * warmup-cosine LR, periodic eval, periodic checkpointing with resume
  * metrics: loss, perplexity, lr, grad_norm, tokens, step_time,
    tokens/sec, MFU — wandb-compatible names via MetricsWriter sinks
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import math
import os
import time
import warnings
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from solvingpapers_tpu import ops
from solvingpapers_tpu.checkpoint import CheckpointManager
from solvingpapers_tpu.metrics import ConsoleWriter, MetricsWriter, hlo_cost
from solvingpapers_tpu.metrics import trace as run_trace
from solvingpapers_tpu.metrics.xla_obs import compile_spans
from solvingpapers_tpu.sharding import (
    LM_RULES,
    MeshConfig,
    ambient_mesh,
    batch_sharding,
    create_mesh,
    param_specs,
)
from solvingpapers_tpu.sharding.pipeline import shard_map_compat
from solvingpapers_tpu.train.optim import OptimizerConfig, make_optimizer
from solvingpapers_tpu.train.state import TrainState

# loss_fn(model, params, batch, rng, model_state, train) -> (loss, aux, new_model_state)
LossFn = Callable[..., tuple[jax.Array, dict, Any]]

# JAX's compile events go to the run's recorder from here on (every path
# to a Trainer imports this module before anything compiles)
_COMPILE_SPANS = compile_spans()
# `fit` calls of the process, counted: the first one's first step is
# start-up's last part
_FIT_CALLS = itertools.count(1)


def _pp_param_spec(path, _leaf) -> P:
    """shard_map in_spec for pipeline-parallel params: the stage-stacked
    subtree (top-level 'stages' key, models/gpt_pipe.py) over 'pipe',
    everything else replicated. One definition for both PP and CP+PP."""
    key = getattr(path[0], "key", None) if path else None
    return P("pipe") if key == "stages" else P()


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 1000
    batch_size: int = 32
    log_every: int = 50
    eval_every: int = 500
    eval_batches: int = 20
    ckpt_every: int = 0  # 0 = disabled
    checkpoint_dir: str | None = None
    keep_n: int = 3
    # periodic saves return after the device->host snapshot and write to
    # disk in a background thread (final/preemption saves always block);
    # safe with donated step buffers because Orbax completes the D2H copy
    # before save() returns
    async_checkpointing: bool = True
    seed: int = 0
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    tokens_per_step: int | None = None  # enables tokens/sec + MFU metrics
    flops_per_token: float | None = None
    # TPU-fast PRNG for dropout masks etc. (threefry bit-gen dominates the
    # reference GPT config's step time: 37.7 -> 25.9 ms/step on v5e with
    # rbg, same Bernoulli distribution, different stream). Applied to this
    # trainer's key stream only; None = the jax default (threefry). Note:
    # checkpoints store key data, so resume with the impl that wrote them.
    prng_impl: str | None = "rbg"
    # on-device training window: lax.scan `scan_steps` train steps per
    # dispatch (one host->device batch transfer of K stacked batches, one
    # fused XLA program). Amortizes per-step dispatch latency, which
    # weighs most on small models. Semantically
    # identical to K sequential steps (tests/test_engine.py pins equality);
    # log/eval/ckpt cadences must be multiples of scan_steps since the
    # host only sees window boundaries.
    scan_steps: int = 1
    # aux subsystems (SURVEY.md §5)
    debug_nans: bool = False  # jax_debug_nans: fail fast at the faulting op
    # jax.profiler trace of the steps [start, stop) counted from where
    # fit() starts: the device is fenced before the trace starts and before
    # it stops, so the window holds exactly stop - start whole executions
    # of the train step; the loop's spans (fit_setup, data_wait,
    # train_dispatch, log_fetch, log_write, eval, callback, checkpoint) are
    # TraceAnnotations on the host plane of the same file, each step under
    # a StepTraceAnnotation "train" (they are always emitted: any profiler
    # session, this one or a caller's own, holds them); `device_scopes.json`
    # beside the trace maps the programs' instructions to the layers of
    # metrics/hlo_cost.LAYER_SCOPES.
    profile_dir: str | None = None
    profile_steps: tuple[int, int] = (10, 15)
    # flight recorder (metrics/trace.py): record data-wait / step / eval /
    # checkpoint / callback spans on a "train" track and export a Chrome
    # trace-event JSON here when fit() ends (also on exceptions — the
    # post-mortem case). Adds a goodput metric (traced step time / wall:
    # the fraction of the run actually training vs waiting on data, eval,
    # and checkpoints). Observability mode: each dispatch is fenced with
    # block_until_ready so step spans are true durations — do not leave it
    # on for production throughput runs.
    trace_path: str | None = None
    # compile & memory observatory (metrics/xla_obs.py, opt-in): the
    # train/eval steps route through a CompileRegistry (every XLA
    # compilation recorded — signature, wall time, cost_analysis
    # flops/bytes — with recompile-storm flagging) and an HBMLedger
    # tracks params/opt_state live bytes + projected peak vs device
    # capacity; compile/* + mem/* + roofline/* gauges ride each logged
    # metrics row. Observability mode (steps are fenced like trace_path)
    # — leave off for production throughput runs.
    xla_obs: bool = False
    # mesh observatory (metrics/mesh_obs.py, opt-in): extends the
    # compile observatory with (1) a collective ledger — every compiled
    # program's HLO parsed for all-reduce/all-gather/reduce-scatter/
    # all-to-all/collective-permute ops, per-program comm bytes as
    # mesh/comm_* gauges; (2) pipeline-bubble diagnosis when
    # pipeline_parallel — each stage_fn probed standalone after the
    # compile step, analytic (S-1)/(M+S-1) vs measured bubble fraction
    # and the straggler stage in gauges, /statusz and trace-summary;
    # (3) per-device HBM ledger math (shard_shape bytes, not global);
    # (4) per-tick stage<N> trace tracks when trace_path is also set.
    # Implies the compile registry (xla_obs); observability mode —
    # steps are fenced, so leave off for production throughput runs.
    mesh_obs: bool = False
    # live /healthz /metrics /statusz endpoint during fit()
    # (metrics/http.py); port 0 = ephemeral, None = off
    status_port: int | None = None
    status_host: str = "127.0.0.1"
    # context parallelism: shard the sequence dim of (B, S) token batches
    # over the mesh 'context' axis and run the whole loss inside shard_map
    # (the model must be built with context_parallel=True so its attention
    # runs the ppermute ring / Ulysses all_to_all). Composes with the data
    # axes AND fsdp: params stay stored in their ZeRO layout (sharded over
    # 'fsdp') and are all-gathered inside the step, grads reduce-scatter.
    context_parallel: bool = False
    # pipeline parallelism: the model's stage-stacked decoder params (under
    # a top-level 'stages' key, models/gpt_pipe.py) are sharded over the
    # mesh 'pipe' axis and the loss runs inside shard_map with the GPipe
    # microbatch schedule. Composes with the data axis; use rules=PP_RULES.
    pipeline_parallel: bool = False
    # memory-bounded PP training: split the batch into `pp_grad_groups`
    # groups and run loss+backward PER GROUP in a lax.scan, accumulating
    # gradients — each group is one pipeline flush, so the backward's live
    # residuals cover ONE group's schedule ticks instead of the whole
    # batch's. With the model's n_microbatches set to the pipe size, live
    # activation memory scales with n_stages rather than the total
    # microbatch count (GPipe's weakness at depth); the price is one
    # fill+drain bubble per group. Gradients equal the single-flush step
    # up to fp reassociation (tests/test_pipeline_model.py pins this);
    # model_state (MoE routing bias) threads through groups sequentially.
    pp_grad_groups: int = 1
    # PP backward schedule: "gpipe" = jax.grad through the forward
    # schedule (activation memory grows with total microbatches; pair with
    # pp_grad_groups to bound it at the cost of per-group bubbles).
    # "1f1b" = one-forward-one-backward (sharding.pipeline
    # .pipeline_1f1b_value_and_grad): each microbatch's backward runs as
    # soon as its loss exists, bounding live activations by PIPE DEPTH
    # with no extra bubble. Requires a model exposing f1b_value_and_grad
    # (GPTPipe, LlamaPipe); dropout trains via per-(stage, microbatch)
    # regenerable keys; data x pipe meshes and the LM objective in v1.
    pp_schedule: str = "gpipe"


def lm_loss_fn(model, params, batch, rng, model_state, train):
    """Default LM objective: next-token CE on batch['x'] -> batch['y']."""
    logits, _ = model.apply(
        {"params": params},
        batch["x"],
        deterministic=not train,
        rngs={"dropout": rng} if train else None,
    )
    loss = ops.cross_entropy(logits, batch["y"])  # auto-chunks at scale
    return loss, {"perplexity": jnp.exp(loss)}, model_state


class Trainer:
    def __init__(
        self,
        model,
        config: TrainConfig,
        loss_fn: LossFn = lm_loss_fn,
        rules=LM_RULES,
        init_fn: Callable | None = None,
        mesh=None,
    ):
        self.model = model
        self.config = config
        # debug_nans is enabled inside fit() and restored on exit so the
        # process-global flag does not leak across Trainers; prng_impl is
        # scoped to this trainer's key stream (init_state), not the global
        self.loss_fn = loss_fn
        self.rules = rules
        with run_trace.run_span("trainer_init"):
            self.mesh = mesh if mesh is not None else create_mesh(config.mesh)
            self.tx, self.schedule = make_optimizer(config.optimizer)
        # init_fn(model, rngs, batch) -> params dict
        self.init_fn = init_fn or (
            lambda model, rngs, batch: model.init(rngs, batch["x"])["params"]
        )
        self._train_step = None
        self._train_step_scan = None
        self._eval_step = None
        self._state_shardings = None
        self._batch_shardings = None
        # compile & memory observatory (TrainConfig.xla_obs) and mesh
        # observatory (TrainConfig.mesh_obs); built in fit() so the
        # ledger can track the live TrainState
        self._registry = None
        self._ledger = None
        self._mesh_obs = None
        self._status = None
        # programs already made known to metrics/hlo_cost.py
        self._known_programs: set[str] = set()

    def _dispatch(self, name: str, jitted, state, batch):
        """Run a jitted step, through the compile registry when the
        observatory is on (signature = the batch's leaf shapes; the
        state's shapes are fixed after init) — one branch when off. A
        program's first dispatch leaves its abstract arguments with
        `hlo_cost.register_program`, under the name a profile shows."""
        if name not in self._known_programs:
            self._known_programs.add(name)
            hlo_cost.register_program(f"jit_{name}", jitted, (state, batch))
        if self._registry is None:
            return jitted(state, batch)
        key = tuple(
            (tuple(leaf.shape), str(leaf.dtype))
            for leaf in jax.tree_util.tree_leaves(batch)
        )
        return self._registry.call(name, key, jitted, (state, batch))

    # ------------------------------------------------------------------ init

    @run_trace.run_span("init_state")
    def init_state(self, example_batch: dict) -> TrainState:
        cfg = self.config

        def make(rng):
            p_rng, d_rng, s_rng = jax.random.split(rng, 3)
            rngs = {"params": p_rng, "dropout": d_rng}
            if cfg.context_parallel:
                # a CP model's forward calls axis collectives, so init must
                # also run inside shard_map; identical rngs/shapes on every
                # shard make the params replicated (out_specs P())
                out = shard_map_compat(
                    lambda r, b: self.init_fn(self.model, r, b),
                    mesh=self.mesh, in_specs=(P(), self._batch_specs()),
                    out_specs=P(), check_vma=self._check_vma(),
                )(rngs, example_batch)
            else:
                # PP models init their blocks on tiny unsharded dummies —
                # routing those through the sharded flash wrapper would be
                # wrong (and the real PP step runs inside shard_map, where
                # the direct kernel is correct); only GSPMD-partitioned
                # inits mark the mesh
                with ambient_mesh(
                    None if cfg.pipeline_parallel else self.mesh
                ):
                    out = self.init_fn(self.model, rngs, example_batch)
            # init_fn may return params alone or (params, model_state)
            params, model_state = out if isinstance(out, tuple) else (out, None)
            return TrainState.create(
                apply_fn=self.model.apply, params=params, tx=self.tx, rng=s_rng,
                model_state=model_state,
            )

        # the impl is carried by the key itself: split/fold_in preserve it,
        # so every dropout/init key in this trainer derives from it without
        # touching the process-global default
        rng = (
            jax.random.key(cfg.seed, impl=cfg.prng_impl)
            if cfg.prng_impl
            else jax.random.key(cfg.seed)
        )
        self._set_batch_shardings(example_batch)
        with run_trace.run_span("init_eval_shape"):
            abstract = jax.eval_shape(make, rng)
            specs = param_specs(abstract, self.rules, mesh=self.mesh)
            self._state_shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P),
            )
        with run_trace.run_span("init_jit"):
            # compile (or the cache's load) and the run; fenced here so that
            # the span holds them, where the state's first use did before
            state = jax.block_until_ready(
                jax.jit(make, out_shardings=self._state_shardings)(rng)
            )
        return state

    def _set_batch_shardings(self, example_batch: dict) -> None:
        """Record rank-appropriate batch shardings (x may be 2-D tokens or
        4-D images; y may be 2-D targets or 1-D labels). Under context
        parallelism, the sequence dim of rank-2 arrays under the
        sequence-aligned keys 'x'/'y' is sharded over 'context' in addition
        to the batch dim over (data, fsdp) — the key gate keeps a rank-2
        non-sequence array (e.g. (B, n_classes) soft labels) from being
        silently mis-sharded over 'context'."""
        cp = self.config.context_parallel

        def shard(path, a):
            key = getattr(path[0], "key", None) if path else None
            seq = cp and jnp.ndim(a) == 2 and key in ("x", "y")
            return batch_sharding(self.mesh, jnp.ndim(a) - 1, context=seq)

        self._batch_shardings = jax.tree_util.tree_map_with_path(
            shard, example_batch
        )

    def _batch_specs(self):
        """PartitionSpec pytree of the recorded batch shardings."""
        return jax.tree.map(
            lambda s: s.spec, self._batch_shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding),
        )

    # ------------------------------------------------------------------ steps

    def _cp_loss_call(self):
        """Build the context-parallel loss: the model applies inside
        shard_map with the sequence sharded over 'context' (its attention
        runs the ppermute ring / Ulysses all_to_all); params enter in their
        STORED layout — sharded over 'fsdp' (ZeRO) when that axis is > 1,
        replicated otherwise — and are all-gathered inside the step. The
        per-shard loss is pmean'd back to the global mean (equal shard
        sizes make that exact); gradients psum/reduce-scatter through
        shard_map's transpose automatically."""
        self._reject_axes(
            "context_parallel", ("model", "pipe"),
            "replicates params inside shard_map",
        )
        if not getattr(getattr(self.model, "cfg", None), "context_parallel", False):
            raise ValueError(
                "TrainConfig.context_parallel=True but the model was not "
                "built with context_parallel=True: it would attend only "
                "within each local sequence shard (no ring collectives, "
                "positions restarting at 0) and train a silently wrong "
                "objective"
            )
        # FSDP composes: params enter shard_map in their stored (sharded)
        # layout and are all-gathered over 'fsdp' inside the step — the
        # gather's transpose reduce-scatters the grads, i.e. ZeRO-3, so
        # per-device param memory stays 1/fsdp at rest. The 'expert' axis
        # composes as ZeRO over expert STORAGE (sharded at rest, gathered
        # in-step, grads reduce-scattered) plus sliced expert COMPUTE:
        # MoELayer under context_parallel dispatches only its E/ep expert
        # columns and psums the partial combines over 'expert'
        # (ops.moe.moe_expert_sliced_combine — flax validates param shapes
        # at apply, so slicing happens inside the layer after the gather,
        # not in the param pytree). Decorrelate dropout
        # across every shard: each holds a different (batch, seq) slice.
        # 'expert' is in the reduce axes only for typing: gathered expert
        # weights read as expert-varying (all_gather proves no invariance),
        # and the pmean — a numeric no-op across identical members — is
        # what certifies the out_specs P() replication
        return self._shard_map_loss_call(
            ("data", "fsdp", "context", "expert"), self._fsdp_param_specs(),
            rng_axes=("data", "fsdp", "context"), gather_fsdp=True,
        )

    def _fsdp_param_specs(self, axes: tuple = ("fsdp", "expert")):
        """(path, leaf) -> P giving each param's STORED layout restricted
        to `axes` (default 'fsdp' + 'expert') — derived from the same rule
        table/mesh as the state shardings, so it needs no init_state
        precondition (evaluate / fit with an external state build steps
        without one). The kept axes' dims are gathered in-step (ZeRO
        layout at rest). model/pipe are rejected above; their size-1 names
        in the rule table would otherwise mark values conservatively
        varying over those axes — the same reason the PP path passes
        axes=('fsdp',): its mesh rejects 'expert', and an all_gather over
        the size-1 axis would still type every expert-weight consumer as
        expert-varying, failing the out_specs P() contract."""
        from solvingpapers_tpu.sharding.rules import leaf_spec

        def keep(spec):
            def f(entry):
                names = entry if isinstance(entry, tuple) else (entry,)
                kept = tuple(n for n in names if n in axes)
                if len(kept) > 1:
                    # gather_param reassembles one name at a time, which
                    # would interleave a jointly-sharded dim's chunks in
                    # the wrong order — no shipped rule co-shards a dim
                    # over both axes, so refuse rather than corrupt
                    raise NotImplementedError(
                        f"dim jointly sharded over {kept} is not supported "
                        "by the in-step ZeRO gather"
                    )
                return kept[0] if kept else None

            return P(*(f(e) if e is not None else None for e in spec))

        return lambda path, leaf: keep(
            leaf_spec(path, leaf, self.rules, self.mesh)
        )

    def _pp_loss_call(self):
        """Build the pipeline-parallel loss: stage-stacked params (leading
        stage dim under 'stages') are sharded over 'pipe'; inside shard_map
        the model runs the GPipe ppermute schedule (models/gpt_pipe.py).
        Every pipe device computes the identical global loss (the pipeline
        output is psum-broadcast), so the pmean over 'pipe' is exact.

        FSDP composes: non-stage params (embedding/norm/head) enter in
        their stored fsdp layout and are all-gathered in-step (ZeRO —
        same mechanism as the CP path); stage params stay 'pipe'-local
        (the GPipe body wants exactly its own stage)."""
        self._reject_axes(
            "pipeline_parallel", ("model", "expert", "context"),
            "replicates non-stage params inside shard_map",
        )
        mcfg = getattr(self.model, "cfg", None)
        if not getattr(mcfg, "pipeline_parallel", False):
            raise ValueError(
                "TrainConfig.pipeline_parallel=True but the model was not "
                "built with pipeline_parallel=True: it would scan stages "
                "sequentially on every pipe device"
            )
        self._check_pp_stages(mcfg)
        # identical rng on every pipe device (they compute the same loss);
        # decorrelate only across data shards. The loss is already
        # invariant over 'pipe' (the pipeline output is psum-broadcast),
        # so only the data axes are reduced.
        return self._shard_map_loss_call(
            ("data", "fsdp"), self._pp_param_specs(),
            rng_axes=("data", "fsdp"), gather_fsdp=True,
        )

    def _pp_param_specs(self):
        """(path, leaf) -> P for PP in-specs: the stage-stacked subtree is
        sharded over 'pipe' (NOT gathered — each device's GPipe body uses
        its own stage), non-stage params carry their stored fsdp/expert
        layout and are all-gathered in-step by gather_param (which only
        touches the kept names, leaving 'pipe' dims local). 'expert' is
        excluded: the PP mesh rejects that axis (size 1), and gathering
        over it would only poison the vma typing (see _fsdp_param_specs)."""
        fsdp = self._fsdp_param_specs(axes=("fsdp",))

        def spec(path, leaf):
            if path and getattr(path[0], "key", None) == "stages":
                return P("pipe")
            return fsdp(path, leaf)

        return spec

    def _cp_pp_loss_call(self):
        """CP x PP composition: the sequence is sharded over 'context' AND
        the stage-stacked params over 'pipe' — each stage's attention runs
        the ppermute ring within its pipe coordinate's context group while
        microbatches hop stages (orthogonal axes, uniform schedule). The
        loss is invariant over 'pipe' (pipeline output psum-broadcast) and
        pmean'd over the data/context axes (the vma-aware pmean reduces
        exactly the axes each value varies over)."""
        self._reject_axes(
            "context_parallel+pipeline_parallel", ("fsdp", "model", "expert"),
            "replicates non-stage params inside shard_map",
        )
        mcfg = getattr(self.model, "cfg", None)
        for flag in ("context_parallel", "pipeline_parallel"):
            if not getattr(mcfg, flag, False):
                raise ValueError(
                    f"TrainConfig CP+PP but the model was not built with "
                    f"{flag}=True"
                )
        self._check_pp_stages(mcfg)
        return self._shard_map_loss_call(
            ("data", "fsdp", "context"), _pp_param_spec,
            rng_axes=("data", "fsdp", "context"),
        )

    def _check_pp_stages(self, mcfg) -> None:
        pipe = dict(zip(self.mesh.axis_names, self.mesh.devices.shape)).get("pipe", 1)
        v = getattr(mcfg, "virtual_stages", 1)
        if getattr(mcfg, "n_stages", None) != pipe * v:
            raise ValueError(
                f"model n_stages ({getattr(mcfg, 'n_stages', None)}) must "
                f"equal the mesh 'pipe' axis size ({pipe}) x virtual_stages "
                f"({v}): each device holds exactly virtual_stages slices"
            )

    def _reject_axes(self, mode: str, axes: tuple, why: str) -> None:
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        bad = {a: sizes[a] for a in axes if sizes.get(a, 1) > 1}
        if bad:
            raise NotImplementedError(
                f"{mode} {why} and does not compose with {bad} axes yet"
            )

    def _check_vma(self) -> bool:
        """vma checking must be off whenever the model's attention core is
        a pallas kernel: a pallas_call inside lax.scan under the jax-0.9
        vma checker KeyErrors in the closed_call lowering cache. One gate
        for every shard_map this Trainer builds (CP loss, PP loss, CP init)."""
        return not getattr(
            getattr(self.model, "cfg", None), "use_flash", False
        )

    def _pp_1f1b_vg_call(self):
        """Loss AND grads via the 1F1B schedule (TrainConfig.pp_schedule
        = "1f1b"): the model's f1b_value_and_grad runs inside shard_map —
        per-microbatch backwards interleaved with forwards, live
        activations bounded by pipe depth
        — so the engine consumes grads directly instead of wrapping the
        forward in jax.value_and_grad."""
        self._reject_axes(
            "pp_schedule='1f1b'", ("model", "expert", "context", "fsdp"),
            "v1 supports data x pipe meshes only",
        )
        mcfg = getattr(self.model, "cfg", None)
        if not getattr(mcfg, "pipeline_parallel", False):
            raise ValueError(
                "pp_schedule='1f1b' requires a model built with "
                "pipeline_parallel=True"
            )
        self._check_pp_stages(mcfg)
        if not hasattr(self.model, "f1b_value_and_grad"):
            raise NotImplementedError(
                f"{type(self.model).__name__} does not implement "
                "f1b_value_and_grad (GPTPipe and LlamaPipe do); use "
                "pp_schedule='gpipe'"
            )
        if getattr(mcfg, "virtual_stages", 1) != 1:
            raise NotImplementedError(
                "pp_schedule='1f1b' x virtual_stages is not composed; "
                "use pp_schedule='gpipe' for the interleaved schedule"
            )
        if self.config.pp_grad_groups > 1:
            raise NotImplementedError(
                "pp_schedule='1f1b' already bounds activation memory by "
                "pipe depth; pp_grad_groups adds only bubbles — use one "
                "or the other"
            )
        from solvingpapers_tpu.train.objectives import (
            dsv3_loss_fn as _dsv3_loss_fn,
        )

        if self.loss_fn is not lm_loss_fn and self.loss_fn is not _dsv3_loss_fn:
            raise NotImplementedError(
                "pp_schedule='1f1b' computes its objective inside the "
                "schedule (the model's f1b_value_and_grad), so a custom "
                "Trainer loss_fn would be silently ignored — use "
                "pp_schedule='gpipe' for custom objectives"
            )
        batch_specs = self._batch_specs()
        param_in_specs = self._pp_param_specs()

        def call(params, model_state, batch, rng):
            p_specs = jax.tree_util.tree_map_with_path(
                param_in_specs, params
            )

            sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
            n_shards = sizes.get("data", 1) * sizes.get("fsdp", 1)

            def mean_over_data(a):
                # every cross-shard reduction is explicit on this path:
                # psum the per-shard local value and divide by the shard
                # count (the mean the replicated-param grads need)
                return jax.lax.psum(a, ("data", "fsdp")) / n_shards

            def local(params, ms, batch, rng):
                # decorrelate dropout masks across data shards (pipe
                # devices share the key: they must agree on the masks the
                # schedule's units regenerate)
                rng = jax.random.fold_in(
                    rng, jax.lax.axis_index(("data", "fsdp"))
                )
                out = self.model.f1b_value_and_grad(
                    params, batch, rng=rng, model_state=ms
                )
                loss, grads, new_ms = out[0], out[1], out[2]
                # optional 4th element: extra train metrics (the
                # flagship's MoE routing stats)
                extra = out[3] if len(out) > 3 else {}
                loss = mean_over_data(loss)
                grads = jax.tree.map(mean_over_data, grads)
                aux = {
                    "perplexity": jnp.exp(loss),
                    **{k: mean_over_data(v) for k, v in extra.items()},
                }
                return loss, aux, grads, new_ms

            # check_vma OFF deliberately (not just for flash models): under
            # the vma checker, vjp cotangents w.r.t. data-replicated params
            # carry a pending cross-shard sum whose materialization point
            # differs per leaf (measured: stage-param grads came back
            # doubled after pmean while head grads did not) — with the
            # checker off the body has plain SPMD semantics, every device
            # holds its shard-local grads (verified against per-shard
            # oracles), and the ONE explicit psum/n above is the whole
            # cross-shard story.
            loss, aux, grads, new_ms = shard_map_compat(
                local, mesh=self.mesh,
                in_specs=(p_specs, P(), batch_specs, P()),
                out_specs=(P(), P(), p_specs, P()),
                check_vma=False,
            )(params, model_state, batch, rng)
            return loss, aux, new_ms, grads

        return call

    def _shard_map_loss_call(self, axes, param_in_specs, rng_axes,
                             gather_fsdp: bool = False):
        """Common shard_map loss wrapper for CP/PP. `param_in_specs` is a
        spec pytree/prefix, or a (path, leaf) -> P function evaluated
        against the abstract params at call time. With `gather_fsdp`, each
        param enters in its stored (sharded) layout and is all-gathered
        along the dims its spec shards before the model applies — the
        gather's transpose reduce-scatters the grads (ZeRO-3)."""
        batch_specs = self._batch_specs()
        check_vma = self._check_vma()

        def gather_param(p, spec):
            for dim, entry in enumerate(spec):
                if entry is None:
                    continue
                for name in (entry if isinstance(entry, tuple) else (entry,)):
                    # only ZeRO axes are gathered in-step; a 'pipe' entry
                    # (PP stage stacks) marks a dim that must STAY local
                    if name in ("fsdp", "expert"):
                        p = jax.lax.all_gather(p, name, axis=dim, tiled=True)
            return p

        def pmean(a):
            # aux may mix shard-varying values (per-shard loss terms) with
            # already-invariant ones (psum'd MoE stats). Under the vma
            # checker, reduce only the axes a value actually varies over;
            # with check_vma=False nothing is tracked, and the plain pmean
            # of an invariant value is a numeric no-op.
            if check_vma:
                ax = tuple(x for x in axes if x in jax.typeof(a).vma)
                return jax.lax.pmean(a, ax) if ax else a
            return jax.lax.pmean(a, axes)

        def call(params, model_state, batch, rng, train):
            if model_state is not None and not check_vma and getattr(
                getattr(self.model, "cfg", None), "stats_axes", None
            ) is None:
                # with vma checking off (flash models) the out_specs P()
                # contract below is unverified — require the model to
                # declare shard-invariant state updates explicitly, or a
                # per-shard-varying state would be silently mis-replicated
                raise NotImplementedError(
                    "model_state under shard_map without vma checking: the "
                    "model must declare shard-invariant state updates "
                    "(cfg.stats_axes, psum'd like DeepSeekV3's MoE load)"
                )
            p_specs = (
                jax.tree_util.tree_map_with_path(param_in_specs, params)
                if callable(param_in_specs)
                else param_in_specs
            )

            def local(params, ms, batch, rng):
                if gather_fsdp:
                    # p_specs nodes are matched whole at params' leaf
                    # boundary (flatten_up_to), so each leaf pairs with its P
                    params = jax.tree.map(gather_param, params, p_specs)
                rng = jax.random.fold_in(rng, jax.lax.axis_index(rng_axes))
                loss, aux, new_ms = self.loss_fn(
                    self.model, params, batch, rng, ms, train
                )
                loss = pmean(loss)
                if "perplexity" in aux:
                    # reduce in log space: exp of the global-mean MAIN CE
                    # (the loss fn's exp(main)), not the pmean of local
                    # exps — and not exp(total loss), which would fold MTP
                    # and balance aux terms into the reported perplexity
                    aux = dict(aux, perplexity=jnp.log(aux["perplexity"]))
                aux = jax.tree.map(pmean, aux)
                if "perplexity" in aux:
                    aux = dict(aux, perplexity=jnp.exp(aux["perplexity"]))
                return loss, aux, new_ms

            # model_state (e.g. the MoE routing bias) enters replicated and
            # must leave replicated: the model's in-step updates have to be
            # shard-invariant (psum'd loads — DeepSeekV3Config.stats_axes);
            # out_specs P() asserts that contract under the vma checker
            loss, aux, new_ms = shard_map_compat(
                local, mesh=self.mesh,
                in_specs=(p_specs, P(), batch_specs, P()),
                out_specs=(P(), P(), P()), check_vma=check_vma,
            )(params, model_state, batch, rng)
            return loss, aux, new_ms

        return call

    @run_trace.run_span("build_steps")
    def _build_steps(self):
        replicated = NamedSharding(self.mesh, P())
        if self.config.context_parallel and self.config.pipeline_parallel:
            loss_call = self._cp_pp_loss_call()
        elif self.config.context_parallel:
            loss_call = self._cp_loss_call()
        elif self.config.pipeline_parallel:
            loss_call = self._pp_loss_call()
        else:
            def loss_call(params, ms, batch, rng, train):
                # mark the GSPMD mesh while the model traces so use_flash
                # attention routes through the shard_map-wrapped kernel on
                # >1-device meshes (pallas_call is opaque to GSPMD — the
                # direct call would all-gather q/k/v)
                with ambient_mesh(self.mesh):
                    return self.loss_fn(self.model, params, batch, rng, ms, train)

        pp_groups = (
            self.config.pp_grad_groups if self.config.pipeline_parallel else 1
        )

        def grouped_value_and_grad(state, batch, step_rng):
            """Scan loss+backward over pp_grad_groups batch groups,
            accumulating grads — one pipeline flush per group, so the
            backward holds one group's residuals at a time (see
            TrainConfig.pp_grad_groups)."""
            bsz = jax.tree.leaves(batch)[0].shape[0]
            if bsz % pp_groups:
                raise ValueError(
                    f"batch {bsz} not divisible by pp_grad_groups {pp_groups}"
                )
            gbatch = jax.tree.map(
                lambda a: a.reshape(pp_groups, a.shape[0] // pp_groups,
                                    *a.shape[1:]),
                batch,
            )

            def body(carry, inp):
                ms, acc_loss, acc_aux, acc_g = carry
                gidx, grp = inp

                def loss_wrap(params):
                    loss, aux, new_ms = loss_call(
                        params, ms,
                        grp, jax.random.fold_in(step_rng, gidx), True,
                    )
                    return loss, (aux, new_ms)

                (l, (aux, new_ms)), g = jax.value_and_grad(
                    loss_wrap, has_aux=True
                )(state.params)
                if "perplexity" in aux:
                    # accumulate mean MAIN-CE (log of per-group ppl), not
                    # mean-of-exps — exponentiated back after the scan.
                    # exp(total loss) would be wrong for objectives whose
                    # total carries aux terms (MTP, balance)
                    aux = dict(aux, perplexity=jnp.log(aux["perplexity"]))
                acc_g = jax.tree.map(lambda a, b: a + b / pp_groups, acc_g, g)
                acc_aux = jax.tree.map(
                    lambda a, b: a + b / pp_groups, acc_aux, aux
                )
                return (new_ms, acc_loss + l / pp_groups, acc_aux, acc_g), None

            aux_shape = jax.eval_shape(
                lambda p: loss_call(p, state.model_state,
                                    jax.tree.map(lambda a: a[0], gbatch),
                                    step_rng, True)[1],
                state.params,
            )
            carry0 = (
                state.model_state,
                jnp.zeros(()),
                jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), aux_shape),
                jax.tree.map(jnp.zeros_like, state.params),
            )
            (new_ms, loss, aux, grads), _ = jax.lax.scan(
                body, carry0, (jnp.arange(pp_groups), gbatch)
            )
            if "perplexity" in aux:
                # exp of the accumulated mean main-CE (see body)
                aux = dict(aux, perplexity=jnp.exp(aux["perplexity"]))
            return loss, aux, new_ms, grads

        if self.config.pp_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"pp_schedule must be 'gpipe' or '1f1b', got "
                f"{self.config.pp_schedule!r}"
            )
        if (self.config.pp_schedule == "1f1b"
                and not self.config.pipeline_parallel):
            raise ValueError(
                "pp_schedule='1f1b' requires pipeline_parallel=True — "
                "without it the config would silently train on the plain "
                "data-parallel path"
            )
        pp_1f1b_vg = (
            self._pp_1f1b_vg_call()
            if self.config.pipeline_parallel
            and self.config.pp_schedule == "1f1b"
            else None
        )

        def train_step(state: TrainState, batch: dict):
            step_rng = jax.random.fold_in(state.rng, state.step)

            if pp_1f1b_vg is not None:
                loss, aux, new_ms, grads = pp_1f1b_vg(
                    state.params, state.model_state, batch, step_rng
                )
            elif pp_groups > 1:
                loss, aux, new_ms, grads = grouped_value_and_grad(
                    state, batch, step_rng
                )
            else:
                def loss_wrap(params):
                    loss, aux, new_ms = loss_call(
                        params, state.model_state, batch, step_rng, True
                    )
                    return loss, (aux, new_ms)

                (loss, (aux, new_ms)), grads = jax.value_and_grad(
                    loss_wrap, has_aux=True
                )(state.params)
            with jax.named_scope("L_optimizer"):
                grad_norm = optax.global_norm(grads)
                new_state = state.apply_gradients(grads, new_ms)
                lr = self.schedule(state.step)
            metrics = {
                "train_loss": loss,
                "grad_norm": grad_norm,
                "lr": lr,
                **{f"train_{k}": v for k, v in aux.items()},
            }
            return new_state, metrics

        def eval_step(state: TrainState, batch: dict):
            loss, aux, _ = loss_call(
                state.params, state.model_state, batch, state.rng, False
            )
            return {"val_loss": loss, **{f"val_{k}": v for k, v in aux.items()}}

        if self._batch_shardings is None:
            raise RuntimeError(
                "batch shardings unknown: call init_state(example_batch) or "
                "fit() (which derives them from the first batch) before "
                "building steps"
            )
        data_sharding = self._batch_shardings
        self._train_step = jax.jit(
            train_step,
            in_shardings=(self._state_shardings, data_sharding),
            out_shardings=(self._state_shardings, replicated),
            donate_argnums=0,
        )
        self._eval_step = jax.jit(
            eval_step,
            in_shardings=(self._state_shardings, data_sharding),
            out_shardings=replicated,
        )

        if self.config.scan_steps > 1:
            def train_step_scan(state: TrainState, batches: dict):
                # batches: the per-step batch pytree with a stacked leading
                # K dim. Each scan iteration is bit-identical to one
                # _train_step call (same per-step rng fold on state.step);
                # returned metrics are the LAST step's — what a per-step
                # loop would log at the window boundary.
                new_state, ms = jax.lax.scan(train_step, state, batches)
                return new_state, jax.tree.map(lambda x: x[-1], ms)

            scan_shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, P(None, *s.spec)),
                data_sharding,
            )
            self._train_step_scan = jax.jit(
                train_step_scan,
                in_shardings=(self._state_shardings, scan_shardings),
                out_shardings=(self._state_shardings, replicated),
                donate_argnums=0,
            )

    # ------------------------------------------------------------------ fit

    def fit(
        self,
        batch_iter: Iterator[dict],
        eval_iter_fn: Callable[[], Iterator[dict]] | None = None,
        writer: MetricsWriter | None = None,
        state: TrainState | None = None,
        callbacks: list[tuple[int, Callable]] | None = None,
    ) -> TrainState:
        """`callbacks`: [(every, fn(state, step))] — periodic hooks for
        qualitative eval (e.g. deepseekv3 cell 54's sample-and-save-text
        every 500 steps); exceptions propagate."""
        cfg = self.config
        # fit() already gates writes by log_every; the writer must not
        # re-filter or eval/final-step writes would be dropped
        writer = writer or ConsoleWriter()
        # flight recorder (TrainConfig.trace_path): spans for everything
        # the loop blocks on, exported in the finally below. step spans
        # fence each dispatch (see the config docstring), so goodput =
        # traced-step-time / wall is an honest utilization number.
        recorder = None
        step_span_total = 0.0
        t_fit0 = 0.0
        if cfg.trace_path:
            from solvingpapers_tpu.metrics.trace import FlightRecorder

            recorder = FlightRecorder()
            t_fit0 = recorder.clock()

        # every section the loop spends host time in is a
        # jax.profiler.TraceAnnotation: whoever runs a profiler session
        # (`profile_dir` here, or a caller that knows nothing of it) finds
        # them in the profiler's own file on the device operations' clock;
        # with no session an annotation is the profiler's own no-op
        @contextlib.contextmanager
        def _recorded(name, kw):
            with jax.profiler.TraceAnnotation(name, **kw), \
                    recorder.span(name, "train", "train", **kw):
                yield

        def _span(name, **kw):
            """The one instrumented-section helper: a profiler annotation
            and, with `trace_path`, the recorder's span beside it."""
            if recorder is None:
                return jax.profiler.TraceAnnotation(name, **kw)
            return _recorded(name, kw)

        def _step_scope(step_num):
            return jax.profiler.StepTraceAnnotation("train", step_num=step_num)

        # host seconds since the last logged row: waiting for the batch
        # iterator, and blocked on the device (the fetch at the log cadence
        # and the fences before eval, callbacks and checkpoints)
        wait_s = 0.0
        blocked_s = 0.0
        # what an untraced run can still say of one slow step: the longest
        # single dispatch since the last row, and the longest stretch from
        # the end of one dispatch to the start of the next less what the
        # loop spent blocked on the device in it (data, logging, eval, a
        # collector's pause), each with the step it was dispatching
        dispatch_max = (0.0, 0)
        gap_max = (0.0, 0)
        t_dispatched = None  # perf_counter at the last dispatch's return
        blocked_since_dispatch = 0.0

        def _next(it):
            nonlocal wait_s
            t0 = time.perf_counter()
            with _span("data_wait"):
                batch = next(it)
            wait_s += time.perf_counter() - t0
            return batch

        def _fence():
            """Wait for the steps in flight (not counted as loop time)."""
            t0 = time.perf_counter()
            jax.device_get(metrics["train_loss"])
            _blocked(time.perf_counter() - t0)

        def _blocked(seconds):
            nonlocal blocked_s, blocked_since_dispatch
            blocked_s += seconds
            blocked_since_dispatch += seconds

        def _stop_profile() -> float:
            """Close the profile between two steps of the device and leave
            the programs' layer map beside it; returns the seconds spent
            (the map costs a compile of each program that ran)."""
            t0 = time.perf_counter()
            jax.block_until_ready(state)
            jax.profiler.stop_trace()
            scopes = {
                f"jit_{name}": hlo_cost.program_scopes(f"jit_{name}")
                for name in sorted(self._known_programs)
            }
            with open(
                os.path.join(cfg.profile_dir, "device_scopes.json"), "w"
            ) as f:
                json.dump(scopes, f)
            return time.perf_counter() - t0

        # what comes before the loop (state, steps, observatories, the
        # checkpoint's restore) has a name in a profile too
        with jax.profiler.TraceAnnotation("fit_setup"):
            if state is None:
                first = _next(batch_iter)
                state = self.init_state(first)
            else:
                first = _next(batch_iter) if self._batch_shardings is None else None
                if first is not None:
                    self._set_batch_shardings(first)
            if self._train_step is None:
                self._build_steps()

            if (cfg.xla_obs or cfg.mesh_obs) and self._registry is None:
                from solvingpapers_tpu.metrics.xla_obs import (
                    CompileRegistry,
                    HBMLedger,
                    pytree_device_bytes,
                )

                # mesh_obs implies the compile registry (the collective
                # ledger reads compiled HLO) with per-program HLO parsing on
                self._registry = CompileRegistry(trace=recorder,
                                                 collectives=cfg.mesh_obs)
                self._ledger = HBMLedger()
                # the lambdas close over the loop variable `state`, so the
                # gauges follow the live TrainState across step rebinding;
                # PER-DEVICE bytes (shard_shape), not global — capacity is a
                # per-chip number and fsdp/pipe-sharded pools must not book
                # their full global size against it
                self._ledger.register(
                    "params", lambda: pytree_device_bytes(state.params)
                )
                self._ledger.register(
                    "opt_state", lambda: pytree_device_bytes(state.opt_state)
                )
                self._ledger.temp_fn = self._registry.max_temp_bytes
            if cfg.mesh_obs and self._mesh_obs is None:
                from solvingpapers_tpu.metrics.mesh_obs import (
                    MeshObservatory,
                    PipelineScheduleInfo,
                )
                from solvingpapers_tpu.sharding import mesh_axis_sizes

                sched = None
                mcfg = getattr(self.model, "cfg", None)
                if cfg.pipeline_parallel and mcfg is not None:
                    sched = PipelineScheduleInfo(
                        n_stages=mesh_axis_sizes(self.mesh).get("pipe", 1),
                        n_microbatches=getattr(mcfg, "n_microbatches", 1),
                        n_virtual=getattr(mcfg, "virtual_stages", 1),
                        schedule=cfg.pp_schedule,
                    )
                self._mesh_obs = MeshObservatory(
                    mesh=self.mesh, registry=self._registry, trace=recorder,
                    schedule=sched,
                )
            # registry/observatory persist across fit() calls but the
            # recorder is per-run: re-attach so a resumed fit's compile and
            # mesh events land in ITS trace, not the first run's dead ring
            if self._registry is not None:
                self._registry.trace = recorder
            if self._mesh_obs is not None:
                self._mesh_obs.attach_trace(recorder)
            # observability modes fence every dispatch so step walls are
            # device-true; _obs_clock is the shared time base
            _fenced = recorder is not None or self._mesh_obs is not None
            _obs_clock = (
                recorder.clock if recorder is not None
                else self._mesh_obs.clock if self._mesh_obs is not None
                else None
            )
            # live status endpoint for the duration of fit(); last_row is
            # mutated at every log write so /metrics and /statusz always
            # serve the newest row without re-deriving device values
            last_row = {"step": int(jax.device_get(state.step)), "metrics": {}}
            if cfg.status_port is not None:
                from solvingpapers_tpu.metrics.http import StatusServer

                def _statusz() -> dict:
                    d = {
                        "train": {"step": last_row["step"],
                                  "steps_total": cfg.steps},
                        "metrics": last_row["metrics"],
                    }
                    if self._registry is not None:
                        d["compile"] = self._registry.snapshot()
                    if self._ledger is not None:
                        d["mem"] = self._ledger.snapshot()
                    if self._mesh_obs is not None:
                        d["mesh"] = self._mesh_obs.snapshot()
                    return d

                def _metrics_fn() -> tuple[int, dict]:
                    m = dict(last_row["metrics"])
                    if self._registry is not None:
                        m.update(self._registry.gauges())
                        m.update(self._ledger.gauges())
                    if self._mesh_obs is not None:
                        m.update(self._mesh_obs.gauges())
                    return last_row["step"], m

                self._status = StatusServer(
                    _statusz, _metrics_fn,
                    host=cfg.status_host, port=cfg.status_port,
                )

            ckpt = None
            start_step = int(jax.device_get(state.step))
            if cfg.checkpoint_dir and cfg.ckpt_every > 0:
                ckpt = CheckpointManager(cfg.checkpoint_dir, cfg.keep_n, cfg.ckpt_every,
                                         async_saves=cfg.async_checkpointing)
                restored = ckpt.restore_latest(_pure_state(state))
                if restored is not None:
                    pure, start_step = restored
                    state = _apply_pure(state, pure)

            # preemption handling: SIGTERM/SIGINT request a final checkpoint at
            # the next step boundary (the auto-resume path restores it — the
            # workflow the reference performs by hand after Kaggle preemptions)
            preempted = {"flag": False}
            old_handlers = {}
            if ckpt is not None:
                import signal

                def _on_signal(signum, frame):
                    preempted["flag"] = True

                for sig in (signal.SIGTERM, signal.SIGINT):
                    try:
                        old_handlers[sig] = signal.signal(sig, _on_signal)
                    except ValueError:  # non-main thread
                        break

            profiling = False
            nan_debug_prev = None
            if cfg.debug_nans:
                nan_debug_prev = jax.config.jax_debug_nans
                jax.config.update("jax_debug_nans", True)
            t_prev = time.perf_counter()
            last_log_step = start_step
            scan_k = max(cfg.scan_steps, 1)
            if scan_k > 1:
                cadences = [("log_every", cfg.log_every),
                            ("eval_every", cfg.eval_every),
                            ("ckpt_every", cfg.ckpt_every)]
                cadences += [
                    (f"callbacks[{i}].every", every)
                    for i, (every, _) in enumerate(callbacks or [])
                ]
                for nm, ev in cadences:
                    if ev > 0 and ev % scan_k:
                        raise ValueError(
                            f"{nm}={ev} must be a multiple of scan_steps="
                            f"{scan_k}: the host only sees window boundaries"
                        )
            profile_stopped = False
            warmed = set()  # the step programs this call has run
            excluded_steps = 0  # steps whose wall time was excluded since last log
            fit_call = next(_FIT_CALLS)
            # the process's recompiles when this call began, and at its
            # last row
            recompiles_0 = _COMPILE_SPANS.recompiles_after_first_step
            recompiles_row = 0
        first_step_scope = None  # the open `fit_first_step` span
        try:
            step = start_step
            while step < cfg.steps:
                # full scan windows on scan_k-aligned steps; single-step to
                # re-align (a checkpoint resume can start mid-window) and
                # through the ragged tail, so cfg.steps is hit exactly and
                # window ends stay multiples of scan_k (the cadence checks
                # depend on that)
                if step % scan_k or step + scan_k > cfg.steps:
                    kk = 1
                else:
                    kk = scan_k
                end = step + kk
                if preempted["flag"]:
                    ckpt.maybe_save(step, _pure_state(state), force=True)
                    writer.write(step, {"preempted": 1.0})
                    break
                # stop BEFORE the start check: when the profile window fits
                # inside one scan window, checking start first would open
                # and immediately close an empty trace in the same iteration
                if profiling and step - start_step >= cfg.profile_steps[1]:
                    t_prev += _stop_profile()
                    profiling = False
                    profile_stopped = True
                if cfg.profile_dir and not profiling and not profile_stopped \
                        and step - start_step >= cfg.profile_steps[0]:
                    # the host runs steps ahead of the device: fence, so
                    # the trace starts between two steps
                    t_prof = time.perf_counter()
                    jax.block_until_ready(state)
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0  # the loop's spans suffice
                    jax.profiler.start_trace(cfg.profile_dir,
                                             profiler_options=opts)
                    profiling = True
                    t_prev += time.perf_counter() - t_prof
                with _step_scope(step):
                    window = []
                    if first is not None and step == start_step:
                        window.append(first)
                        first = None
                    while len(window) < kk:
                        window.append(_next(batch_iter))
                    if kk == 1:
                        batch = window[0]
                        name, jitted = "train_step", self._train_step
                    else:
                        # device arrays (e.g. lm_batch_iterator's on-device
                        # crops) stack with jnp — np.stack would force K
                        # synchronous D2H pulls per window; host arrays
                        # stack on host so the window ships as ONE transfer
                        batch = jax.tree.map(
                            lambda *xs: (
                                jnp.stack(xs) if isinstance(xs[0], jax.Array)
                                else np.stack(xs)
                            ),
                            *window,
                        )
                        name, jitted = (
                            "train_step_scan", self._train_step_scan
                        )
                    exclude_compile = (
                        scan_k > 1 and name not in warmed
                        and step != start_step
                    )
                    if exclude_compile:
                        # a scan-windowed run's first call of its other
                        # program (the single step of the ragged tail, or
                        # the window after a resume's re-aligning steps): it
                        # has not been traced yet, so fence and keep its
                        # compile out of the step timing, like eval/checkpoint
                        _fence()
                        t_tail = time.perf_counter()
                    t_span = _obs_clock() if _fenced else 0.0
                    if step == start_step:
                        # start-up's last part: the call's first dispatch
                        # (trace, lower, compile or cache load inside it)
                        # to the fetch that fences it below
                        first_step_scope = run_trace.run_span(
                            "fit_first_step", fit=fit_call, step=end
                        )
                        first_step_scope.__enter__()
                    t_dispatch = time.perf_counter()
                    if t_dispatched is not None:
                        gap = (t_dispatch - t_dispatched
                               - blocked_since_dispatch)
                        if gap > gap_max[0]:
                            gap_max = (gap, end)
                    # a program that compiles inside a timed step's dispatch
                    # is a recompile; the call's first step and a scan run's
                    # first tail step compile by design, out of the timing
                    timed = step != start_step and not exclude_compile
                    with _span("train_dispatch"), \
                            _COMPILE_SPANS.steady(end if timed else None):
                        state, metrics = self._dispatch(
                            name, jitted, state, batch
                        )
                    t_dispatched = time.perf_counter()
                    blocked_since_dispatch = 0.0
                    if t_dispatched - t_dispatch > dispatch_max[0]:
                        dispatch_max = (t_dispatched - t_dispatch, end)
                    if _fenced:
                        t_block = time.perf_counter()
                        jax.block_until_ready(metrics)
                        _blocked(time.perf_counter() - t_block)
                        d_span = _obs_clock() - t_span
                        compiled = step == start_step
                        if recorder is not None:
                            recorder.complete("step", "train", "train",
                                              ts=t_span, dur=d_span, steps=kk,
                                              compiled=int(compiled))
                        if not compiled:
                            # goodput's numerator counts TRAINING time;
                            # folding the first step's jit compile in
                            # would report ~1.0 on a run that spent most
                            # of its wall compiling (the wall stays in
                            # the denominator, so compile-dominated runs
                            # honestly read as low goodput)
                            step_span_total += d_span
                            if self._mesh_obs is not None:
                                self._mesh_obs.observe_step(
                                    t_span, d_span, steps=kk
                                )
                    if exclude_compile:
                        jax.device_get(metrics["train_loss"])
                        t_prev += time.perf_counter() - t_tail
                        # the step's time is excluded, so drop it from the
                        # next log row's denominator too (else step_time /
                        # tokens_per_sec overstate by the excluded step)
                        excluded_steps += kk
                    warmed.add(name)
                    if step == start_step:
                        # fence the first step so compile time never pollutes
                        # step_time/tokens_per_sec/MFU metrics; the timed
                        # window therefore starts at the NEXT step
                        jax.device_get(metrics["train_loss"])
                        first_step_scope.__exit__(None, None, None)
                        first_step_scope = None
                if step == start_step:
                    if fit_call == 1:
                        # what the process spent before it trained: one row
                        with _span("log_write", step=end):
                            writer.write(end, {
                                f"startup/{k}": float(v) for k, v in
                                run_trace.summarize_startup(
                                    run_trace.RUN.events()).items()
                            })
                    if self._mesh_obs is not None and cfg.pipeline_parallel:
                        # one-time stage probe for the bubble report,
                        # after the compile step (params live, jit warm)
                        # and before t_prev resets so its wall never
                        # leaks into step timing
                        self._probe_pipeline_stages(state, batch)
                    t_prev = time.perf_counter()
                    last_log_step = end
                    wait_s = blocked_s = 0.0
                    dispatch_max = gap_max = (0.0, 0)
                    t_dispatched = None

                run_eval = (
                    cfg.eval_every > 0 and eval_iter_fn
                    and end % cfg.eval_every == 0
                )
                run_cbs = callbacks and any(
                    every > 0 and end % every == 0 for every, _ in callbacks
                )
                if run_eval or run_cbs:
                    # fence queued async train steps BEFORE starting the
                    # excluded-time window: evaluate()/callbacks force them
                    # to completion via their data dependency on `state`,
                    # and without the fence that train time would be
                    # misattributed to eval and subtracted from the step
                    # timing (the source of impossible tokens/sec spikes on
                    # eval-aligned log rows)
                    _fence()
                if run_eval:
                    t_eval = time.perf_counter()
                    with _span("eval", step=end):
                        val = self.evaluate(state, eval_iter_fn())
                    writer.write(end, {k: float(v) for k, v in val.items()})
                    t_prev += time.perf_counter() - t_eval  # keep eval out of step timing

                if run_cbs:
                    t_cb = time.perf_counter()
                    for every, fn in callbacks:
                        if every > 0 and end % every == 0:
                            with _span("callback", step=end):
                                fn(state, end)
                    t_prev += time.perf_counter() - t_cb

                if end % max(cfg.log_every, 1) == 0 or end == cfg.steps:
                    # the one place the loop blocks; also fences timing
                    t_fetch = time.perf_counter()
                    with _span("log_fetch", step=end):
                        metrics = jax.device_get(metrics)
                    if step == start_step:
                        # the compile step is excluded from the timed window;
                        # report its metrics without timing-derived fields
                        pass
                    else:
                        now = time.perf_counter()
                        n_timed = max(end - last_log_step - excluded_steps, 1)
                        wall = now - t_prev
                        dt = wall / n_timed
                        _blocked(now - t_fetch)
                        # where the host's share of the loop went, a step:
                        # in next() of the batch iterator, and everywhere
                        # else that is not waiting for the device
                        metrics["data_wait_ms"] = 1e3 * wait_s / n_timed
                        metrics["host_loop_ms"] = 1e3 * max(
                            wall - wait_s - blocked_s, 0.0
                        ) / n_timed
                        metrics["dispatch_max_ms"] = 1e3 * dispatch_max[0]
                        metrics["dispatch_max_step"] = dispatch_max[1]
                        metrics["host_gap_max_ms"] = 1e3 * gap_max[0]
                        metrics["host_gap_max_step"] = gap_max[1]
                        dispatch_max = gap_max = (0.0, 0)
                        # a long dispatch is a retrace where this counts
                        # one, and the queue's back-pressure where not
                        recompiles = (
                            _COMPILE_SPANS.recompiles_after_first_step
                            - recompiles_0
                        )
                        metrics["recompiles_after_first_step"] = recompiles
                        if recompiles:
                            metrics["recompile_last_step"] = (
                                _COMPILE_SPANS.recompiled[-1][0]
                            )
                        if recompiles > recompiles_row:
                            warnings.warn(
                                f"compiled again inside a timed step: "
                                + _COMPILE_SPANS.newest(
                                    recompiles - recompiles_row
                                ) + f" ({recompiles} this fit); a batch's or "
                                "a given state's shape, dtype or sharding changed",
                                stacklevel=2,
                            )
                        recompiles_row = recompiles
                        wait_s = blocked_s = 0.0
                        t_prev = now
                        last_log_step = end
                        excluded_steps = 0
                        metrics["step_time_s"] = dt
                        if cfg.tokens_per_step:
                            metrics["tokens_per_sec"] = cfg.tokens_per_step / dt
                            metrics["tokens"] = end * cfg.tokens_per_step
                            if cfg.flops_per_token:
                                from solvingpapers_tpu.metrics.mfu import chip_peak_flops

                                n_chips = self.mesh.devices.size
                                peak = chip_peak_flops() * n_chips
                                # NaN-safe: unknown chips have no peak
                                # table entry — omit the gauge rather
                                # than log a mis-scaled utilization
                                if math.isfinite(peak):
                                    metrics["mfu"] = (
                                        metrics["tokens_per_sec"]
                                        * cfg.flops_per_token / peak
                                    )
                    with _span("log_write", step=end):
                        row = {k: float(v) for k, v in metrics.items()}
                        if self._registry is not None:
                            row.update(self._registry.gauges())
                            row.update(self._ledger.gauges())
                            self._ledger.check()
                        if self._mesh_obs is not None:
                            row.update(self._mesh_obs.gauges())
                        last_row["step"] = end
                        last_row["metrics"] = row
                        writer.write(end, row)

                if ckpt is not None and ckpt.save_every > 0 \
                        and end % ckpt.save_every == 0:
                    # keep the save (fence + D2H snapshot; the disk write is
                    # already async) out of step timing, like eval/callbacks
                    _fence()
                    t_save = time.perf_counter()
                    with _span("checkpoint", step=end):
                        ckpt.maybe_save(end, _pure_state(state))
                    t_prev += time.perf_counter() - t_save
                step = end

            # unconditional: maybe_save dedupes existing steps, and a signal
            # landing during the final iteration must not lose the run
            if ckpt is not None:
                final_step = int(jax.device_get(state.step))
                ckpt.maybe_save(final_step, _pure_state(state), force=True)
            if profiling:  # fit ended inside the profile window
                _stop_profile()
                profiling = False
        finally:
            if first_step_scope is not None:  # the first step raised
                first_step_scope.__exit__(None, None, None)
            if self._status is not None:
                self._status.close()
                self._status = None
            if profiling:
                jax.profiler.stop_trace()
            if nan_debug_prev is not None:
                jax.config.update("jax_debug_nans", nan_debug_prev)
            if ckpt is not None:
                ckpt.close()
            if old_handlers:
                import signal

                for sig, h in old_handlers.items():
                    signal.signal(sig, h)
            if recorder is not None:
                # goodput = fenced step time / fit wall: the fraction of
                # the run spent training vs data waits / eval / ckpt /
                # host bookkeeping. Export lives in the finally so a
                # crashed run still leaves its trace for the post-mortem.
                wall = recorder.clock() - t_fit0
                goodput = step_span_total / wall if wall > 0 else 0.0
                recorder.instant(
                    "goodput", "train", "train", goodput=round(goodput, 4),
                    step_s=round(step_span_total, 4), wall_s=round(wall, 4),
                )
                recorder.export_chrome(cfg.trace_path)
                writer.write(step, {"goodput": goodput})
        return state

    def _probe_pipeline_stages(self, state, batch) -> None:
        """One-time mesh-observatory stage probe (TrainConfig.mesh_obs +
        pipeline_parallel): run each stage_fn standalone on one
        microbatch-shaped activation, forward plus grad-of-recompute
        (the 1F1B unit-cost shape; a fair proxy for the GPipe backward
        too), and hand the per-stage seconds to the observatory — the
        bubble report then compares them against every later fenced step
        wall. Diagnosis must never kill training: any failure degrades
        to a warning and the report stays absent."""
        import warnings

        obs = self._mesh_obs
        mcfg = getattr(self.model, "cfg", None)
        probe_hook = getattr(self.model, "stage_probe_fn", None)
        params = state.params if isinstance(state.params, dict) else {}
        stages = params.get("stages")
        if obs is None or mcfg is None or stages is None:
            return
        if probe_hook is None:
            # explicit, not silent: the diagnosis needs a standalone
            # per-stage callable and this model does not provide one
            # (GPTPipe/LlamaPipe do; DSV3Pipe's stage_fn is entangled
            # with the routing-bias stack and axis_index)
            warnings.warn(
                f"mesh_obs: {type(self.model).__name__} has no "
                "stage_probe_fn — pipeline bubble diagnosis skipped "
                "(collective ledger and stage trace tracks still run)",
                stacklevel=2,
            )
            return
        try:
            from solvingpapers_tpu.metrics.mesh_obs import probe_stage_costs
            from solvingpapers_tpu.sharding import mesh_axis_sizes

            sizes = mesh_axis_sizes(self.mesh)
            x_leaf = batch["x"] if isinstance(batch, dict) \
                else jax.tree_util.tree_leaves(batch)[0]
            seq = int(x_leaf.shape[-1])
            n_micro = int(getattr(mcfg, "n_microbatches", 1))
            local_b = self.config.batch_size // max(
                sizes.get("data", 1) * sizes.get("fsdp", 1), 1
            )
            mb = max(local_b // n_micro, 1)
            x = jnp.zeros(
                (mb, seq, int(mcfg.dim)),
                getattr(mcfg, "compute_dtype", jnp.float32),
            )
            stage_s = probe_stage_costs(
                stages, x, probe_hook(mb, seq), train=True,
            )
            obs.set_stage_probe(stage_s, n_micro)
        except Exception as e:  # noqa: BLE001 — observability, not training
            warnings.warn(f"mesh_obs stage probe failed: {e}", stacklevel=2)

    def evaluate(self, state: TrainState, eval_iter: Iterator[dict]) -> dict:
        if self._eval_step is None:
            if self._batch_shardings is None:
                import itertools

                eval_iter = iter(eval_iter)
                first = next(eval_iter)
                self._set_batch_shardings(first)
                eval_iter = itertools.chain([first], eval_iter)
            self._build_steps()
        acc: dict[str, float] = {}
        n = 0
        for i, batch in enumerate(eval_iter):
            if i >= self.config.eval_batches:
                break
            m = jax.device_get(
                self._dispatch("eval_step", self._eval_step, state, batch)
            )
            for k, v in m.items():
                acc[k] = acc.get(k, 0.0) + float(v)
            n += 1
        return {k: v / max(n, 1) for k, v in acc.items()}


# ---------------------------------------------------------------- checkpoint IO


def _pure_state(state: TrainState) -> dict:
    """Strip static fields so Orbax only sees serializable arrays."""
    return {
        "step": state.step,
        "params": state.params,
        "opt_state": state.opt_state,
        "rng": jax.random.key_data(state.rng),
        "model_state": state.model_state,
    }


def _apply_pure(state: TrainState, pure: dict) -> TrainState:
    return state.replace(
        step=pure["step"],
        params=pure["params"],
        opt_state=pure["opt_state"],
        # wrap with the template's impl (rbg key data is (4,) uint32,
        # threefry (2,)); the default impl would reject mismatched shapes
        rng=jax.random.wrap_key_data(
            pure["rng"], impl=jax.random.key_impl(state.rng)
        ),
        model_state=pure["model_state"],
    )
