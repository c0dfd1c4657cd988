"""Pipeline-parallel GPT: decoder blocks grouped into stages whose params
are STORED stacked with a leading stage dim sharded over the 'pipe' mesh
axis, and applied with the GPipe ppermute microbatch schedule
(sharding/pipeline.py) inside shard_map.

No counterpart in the reference (SURVEY.md §2.3 lists PP as a TPU-native
capability to add; the reference's ceiling is single-process DataParallel,
deepseekv3.ipynb cell 37). The embedding, final norm and head are small and
run replicated on every pipe device; only the decoder stack — where the
params and FLOPs are — is staged. With pipeline_parallel=False the same
stacked params are applied by a sequential scan over stages, which is the
dense oracle the PP schedule is tested against.

Functional-style module (init/apply duck-typing the Flax surface the
Trainer uses): stacked per-stage params cannot be expressed as ordinary
Flax submodules, so the stage stack is built by initializing each
GPTBlock per layer and stacking — the blocks themselves are the shared
models/layers.py modules.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from solvingpapers_tpu.models.gpt import GPTBlock, GPTConfig
from solvingpapers_tpu.models.layers import LayerNorm, default_positions
from solvingpapers_tpu.sharding.pipeline import pipeline_local_apply


@dataclasses.dataclass(frozen=True)
class GPTPipeConfig:
    vocab_size: int = 65
    block_size: int = 256
    dim: int = 256
    n_layers: int = 8
    n_heads: int = 4
    mlp_mult: int = 4
    # dropout trains under the schedule via the regenerable-seed recipe:
    # the GPipe/interleaved tick derives a per-(stage, microbatch) key
    # (sharding/pipeline.py rng kwarg) and the stage_fn folds in the layer
    # index, so every mask is a pure function of (base key, stage, layer,
    # microbatch) and regenerates identically across remat/backward
    dropout: float = 0.0
    dtype: str = "float32"
    n_stages: int = 4
    n_microbatches: int = 4
    # interleaved (virtual-stage) schedule: each pipe device holds
    # n_stages/pipe_size thin stages... concretely `virtual_stages` slices
    # per device (n_stages = pipe_size * virtual_stages), microbatches
    # enter in groups of pipe_size and loop the ring — bubble shrinks from
    # (P-1)/(m+P-1) to (P-1)/(m*v+P-1) (sharding/pipeline.py). 1 = GPipe.
    # Does not compose with context_parallel (slice selection is a
    # data-dependent branch; the CP ring's collectives can't sit inside it).
    virtual_stages: int = 1
    # jax.checkpoint each block inside the stage_fn: the schedule scan then
    # saves only tick-boundary activations (recompute in backward)
    remat: bool = False
    # True: apply inside shard_map over the 'pipe' axis with the GPipe
    # schedule; False: sequential scan over stages (dense oracle)
    pipeline_parallel: bool = False
    # compose with context parallelism: the sequence dim is additionally
    # sharded over 'context' and each stage's attention runs the ppermute
    # ring within its pipe coordinate's context group (orthogonal axes,
    # uniform schedule on every device)
    context_parallel: bool = False
    context_impl: str = "ring"  # ring | ulysses
    use_flash: bool = False

    def __post_init__(self):
        if self.n_layers % self.n_stages:
            raise ValueError(
                f"n_layers {self.n_layers} not divisible by n_stages "
                f"{self.n_stages}"
            )
        from solvingpapers_tpu.models.staged import validate_interleaved_config

        validate_interleaved_config(
            self.n_stages, self.virtual_stages, self.n_microbatches,
            self.context_parallel,
        )

    @property
    def pipe_size(self) -> int:
        """Devices on the pipe axis (= n_stages / virtual_stages)."""
        return self.n_stages // self.virtual_stages

    def storage_index(self, global_stage: int) -> int:
        """Row of the stacked params holding `global_stage` (the shared
        interleaved layout — models/staged.py)."""
        from solvingpapers_tpu.models.staged import interleaved_storage_index

        return interleaved_storage_index(
            global_stage, self.virtual_stages, self.pipe_size
        )

    @property
    def layers_per_stage(self) -> int:
        return self.n_layers // self.n_stages

    @property
    def compute_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)

    def block_cfg(self) -> GPTConfig:
        return GPTConfig(
            vocab_size=self.vocab_size, block_size=self.block_size,
            dim=self.dim, n_layers=self.n_layers, n_heads=self.n_heads,
            mlp_mult=self.mlp_mult, dropout=self.dropout, dtype=self.dtype,
            use_flash=self.use_flash,
            context_parallel=self.context_parallel,
            context_impl=self.context_impl,
        )


def _emb_dropout(x, key, rate):
    """The embedding-dropout site shared by apply() and the 1F1B path:
    replicated key (every pipe device must agree on stage 0's input)."""
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


class GPTPipe:
    """init/apply surface compatible with Trainer + lm_loss_fn."""

    def __init__(self, cfg: GPTPipeConfig):
        self.cfg = cfg
        self._block = GPTBlock(cfg.block_cfg())

    # ------------------------------------------------------------------ init

    def init(self, rngs: dict, tokens: jax.Array) -> dict:
        cfg = self.cfg
        rng = rngs["params"] if isinstance(rngs, dict) else rngs
        k_emb, k_pos, k_blocks, k_ln, k_head = jax.random.split(rng, 5)
        dummy = jnp.zeros((1, min(tokens.shape[1], cfg.block_size), cfg.dim),
                          cfg.compute_dtype)
        if cfg.context_parallel:
            # init runs inside shard_map (the blocks trace the context
            # ring); a constant dummy is axis-invariant and would clash
            # with the ring's varying carries under the vma checker
            dummy = jax.lax.pcast(dummy, ("context",), to="varying")

        def stage_init(key):
            blocks = {}
            for j in range(cfg.layers_per_stage):
                blocks[f"block_{j}"] = self._block.init(
                    jax.random.fold_in(key, j), dummy
                )["params"]
            return blocks

        stage_list = [
            stage_init(jax.random.fold_in(k_blocks, s))
            for s in range(cfg.n_stages)
        ]
        # storage row r holds global stage order[r] (identity for GPipe;
        # the shared interleaved permutation for virtual_stages > 1)
        from solvingpapers_tpu.models.staged import interleaved_storage_order

        order = interleaved_storage_order(cfg.n_stages, cfg.virtual_stages)
        stages = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[stage_list[g] for g in order]
        )

        params = {
            "tok_emb": {
                "embedding": nn.initializers.normal(0.02)(
                    k_emb, (cfg.vocab_size, cfg.dim), jnp.float32
                )
            },
            "pos_emb": nn.initializers.normal(0.02)(
                k_pos, (cfg.block_size, cfg.dim), jnp.float32
            ),
            "stages": stages,
            "ln_f": LayerNorm().init(k_ln, dummy)["params"],
            "lm_head": {
                "kernel": nn.initializers.lecun_normal()(
                    k_head, (cfg.dim, cfg.vocab_size), jnp.float32
                )
            },
        }
        return {"params": params}

    # ----------------------------------------------------------------- apply

    def _stage_fn(self, stage_params, x, rng=None, virtual_idx=0):
        # virtual_idx: interleaved-schedule slice index (unused here — the
        # unit_rng already encodes the global stage)
        def one(p, x, key):
            if key is None:
                y, _ = self._block.apply({"params": p}, x, None, None, True)
            else:
                y, _ = self._block.apply(
                    {"params": p}, x, None, None, False, None,
                    rngs={"dropout": key},
                )
            return y

        if self.cfg.remat:
            # same key on the remat replay -> identical masks in backward
            one = jax.checkpoint(one)
        for j in range(self.cfg.layers_per_stage):
            x = one(
                stage_params[f"block_{j}"], x,
                None if rng is None else jax.random.fold_in(rng, j),
            )
        return x

    def stage_probe_fn(self, mb: int, seq: int):
        """Standalone per-stage callable for the mesh observatory's
        bubble probe (metrics/mesh_obs.probe_stage_costs): the
        schedule's rng/virtual kwargs stripped. GPT blocks carry their
        positions in the embedded input, so the shape args are unused."""
        del mb, seq
        return lambda p, x: self._stage_fn(p, x)

    def apply(
        self,
        variables: dict,
        tokens: jax.Array,
        *,
        positions: jax.Array | None = None,
        caches=None,
        deterministic: bool = True,
        rngs=None,
    ):
        if caches is not None:
            raise NotImplementedError(
                "decode caches are unsupported under pipeline parallelism; "
                "export the params and restack for the dense GPT to decode"
            )
        cfg = self.cfg
        p = variables["params"]
        b, s = tokens.shape
        if positions is None:
            positions = default_positions(
                b, s, cfg.context_parallel, max_positions=cfg.block_size
            )
        x = jnp.take(p["tok_emb"]["embedding"], tokens, axis=0)
        # full (B, S) positions like models/gpt.py — positions[0] would
        # silently apply the first row's positions to every batch row
        x = x + jnp.take(p["pos_emb"], positions, axis=0)
        x = x.astype(cfg.compute_dtype)

        train_drop = (not deterministic) and cfg.dropout > 0.0
        sched_rng = None
        if train_drop:
            if not rngs or "dropout" not in rngs:
                raise ValueError(
                    "dropout > 0 training requires rngs={'dropout': key}"
                )
            k_emb, sched_rng = jax.random.split(rngs["dropout"])
            # embedding dropout (models/gpt.py's nn.Dropout site) applied
            # manually (shared helper with the 1F1B path)
            x = _emb_dropout(x, k_emb, cfg.dropout)

        if cfg.pipeline_parallel and cfg.virtual_stages > 1:
            # interleaved schedule: local slice holds this device's
            # virtual_stages rows (blocked 'pipe' sharding of the permuted
            # stack — cfg.storage_index)
            from solvingpapers_tpu.sharding.pipeline import (
                pipeline_local_apply_interleaved,
            )

            x = pipeline_local_apply_interleaved(
                p["stages"], x, self._stage_fn,
                n_microbatches=cfg.n_microbatches,
                n_virtual=cfg.virtual_stages,
                rng=sched_rng,
            )
        elif cfg.pipeline_parallel:
            # local stage slice has leading dim n_stages/pipe_size == 1
            # (shard_map over in_specs P('pipe'))
            x = pipeline_local_apply(
                p["stages"], x, self._stage_fn,
                n_microbatches=cfg.n_microbatches,
                rng=sched_rng,
            )
        else:
            for g in range(cfg.n_stages):  # GLOBAL stage order
                x = self._stage_fn(
                    jax.tree.map(
                        lambda a: a[cfg.storage_index(g)], p["stages"]
                    ),
                    x,
                    None if sched_rng is None
                    else jax.random.fold_in(sched_rng, g),
                )

        x = LayerNorm().apply({"params": p["ln_f"]}, x)
        logits = (
            x.astype(cfg.compute_dtype)
            @ p["lm_head"]["kernel"].astype(cfg.compute_dtype)
        )
        return logits, None

    @property
    def max_positions(self) -> int:
        return self.cfg.block_size

    # ------------------------------------------------------------------ 1f1b

    def f1b_value_and_grad(self, params, batch, rng=None,
                           model_state=None):
        """Loss AND grads in one 1F1B pass (sharding.pipeline
        .pipeline_1f1b_value_and_grad) — call INSIDE a shard_map whose
        'pipe' axis shards the stage stack. Returns (loss, grads,
        model_state) — state passed through unchanged (stateless) — with
        `grads` matching the params tree (stage grads keep this device's
        leading-1 stage dim; head/embedding grads are pipe-invariant).
        With `rng` and dropout > 0, masks come from the schedule's
        per-(stage, microbatch) regenerable keys (identical in the
        backward recompute) plus a replicated embedding-dropout key —
        the same recipe as the GPipe path. The Trainer opts in via
        TrainConfig.pp_schedule."""
        from solvingpapers_tpu import ops
        from solvingpapers_tpu.models.staged import f1b_lm_value_and_grad

        cfg = self.cfg
        tokens, targets = batch["x"], batch["y"]
        b, s = tokens.shape
        m = cfg.n_microbatches
        positions = default_positions(b, s, False,
                                      max_positions=cfg.block_size)
        head = {"ln_f": params["ln_f"], "lm_head": params["lm_head"]}
        embed = {"tok_emb": params["tok_emb"], "pos_emb": params["pos_emb"]}

        train_drop = rng is not None and cfg.dropout > 0.0
        sched_rng = k_emb = None
        if train_drop:
            k_emb, sched_rng = jax.random.split(rng)

        def embed_fn(ep):
            x = jnp.take(ep["tok_emb"]["embedding"], tokens, axis=0)
            x = x + jnp.take(ep["pos_emb"], positions, axis=0)
            x = x.astype(cfg.compute_dtype)
            if train_drop:
                x = _emb_dropout(x, k_emb, cfg.dropout)
            return x.reshape(m, b // m, s, cfg.dim)

        def head_loss(hp, h, t):
            z = LayerNorm().apply({"params": hp["ln_f"]}, h)
            logits = (
                z.astype(cfg.compute_dtype)
                @ hp["lm_head"]["kernel"].astype(cfg.compute_dtype)
            )
            return ops.cross_entropy(logits, t)

        loss, dstage, dhead, dembed = f1b_lm_value_and_grad(
            params["stages"], embed, head, targets, m, embed_fn,
            self._stage_fn, head_loss, rng=sched_rng,
        )
        grads = {
            "tok_emb": dembed["tok_emb"], "pos_emb": dembed["pos_emb"],
            "stages": dstage,
            "ln_f": dhead["ln_f"], "lm_head": dhead["lm_head"],
        }
        return loss, grads, model_state

    # ---------------------------------------------------------------- export

    def to_dense(self, params: dict):
        """Restack the stage-stacked params into the dense GPT layout
        (block_{i} keys) and return (GPT model, params) — the decode path
        for pipeline-trained weights (PP itself has no cache support).
        GPTPipe block j of stage s is GPT block s*layers_per_stage + j;
        module names are shared, so the forward is bit-identical. The
        export config drops context_parallel: the dense model decodes
        outside shard_map (no 'context' axis to ring over)."""
        from solvingpapers_tpu.models.gpt import GPT

        cfg = self.cfg
        dense = {k: v for k, v in params.items() if k != "stages"}
        for s in range(cfg.n_stages):  # s = GLOBAL stage index
            row = cfg.storage_index(s)
            for j in range(cfg.layers_per_stage):
                dense[f"block_{s * cfg.layers_per_stage + j}"] = jax.tree.map(
                    lambda a: a[row], params["stages"][f"block_{j}"]
                )
        dense_cfg = dataclasses.replace(cfg.block_cfg(), context_parallel=False)
        return GPT(dense_cfg), dense
