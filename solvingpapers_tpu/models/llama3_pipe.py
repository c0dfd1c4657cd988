"""Pipeline-parallel LLaMA3: decoder blocks staged over the 'pipe' axis
with the shared staged-LM machinery (models/staged.py) and the GPipe
ppermute schedule (sharding/pipeline.py).

No counterpart in the reference (SURVEY.md §2.3 PP row). Blocks are the
exact LlamaBlock modules of models/llama3.py — GQA + RoPE + SwiGLU — so
staged == dense is a restack away (`to_dense`), which is also the decode
path (PP has no cache support). Stateless blocks make this the simple
instantiation of the pattern; the flagship's stateful-MoE version is
models/deepseekv3_pipe.py. Dropout trains under the schedule via
per-(stage, microbatch, layer) keys (sharding/pipeline.py rng kwarg —
the same regenerable-seed recipe as GPTPipe).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from solvingpapers_tpu.models.llama3 import LlamaBlock, LlamaConfig
from solvingpapers_tpu.models.layers import RMSNorm, default_positions
from solvingpapers_tpu.models.staged import init_stage_stack, restack_to_dense
from solvingpapers_tpu.sharding.pipeline import pipeline_local_apply


@dataclasses.dataclass(frozen=True)
class LlamaPipeConfig:
    vocab_size: int = 50257
    max_seq_len: int = 128
    dim: int = 256
    n_layers: int = 4
    n_heads: int = 4
    n_kv_heads: int = 2
    hidden_dim: int | None = None
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    # block-level dropout (the reference's transformer_block Bernoulli
    # masks, LLaMA-jax.ipynb cell 26) via per-(stage, microbatch, layer)
    # schedule keys
    dropout: float = 0.0
    dtype: str = "float32"
    use_flash: bool = False
    remat: bool = False  # jax.checkpoint each block inside the stage_fn
    n_stages: int = 2
    n_microbatches: int = 2
    # interleaved (virtual-stage) schedule: each pipe device holds
    # `virtual_stages` thin stages (n_stages = pipe_size * virtual_stages),
    # shrinking the bubble to (P-1)/(m*v + P - 1). 1 = GPipe. Does not
    # compose with context_parallel (the virtual-slice branch cannot
    # contain the CP ring's collectives).
    virtual_stages: int = 1
    pipeline_parallel: bool = False
    context_parallel: bool = False
    context_impl: str = "ring"

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

    def block_cfg(self) -> LlamaConfig:
        return LlamaConfig(
            vocab_size=self.vocab_size, max_seq_len=self.max_seq_len,
            dim=self.dim, n_layers=self.n_layers, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, hidden_dim=self.hidden_dim,
            rope_theta=self.rope_theta, norm_eps=self.norm_eps,
            dropout=self.dropout, dtype=self.dtype, use_flash=self.use_flash,
            context_parallel=self.context_parallel,
            context_impl=self.context_impl,
        )


class LlamaPipe:
    """init/apply surface compatible with Trainer + lm_loss_fn."""

    def __init__(self, cfg: LlamaPipeConfig):
        self.cfg = cfg
        self._block = LlamaBlock(cfg.block_cfg())

    def init(self, rngs: dict, tokens: jax.Array) -> dict:
        cfg = self.cfg
        rng = rngs["params"] if isinstance(rngs, dict) else rngs
        k_emb, k_blocks, k_ln, k_head = jax.random.split(rng, 4)
        dummy = jnp.zeros(
            (1, min(tokens.shape[1], cfg.max_seq_len), cfg.dim),
            cfg.compute_dtype,
        )
        if cfg.context_parallel:
            dummy = jax.lax.pcast(dummy, ("context",), to="varying")
        from solvingpapers_tpu.models.staged import interleaved_storage_order

        stacked = init_stage_stack(
            self._block, k_blocks, dummy, cfg.n_stages, cfg.layers_per_stage,
            order=interleaved_storage_order(cfg.n_stages, cfg.virtual_stages),
        )
        params = {
            "tok_emb": {
                "embedding": nn.initializers.variance_scaling(
                    1.0, "fan_in", "normal", out_axis=0
                )(k_emb, (cfg.vocab_size, cfg.dim), jnp.float32)
            },
            "stages": stacked["params"],
            "norm_f": RMSNorm(eps=cfg.norm_eps).init(k_ln, dummy)["params"],
            "lm_head": {
                "kernel": nn.initializers.lecun_normal()(
                    k_head, (cfg.dim, cfg.vocab_size), jnp.float32
                )
            },
        }
        return {"params": params}

    def _stage_fn(self, positions):
        def one(p, x, key):
            if key is None:
                y, _ = self._block.apply({"params": p}, x, positions, None,
                                         True, None)
            else:
                y, _ = self._block.apply(
                    {"params": p}, x, positions, None, False, None,
                    rngs={"dropout": key},
                )
            return y

        if self.cfg.remat:
            # same key on the remat replay -> identical masks in backward
            one = jax.checkpoint(one)

        def stage_fn(sp, x, rng=None, virtual_idx=0):
            for j in range(self.cfg.layers_per_stage):
                x = one(
                    sp[f"block_{j}"], x,
                    None if rng is None else jax.random.fold_in(rng, j),
                )
            return x

        return stage_fn

    def stage_probe_fn(self, mb: int, seq: int):
        """Standalone per-stage callable for the mesh observatory's
        bubble probe (metrics/mesh_obs.probe_stage_costs): the stage
        closure built over plain microbatch positions, rng/virtual
        kwargs stripped."""
        positions = default_positions(
            mb, seq, False, max_positions=self.cfg.max_seq_len
        )
        fn = self._stage_fn(positions)
        return lambda p, x: fn(p, x)

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
                "to_dense() the params and decode with Llama"
            )
        cfg = self.cfg
        p = variables["params"]
        b, s = tokens.shape
        if positions is None:
            positions = default_positions(
                b, s, cfg.context_parallel, max_positions=cfg.max_seq_len
            )
        x = jnp.take(p["tok_emb"]["embedding"], tokens, axis=0)
        x = x.astype(cfg.compute_dtype)

        train_drop = (not deterministic) and cfg.dropout > 0.0
        sched_rng = None
        if train_drop:
            if not rngs or "dropout" not in rngs:
                raise ValueError(
                    "dropout > 0 training requires rngs={'dropout': key}"
                )
            sched_rng = rngs["dropout"]

        if cfg.pipeline_parallel and cfg.virtual_stages > 1:
            from solvingpapers_tpu.sharding.pipeline import (
                pipeline_local_apply_interleaved,
            )

            mb = x.shape[0] // cfg.n_microbatches
            stage_fn = self._stage_fn(positions[:mb])
            x = pipeline_local_apply_interleaved(
                p["stages"], x, stage_fn,
                n_microbatches=cfg.n_microbatches,
                n_virtual=cfg.virtual_stages,
                rng=sched_rng,
            )
        elif cfg.pipeline_parallel:
            mb = x.shape[0] // cfg.n_microbatches
            stage_fn = self._stage_fn(positions[:mb])
            x = pipeline_local_apply(
                p["stages"], x, stage_fn,
                n_microbatches=cfg.n_microbatches,
                rng=sched_rng,
            )
        else:
            stage_fn = self._stage_fn(positions)
            for g in range(cfg.n_stages):  # GLOBAL stage order
                x = stage_fn(
                    jax.tree.map(
                        lambda a: a[cfg.storage_index(g)], p["stages"]
                    ),
                    x,
                    None if sched_rng is None
                    else jax.random.fold_in(sched_rng, g),
                )

        x = RMSNorm(eps=cfg.norm_eps).apply({"params": p["norm_f"]}, x)
        logits = (
            x.astype(cfg.compute_dtype)
            @ p["lm_head"]["kernel"].astype(cfg.compute_dtype)
        )
        return logits, None

    @property
    def max_positions(self) -> int:
        return self.cfg.max_seq_len

    def f1b_value_and_grad(self, params, batch, rng=None,
                           model_state=None):
        """Loss AND grads in one 1F1B pass — same contract as
        GPTPipe.f1b_value_and_grad (call inside the Trainer's 'pipe'
        shard_map via TrainConfig.pp_schedule='1f1b'; with `rng`,
        block dropout uses the schedule's per-(stage, microbatch)
        regenerable keys). RoPE positions are baked into the stage_fn
        closure, the RMSNorm+lm_head ride as the schedule's loss head."""
        from solvingpapers_tpu import ops
        from solvingpapers_tpu.models.staged import f1b_lm_value_and_grad

        cfg = self.cfg
        tokens, targets = batch["x"], batch["y"]
        b, s = tokens.shape
        m = cfg.n_microbatches
        positions = default_positions(b, s, False,
                                      max_positions=cfg.max_seq_len)
        head = {"norm_f": params["norm_f"], "lm_head": params["lm_head"]}
        stage_fn = self._stage_fn(positions[: b // m])

        def embed_fn(emb):
            x = jnp.take(emb["embedding"], tokens, axis=0)
            return x.astype(cfg.compute_dtype).reshape(
                m, b // m, s, cfg.dim
            )

        def head_loss(hp, h, t):
            z = RMSNorm(eps=cfg.norm_eps).apply({"params": hp["norm_f"]}, h)
            logits = (
                z.astype(cfg.compute_dtype)
                @ hp["lm_head"]["kernel"].astype(cfg.compute_dtype)
            )
            return ops.cross_entropy(logits, t)

        loss, dstage, dhead, dembed = f1b_lm_value_and_grad(
            params["stages"], params["tok_emb"], head, targets, m,
            embed_fn, stage_fn, head_loss,
            rng=rng if cfg.dropout > 0.0 else None,
        )
        grads = {
            "tok_emb": dembed, "stages": dstage,
            "norm_f": dhead["norm_f"], "lm_head": dhead["lm_head"],
        }
        return loss, grads, model_state

    def to_dense(self, params: dict):
        """Restack into the dense Llama layout (block_{i} keys) — the
        decode path for pipeline-trained weights."""
        from solvingpapers_tpu.models.llama3 import Llama

        cfg = self.cfg
        dense = {k: v for k, v in params.items() if k != "stages"}
        dense.update(restack_to_dense(
            params["stages"], cfg.n_stages, cfg.layers_per_stage,
            lambda i: f"block_{i}", storage_index=cfg.storage_index,
        ))
        dense_cfg = dataclasses.replace(cfg.block_cfg(), context_parallel=False)
        return Llama(dense_cfg), dense
