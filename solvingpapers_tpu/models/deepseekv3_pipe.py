"""Pipeline-parallel DeepSeekV3: MLA + MoE decoder layers grouped into
stages (stacked variables, leading stage dim sharded over 'pipe'), applied
with the GPipe ppermute schedule inside shard_map.

No counterpart in the reference (its flagship trains under single-process
DataParallel, deepseekv3.ipynb cell 37); SURVEY.md §2.3 lists PP as a
TPU-native capability to add. The blocks are the exact DSV3DecoderLayer
modules of models/deepseekv3.py, so staged == dense is a restack away
(`to_dense`), and decode for PP-trained weights goes through the dense
family after export.

Routing state under PP (the hard part): the aux-free routing bias
(deepseekv3.ipynb cell 23's no-grad buffer) is carried stacked over stages
but REPLICATED across the mesh, and must stay shard-invariant. Inside the
GPipe stage_fn the layers apply with 'moe_state' immutable (a pure
(params, x) function re-runs across schedule ticks), sowing their raw
per-expert loads instead; the schedule sums those over each device's valid
ticks (bubble ticks masked — sharding/pipeline.py with_aux), data-axis
psums make the loads global, and each device's update for ITS stage's
layers is scattered into a zero stack and psum'd over 'pipe' — every
device applies the identical full-stack update, so out_specs P() holds by
construction (verified under the vma checker for non-flash configs).

Dropout trains under the schedule (the reference flagship's recipe is
dropout 0.1, deepseekv3.ipynb cell 4): the GPipe tick derives a
per-(stage, microbatch) key (sharding/pipeline.py rng kwarg), the stage_fn
folds in the layer index, and the post-stack dropout runs replicated
outside the schedule — every mask is a pure function of the base key and
regenerates identically across remat/backward (same recipe as GPTPipe).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from solvingpapers_tpu import ops
from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3Config, DSV3DecoderLayer
from solvingpapers_tpu.models.layers import RMSNorm, default_positions
from solvingpapers_tpu.models.staged import (
    init_stage_stack,
    restack_to_dense,
    stage_slice,
)
from solvingpapers_tpu.sharding.pipeline import pipeline_local_apply

_STAT_KEYS = ("load_entropy", "load_max_fraction", "drop_fraction",
              "live_tile_fraction", "bias_norm")


@dataclasses.dataclass(frozen=True)
class DSV3PipeConfig:
    vocab_size: int = 50257
    block_size: int = 256
    dim: int = 512
    n_layers: int = 6
    n_heads: int = 8
    latent_dim: int = 64
    rope_dim: int = 0
    rope_theta: float = 10000.0
    pe_scale: float = 1.0
    n_experts: int = 8
    top_experts: int = 2
    use_shared_expert: bool = True
    use_aux_free: bool = True
    aux_free_bias_update_rate: float = 0.001
    moe_impl: str = "dispatch"  # dispatch | dense
    capacity_factor: float = 2.0
    # the reference recipe's dropout 0.1 (cell 4): residual/out-proj and
    # attention-prob dropout inside the staged layers via per-(stage,
    # microbatch, layer) keys, plus the post-stack dropout (cell 31)
    # applied replicated outside the schedule
    dropout: float = 0.0
    attn_dropout: float = 0.0
    dtype: str = "float32"
    use_flash: bool = False
    remat: bool = False  # jax.checkpoint each block inside the stage_fn
    n_stages: int = 2
    n_microbatches: int = 2
    # interleaved (virtual-stage) schedule: each pipe device holds
    # `virtual_stages` thin stages (n_stages = pipe_size * virtual_stages);
    # the MoE routing state rides the schedule's per-virtual-slice aux
    # stack (sharding/pipeline.py with_aux) and is scattered back into the
    # storage rows [d*v, d*v + v). 1 = GPipe. Does not compose with
    # context_parallel (the virtual-slice branch cannot contain the CP
    # ring's collectives).
    virtual_stages: int = 1
    # True: GPipe schedule inside shard_map over 'pipe'; False: sequential
    # scan over stages (the dense oracle the schedule is tested against)
    pipeline_parallel: bool = False
    # compose with context parallelism (sequence over 'context'; each
    # stage's MLA rings within its pipe coordinate's context group)
    context_parallel: bool = False
    # MTP (deepseekv3.ipynb cells 33/46) composes with PP: the schedule's
    # output is psum-broadcast to every pipe device, so the MTP branch
    # (merge + extra decoder layer + proj per head) runs REPLICATED after
    # the staged stack, exactly like the final norm/head — its params and
    # routing bias are plain (unstaged) entries
    mtp_heads: int = 0
    mtp_loss_weight: float = 0.3

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

    @property
    def stats_axes(self):
        # engine contract for model_state under shard_map without vma
        # checking (use_flash): the state updates are shard-invariant
        # (psum'd loads + pipe-psum'd stack recombination)
        return ("data", "fsdp") + (("context",) if self.context_parallel else ())

    def layer_cfg(self) -> DeepSeekV3Config:
        return DeepSeekV3Config(
            vocab_size=self.vocab_size, block_size=self.block_size,
            dim=self.dim, n_layers=self.n_layers, n_heads=self.n_heads,
            latent_dim=self.latent_dim, rope_dim=self.rope_dim,
            rope_theta=self.rope_theta, pe_scale=self.pe_scale,
            n_experts=self.n_experts, top_experts=self.top_experts,
            use_shared_expert=self.use_shared_expert,
            use_aux_free=self.use_aux_free,
            aux_free_bias_update_rate=self.aux_free_bias_update_rate,
            moe_impl=self.moe_impl, capacity_factor=self.capacity_factor,
            dropout=self.dropout, attn_dropout=self.attn_dropout,
            mtp_heads=self.mtp_heads, mtp_loss_weight=self.mtp_loss_weight,
            dtype=self.dtype,
            use_flash=self.use_flash,
            context_parallel=self.context_parallel,
        )


class DSV3Pipe:
    """init/apply surface compatible with Trainer + dsv3_loss_fn."""

    def __init__(self, cfg: DSV3PipeConfig):
        self.cfg = cfg
        self._block = DSV3DecoderLayer(cfg.layer_cfg())

    # ------------------------------------------------------------------ init

    def init(self, rngs: dict, tokens: jax.Array, return_mtp: bool = False) -> dict:
        cfg = self.cfg
        rng = rngs["params"] if isinstance(rngs, dict) else rngs
        k_emb, k_blocks, k_ln = jax.random.split(rng, 3)
        dummy = jnp.zeros((1, min(tokens.shape[1], cfg.block_size), cfg.dim),
                          cfg.compute_dtype)
        if cfg.context_parallel:
            # init runs inside shard_map (blocks trace the context ring); a
            # constant dummy is axis-invariant and would clash with the
            # ring's varying carries under the vma checker
            dummy = jax.lax.pcast(dummy, ("context",), to="varying")

        from solvingpapers_tpu.models.staged import interleaved_storage_order

        stacked = init_stage_stack(
            self._block, k_blocks, dummy, cfg.n_stages, cfg.layers_per_stage,
            order=interleaved_storage_order(cfg.n_stages, cfg.virtual_stages),
        )
        params = {
            "tok_emb": {
                "embedding": nn.initializers.normal(0.02)(
                    k_emb, (cfg.vocab_size, cfg.dim), jnp.float32
                )
            },
            "stages": stacked["params"],
            "norm_f": RMSNorm().init(k_ln, dummy)["params"],
        }
        moe_state = {"stages": stacked["moe_state"]}
        if cfg.mtp_heads > 0:
            # dense DeepSeekV3's MTP machinery under the dense family's
            # exact param names, so to_dense export is a plain key copy
            from solvingpapers_tpu.models.layers import LayerNorm

            k_mtp = jax.random.fold_in(k_blocks, 10_000)
            lecun = nn.initializers.lecun_normal()
            for h in range(1, cfg.mtp_heads + 1):
                kh = jax.random.fold_in(k_mtp, h)
                k1, k2, k3, k4, k5 = jax.random.split(kh, 5)
                params[f"mtp_norm_h_{h}"] = LayerNorm().init(k1, dummy)["params"]
                params[f"mtp_norm_e_{h}"] = LayerNorm().init(k2, dummy)["params"]
                params[f"mtp_merge_{h}"] = {
                    "kernel": lecun(k3, (2 * cfg.dim, cfg.dim), jnp.float32)
                }
                lv = self._block.init(k4, dummy)
                params[f"mtp_layer_{h}"] = lv["params"]
                moe_state[f"mtp_layer_{h}"] = lv["moe_state"]
                params[f"mtp_proj_{h}"] = {
                    "kernel": lecun(k5, (cfg.dim, cfg.dim), jnp.float32)
                }
        return {"params": params, "moe_state": moe_state}

    # ----------------------------------------------------------------- apply

    def _make_stage_fn(self, bias_stack, positions, stage_index_fn):
        """stage_fn(stage_params, x) -> (y, aux): applies this stage's
        layers with the routing bias READ-ONLY, collecting per-layer raw
        loads + load stats. `stage_index_fn(virtual_idx)` -> the STORAGE
        row of this unit's stage in the stacked variables (axis index
        under GPipe, d*v + virtual_idx under the interleaved schedule,
        python int under the dense oracle)."""
        cfg = self.cfg

        def one(block_params, bias_j, x, key):
            det = key is None
            (y, _), mut = self._block.apply(
                {"params": block_params, "moe_state": bias_j},
                x, positions, None, det, None,
                mutable=["moe_metrics"],
                **({} if det else {"rngs": {"dropout": key}}),
            )
            stats = mut["moe_metrics"]["moe"]["stats"][0]
            return y, {k: stats[k] for k in (*_STAT_KEYS, "ci")}

        if cfg.remat:
            # same key on the remat replay -> identical masks in backward
            one = jax.checkpoint(one)

        def stage_fn(sp, x, rng=None, virtual_idx=0):
            sid = stage_index_fn(virtual_idx)
            aux_layers = []
            for j in range(cfg.layers_per_stage):
                bias_j = stage_slice(bias_stack[f"block_{j}"], sid)
                x, layer_aux = one(
                    sp[f"block_{j}"], bias_j, x,
                    None if rng is None else jax.random.fold_in(rng, j),
                )
                aux_layers.append(layer_aux)
            aux = {
                k: jnp.stack([a[k] for a in aux_layers])
                for k in aux_layers[0]
            }
            return x, aux

        return stage_fn

    def apply(
        self,
        variables: dict,
        tokens: jax.Array,
        *,
        positions: jax.Array | None = None,
        caches=None,
        deterministic: bool = True,
        rngs=None,
        mutable=(),
        return_mtp: bool = False,
    ):
        if caches is not None:
            raise NotImplementedError(
                "decode caches are unsupported under pipeline parallelism; "
                "to_dense() the params and decode with DeepSeekV3"
            )
        cfg = self.cfg
        use_mtp = return_mtp and cfg.mtp_heads > 0
        if return_mtp and cfg.mtp_heads == 0:
            raise ValueError("return_mtp=True but cfg.mtp_heads == 0")
        p = variables["params"]
        ms_all = variables["moe_state"]
        bias_stack = variables["moe_state"]["stages"]
        b, s = tokens.shape
        if positions is None:
            positions = default_positions(
                b, s, cfg.context_parallel, max_positions=cfg.block_size
            )
        pe = ops.sinusoidal_position_encoding(cfg.block_size, cfg.dim)
        # cast-then-add, matching the dense DeepSeekV3 (its nn.Embed emits
        # compute_dtype before the PE add) so staged and restacked-dense
        # forwards agree bit-for-bit in bf16
        x = jnp.take(p["tok_emb"]["embedding"], tokens, axis=0).astype(
            cfg.compute_dtype
        )
        x = x + cfg.pe_scale * jnp.take(pe, positions, axis=0).astype(
            cfg.compute_dtype
        )

        train_drop = (not deterministic) and (
            cfg.dropout > 0.0 or cfg.attn_dropout > 0.0
        )
        sched_rng = k_out = None
        if train_drop:
            if not rngs or "dropout" not in rngs:
                raise ValueError(
                    "dropout > 0 training requires rngs={'dropout': key}"
                )
            k_out, sched_rng = jax.random.split(rngs["dropout"])

        if cfg.pipeline_parallel and cfg.virtual_stages > 1:
            # interleaved schedule: the routing state rides the schedule's
            # per-virtual-slice aux stack; storage row of slice j on
            # device d is d*v + j
            from solvingpapers_tpu.sharding.pipeline import (
                pipeline_local_apply_interleaved,
            )

            mb = x.shape[0] // cfg.n_microbatches
            v = cfg.virtual_stages
            stage_fn = self._make_stage_fn(
                bias_stack, positions[:mb],
                lambda j: jax.lax.axis_index("pipe") * v + j,
            )
            x, aux = pipeline_local_apply_interleaved(
                p["stages"], x, stage_fn,
                n_microbatches=cfg.n_microbatches,
                n_virtual=v, with_aux=True, rng=sched_rng,
            )
            # aux rows sum over each slice's n_microbatches valid ticks
            n_ticks = cfg.n_microbatches
        elif cfg.pipeline_parallel:
            mb = x.shape[0] // cfg.n_microbatches
            mb_positions = positions[:mb]
            stage_fn = self._make_stage_fn(
                bias_stack, mb_positions,
                lambda j: jax.lax.axis_index("pipe"),
            )
            x, aux = pipeline_local_apply(
                p["stages"], x, stage_fn,
                n_microbatches=cfg.n_microbatches, with_aux=True,
                rng=sched_rng,
            )
            # stack aux like the interleaved path's (v=1, ...) rows so
            # _mutate handles one layout
            aux = jax.tree.map(lambda a: a[None], aux)
            # aux sums over this device's n_microbatches valid ticks
            n_ticks = cfg.n_microbatches
        else:
            # dense oracle: same layers, same aux plumbing, no pipe axis;
            # iterate GLOBAL stage order, slicing the storage row
            aux_stages = []
            for g in range(cfg.n_stages):
                row = cfg.storage_index(g)
                stage_fn = self._make_stage_fn(
                    bias_stack, positions, lambda j, row=row: row
                )
                x, aux_s = stage_fn(
                    jax.tree.map(lambda a: a[row], p["stages"]), x,
                    None if sched_rng is None
                    else jax.random.fold_in(sched_rng, g),
                )
                aux_stages.append((row, aux_s))
            n_ticks = 1

        if train_drop and cfg.dropout > 0.0:
            # the post-stack dropout (cell 31) — replicated on every pipe
            # device with the same key, keeping the psum-broadcast output
            # identical across the axis
            keep = 1.0 - cfg.dropout
            mask = jax.random.bernoulli(k_out, keep, x.shape)
            x = jnp.where(mask, x / keep, 0.0).astype(x.dtype)
        x = 2.0 * cfg.n_layers**-0.5 * x  # deepseek depth scaling (cell 31)
        x = RMSNorm().apply({"params": p["norm_f"]}, x)
        emb = p["tok_emb"]["embedding"]
        dt = cfg.compute_dtype
        logits = x.astype(dt) @ emb.T.astype(dt)

        mtp_aux: list = []
        mtp_logits = None
        if use_mtp:
            # replicated MTP branch on the psum-broadcast stream (every
            # pipe device computes the identical heads, like norm_f/head) —
            # the shared functional core (models.deepseekv3.mtp_head_apply;
            # the dense family's flax-module branch is the only other
            # copy). Under CP the i+k shift is the cp_shift_left ppermute.
            from solvingpapers_tpu.models.deepseekv3 import mtp_head_apply

            h_prev = x
            outs = []
            for h in range(1, cfg.mtp_heads + 1):
                if cfg.context_parallel:
                    from solvingpapers_tpu.sharding import cp_shift_left

                    shifted = cp_shift_left(tokens, h, fill=0)
                else:
                    shifted = jnp.pad(tokens[:, h:], ((0, 0), (0, h)))
                head_rngs = None
                if train_drop:
                    # replicated across pipe (same key on every device)
                    head_rngs = {"dropout": jax.random.fold_in(
                        rngs["dropout"], 20_000 + h)}
                head_logits, y, _, stats = mtp_head_apply(
                    self._block.cfg, p, ms_all, h_prev, shifted, positions,
                    head=h, rngs=head_rngs, collect_stats=True,
                )
                mtp_aux.append(
                    (f"mtp_layer_{h}",
                     {k: stats[k] for k in (*_STAT_KEYS, "ci")})
                )
                outs.append(head_logits)
                h_prev = y
            mtp_logits = jnp.stack(outs, axis=2)

        out = (logits, mtp_logits) if use_mtp else logits
        mutated = {}
        wants = set(mutable if not isinstance(mutable, str) else [mutable])
        if wants:
            mutated = self._mutate(
                bias_stack,
                aux if cfg.pipeline_parallel else aux_stages,
                n_ticks, wants, deterministic, ms_all, mtp_aux,
            )
            return (out, None), mutated
        return out, None

    # --------------------------------------------------------- state updates

    def _mutate(self, bias_stack, aux, n_ticks, wants, deterministic,
                ms_all=None, mtp_aux=()):
        """Recombine per-device aux into the shard-invariant moe_state
        update + scalar metrics. Under PP, `aux` holds THIS device's
        per-virtual-slice stage sums, stacked (v, ...) (v=1 under GPipe);
        the update is scattered into the device's storage rows
        [sid*v, sid*v + v) of a zero stack and psum'd over 'pipe'. Under
        the dense oracle, `aux` is a [(storage row, stats)] list in global
        stage order. `mtp_aux`: [(state key, stats)] for the replicated
        MTP layers — their biases update in place (no pipe scatter: every
        device computed the identical global stats)."""
        cfg = self.cfg
        pp = cfg.pipeline_parallel
        v = cfg.virtual_stages
        mutated: dict = {}

        if pp:
            sid = jax.lax.axis_index("pipe")
            ci = aux["ci"]  # (v, layers_per_stage, E), summed over valid ticks
            # make loads global across the data axes (inside the block,
            # stats_axes covered data/fsdp/context only under CP)
            if not cfg.context_parallel:
                ci = jax.lax.psum(ci, ("data", "fsdp"))
        else:
            # (n_stages, lps, E), index-aligned with aux's global order
            ci = jnp.stack([a["ci"] for _, a in aux])

        def global_ci(raw):
            # mtp layers run replicated per device over the local batch
            # shard; outside shard_map (dense oracle) there is no axis
            if pp and not cfg.context_parallel:
                return jax.lax.psum(raw, ("data", "fsdp"))
            return raw

        mtp_ci = {name: global_ci(a["ci"]) for name, a in mtp_aux}

        if "moe_state" in wants:
            new_stack = bias_stack
            new_state: dict = {}
            rate = cfg.aux_free_bias_update_rate
            if cfg.use_aux_free and not deterministic:
                def upd(bias_j, delta_block):
                    # bias_j: (n_stages, E) storage stack; delta_block:
                    # (v, E) for this device's storage rows [sid*v, ..+v)
                    full = jnp.zeros_like(bias_j)
                    full = jax.lax.dynamic_update_slice(
                        full, delta_block.astype(bias_j.dtype), (sid * v, 0)
                    )
                    return bias_j + jax.lax.psum(full, "pipe")

                new_stack = dict(bias_stack)
                for j in range(cfg.layers_per_stage):
                    key = f"block_{j}"
                    if pp:
                        # per virtual slice: err (v, E)
                        err = (
                            jnp.mean(ci[:, j], axis=-1, keepdims=True)
                            - ci[:, j]
                        )
                        delta = rate * jnp.sign(err)
                        new_stack[key] = jax.tree.map(
                            lambda b: upd(b, delta), bias_stack[key]
                        )
                    else:
                        deltas = [None] * cfg.n_stages
                        for idx, (row, _) in enumerate(aux):
                            err = jnp.mean(ci[idx, j]) - ci[idx, j]
                            deltas[row] = rate * jnp.sign(err)
                        new_stack[key] = jax.tree.map(
                            lambda b: b + jnp.stack(deltas).astype(b.dtype),
                            bias_stack[key],
                        )
                for name, ci_m in mtp_ci.items():
                    # the canonical update rule (cell 23), from the
                    # already-psum'd load — no pipe scatter needed
                    # (replicated compute)
                    new_state[name] = jax.tree.map(
                        lambda b, c=ci_m: ops.moe.aux_free_bias_update(
                            None, b, rate, ci=c
                        ),
                        ms_all[name],
                    )
            # entries not updated this step (eval, or aux-free off) pass
            # through unchanged so the state tree keeps its structure
            passthrough = {
                k: v for k, v in (ms_all or {}).items()
                if k != "stages" and k not in new_state
            }
            mutated["moe_state"] = {"stages": new_stack, **new_state,
                                    **passthrough}

        if "moe_metrics" in wants:
            n_total = cfg.n_layers + len(mtp_aux)

            def ci_stats(rows):
                # rows: (..., E) global loads -> summed entropy / max over
                # the leading dims
                load = rows / jnp.maximum(
                    jnp.sum(rows, axis=-1, keepdims=True), 1e-9
                )
                ent = -jnp.sum(load * jnp.log(load + 1e-9), axis=-1) \
                    / jnp.log(float(cfg.n_experts))
                return jnp.sum(ent), jnp.sum(jnp.max(load, axis=-1))

            if pp:
                # load_entropy/load_max_fraction are recomputed from the
                # GLOBAL per-layer ci (tick-summed + data-psum'd above) —
                # averaging the per-tick device-local stats understates
                # routing collapse vs the dense family, which computes them
                # on the globally reduced load (advisor r3). drop_fraction
                # averages exactly (equal-size microbatches share the
                # denominator); bias_norm is tick-invariant, so its mean
                # over ticks is the value itself. MTP layers are replicated
                # per device — added OUTSIDE the pipe psum (a psum would
                # count them n_stages times).
                ent_s, max_s = ci_stats(ci)
                ent_m = max_m = drop_m = bias_m = 0.0
                for name, a in mtp_aux:
                    em, mm = ci_stats(mtp_ci[name])
                    ent_m += em
                    max_m += mm
                    drop_m += a["drop_fraction"]
                    bias_m += a["bias_norm"]
                stats = {
                    "load_entropy":
                        (jax.lax.psum(ent_s, "pipe") + ent_m) / n_total,
                    "load_max_fraction":
                        (jax.lax.psum(max_s, "pipe") + max_m) / n_total,
                }
                for k, extra in (("drop_fraction", drop_m),
                                 ("bias_norm", bias_m)):
                    v = jnp.sum(aux[k]) / n_ticks
                    stats[k] = (jax.lax.psum(v, "pipe") + extra) / n_total
            else:
                stats = {
                    k: (jnp.sum(jnp.stack([a[k] for _, a in aux]))
                        + sum(a[k] for _, a in mtp_aux)) / n_total
                    for k in _STAT_KEYS
                }
            mutated["moe_metrics"] = {"pipeline": {"stats": (stats,)}}
        return mutated

    @property
    def max_positions(self) -> int:
        return self.cfg.block_size

    # ------------------------------------------------------------------ 1f1b

    def f1b_value_and_grad(self, params, batch, rng=None, model_state=None):
        """The FLAGSHIP through the 1F1B schedule (TrainConfig.pp_schedule
        = '1f1b'): the MoE routing loads ride the schedule's aux channel
        (summed over each stage's forward units, the backward recompute's
        aux discarded), the aux-free bias update is recombined exactly
        like the GPipe path's `_mutate` (data-psum'd loads -> per-stage
        sign deltas scattered into a zero stack, pipe-psum'd), and the
        tied lm head rides as the loss head so the embedding's gradient
        sums its embed-side and head-side contributions. v1 scope:
        deterministic (the post-stack dropout of cell 31 has no
        per-microbatch key channel in the loss head), no MTP heads, no
        balance loss — the GPipe schedule serves those."""
        from solvingpapers_tpu.models.staged import f1b_lm_value_and_grad

        cfg = self.cfg
        if cfg.mtp_heads > 0:
            raise NotImplementedError(
                "MTP under pp_schedule='1f1b' is not composed (the heads "
                "need the full hidden stream); use pp_schedule='gpipe'"
            )
        if getattr(cfg, "balance_loss_weight", 0.0) > 0.0:
            raise NotImplementedError(
                "balance_loss_weight under pp_schedule='1f1b' is not "
                "composed; use pp_schedule='gpipe'"
            )
        if cfg.dropout > 0.0 or cfg.attn_dropout > 0.0:
            raise NotImplementedError(
                "the flagship's 1F1B path is deterministic-only (the "
                "post-stack dropout needs a per-microbatch key in the "
                "loss head); set dropout=0 or use pp_schedule='gpipe'"
            )
        ms_all = model_state["moe_state"]
        bias_stack = ms_all["stages"]
        tokens, targets = batch["x"], batch["y"]
        b, s = tokens.shape
        m = cfg.n_microbatches
        dt = cfg.compute_dtype
        positions = default_positions(b, s, False,
                                      max_positions=cfg.block_size)
        stage_fn = self._make_stage_fn(
            bias_stack, positions[: b // m],
            lambda j: jax.lax.axis_index("pipe"),
        )
        head = {"norm_f": params["norm_f"], "tok_emb": params["tok_emb"]}
        pe = ops.sinusoidal_position_encoding(cfg.block_size, cfg.dim)

        def embed_fn(ep):
            x = jnp.take(ep["embedding"], tokens, axis=0).astype(dt)
            x = x + cfg.pe_scale * jnp.take(pe, positions, axis=0).astype(dt)
            return x.reshape(m, b // m, s, cfg.dim)

        def head_loss(hp, h, t):
            # depth scaling -> final RMSNorm -> weight-tied head (cell 31)
            x = 2.0 * cfg.n_layers**-0.5 * h
            x = RMSNorm().apply({"params": hp["norm_f"]}, x)
            emb = hp["tok_emb"]["embedding"]
            logits = x.astype(dt) @ emb.T.astype(dt)
            return ops.cross_entropy(logits, t)

        loss, dstage, dhead, dembed, aux = f1b_lm_value_and_grad(
            params["stages"], params["tok_emb"], head, targets, m,
            embed_fn, stage_fn, head_loss, with_aux=True,
        )
        grads = {
            # tied embedding: embed-side + head-side contributions
            "tok_emb": jax.tree.map(
                lambda a, b_: a + b_, dembed, dhead["tok_emb"]
            ),
            "norm_f": dhead["norm_f"],
            "stages": dstage,
        }

        # routing-state update + metrics through the ONE recombination
        # path (_mutate's PP branch; the schedule's aux sums take the
        # GPipe layout with a leading v=1 dim)
        mutated = self._mutate(
            bias_stack, jax.tree.map(lambda a: a[None], aux),
            cfg.n_microbatches, {"moe_state", "moe_metrics"},
            deterministic=False, ms_all=ms_all,
        )
        new_ms = {"moe_state": mutated["moe_state"]}
        stats = mutated["moe_metrics"]["pipeline"]["stats"][0]
        metrics = {f"moe_{k}": v for k, v in stats.items()}
        return loss, grads, new_ms, metrics

    # ---------------------------------------------------------------- export

    def to_dense(self, params: dict, moe_state: dict):
        """Restack stage-stacked variables into the dense DeepSeekV3 layout
        and return (model, params, moe_state) — the decode path for
        PP-trained weights (PP itself has no cache support). The export
        config drops context_parallel (dense decode runs outside shard_map)."""
        from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3

        cfg = self.cfg
        name = lambda i: f"layer_{i}"  # noqa: E731
        dense_params = {
            # mtp_* entries (stored under the dense family's exact names)
            # and tok_emb/norm_f copy straight across
            **{k: v for k, v in params.items() if k != "stages"},
            **restack_to_dense(params["stages"], cfg.n_stages,
                               cfg.layers_per_stage, name,
                               storage_index=cfg.storage_index),
        }
        dense_state = {
            **{k: v for k, v in moe_state.items() if k != "stages"},
            **restack_to_dense(
                moe_state["stages"], cfg.n_stages, cfg.layers_per_stage,
                name, storage_index=cfg.storage_index,
            ),
        }
        dense_cfg = dataclasses.replace(
            cfg.layer_cfg(), context_parallel=False
        )
        return DeepSeekV3(dense_cfg), dense_params, dense_state
