"""Workload registry: name -> RunConfig (model + data + train settings)."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

from solvingpapers_tpu.sharding.mesh import MeshConfig
from solvingpapers_tpu.train.engine import TrainConfig
from solvingpapers_tpu.train.optim import OptimizerConfig


@dataclasses.dataclass(frozen=True)
class RunConfig:
    name: str
    model_family: str  # a key of `configs/families.py` `FAMILIES`
    model: Any
    train: TrainConfig
    data: dict = dataclasses.field(default_factory=dict)
    notes: str = ""


_REGISTRY: dict[str, Callable[[], RunConfig]] = {}


def register(name: str):
    def deco(fn: Callable[[], RunConfig]):
        _REGISTRY[name] = fn
        return fn

    return deco


def get_config(name: str, **overrides) -> RunConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown config {name!r}; available: {sorted(_REGISTRY)}")
    cfg = _REGISTRY[name]()
    if overrides:
        train_overrides = {
            k: v for k, v in overrides.items()
            if k in {f.name for f in dataclasses.fields(TrainConfig)}
        }
        rest = {k: v for k, v in overrides.items() if k not in train_overrides}
        if train_overrides:
            train = dataclasses.replace(cfg.train, **train_overrides)
            # keep the LR schedule horizon aligned with an overridden step count
            if "steps" in train_overrides:
                train = dataclasses.replace(
                    train,
                    optimizer=dataclasses.replace(
                        train.optimizer, total_steps=train_overrides["steps"]
                    ),
                )
            cfg = dataclasses.replace(cfg, train=train)
        if rest:
            cfg = dataclasses.replace(cfg, **rest)
    return cfg


def list_configs() -> list[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------- workloads


@register("gpt_tiny")
def _gpt_tiny() -> RunConfig:
    """CPU-runnable smoke config (debugging / CI)."""
    from solvingpapers_tpu.models.gpt import GPTConfig

    return RunConfig(
        name="gpt_tiny",
        model_family="gpt",
        model=GPTConfig(vocab_size=64, block_size=64, dim=64, n_layers=2,
                        n_heads=2, dropout=0.0),
        train=TrainConfig(
            steps=100, batch_size=16, log_every=20, eval_every=50, eval_batches=5,
            optimizer=OptimizerConfig(max_lr=3e-3, warmup_steps=10, total_steps=100),
            tokens_per_step=16 * 64,
        ),
        data={"kind": "char", "path": None, "block_size": 64},
        notes="smoke-test config, not a reference workload",
    )


@register("gpt_tiny_long")
def _gpt_tiny_long() -> RunConfig:
    """gpt_tiny with a 256-position budget: the serving benches' long-
    stream smoke config (CPU-runnable; speculative decoding needs
    streams long enough for drafts to find history, which gpt_tiny's 64
    positions cannot hold). Train at the full block_size — the learned
    position table has no values beyond the trained length."""
    from solvingpapers_tpu.models.gpt import GPTConfig

    return RunConfig(
        name="gpt_tiny_long",
        model_family="gpt",
        model=GPTConfig(vocab_size=64, block_size=256, dim=64, n_layers=2,
                        n_heads=2, dropout=0.0),
        train=TrainConfig(
            steps=300, batch_size=16, log_every=50, eval_every=0,
            optimizer=OptimizerConfig(max_lr=3e-3, warmup_steps=10,
                                      total_steps=300),
            tokens_per_step=16 * 256,
        ),
        data={"kind": "char", "path": None, "block_size": 256},
        notes="smoke/bench config for long serve streams, not a "
              "reference workload",
    )


@register("gpt_shakespeare")
def _gpt_shakespeare() -> RunConfig:
    """The reference's gpt/gpt-jax.ipynb cell 8 hyperparameters."""
    from solvingpapers_tpu.models.gpt import GPTConfig

    return RunConfig(
        name="gpt_shakespeare",
        model_family="gpt",
        model=GPTConfig(
            vocab_size=65, block_size=256, dim=256, n_layers=8, n_heads=1,
            dropout=0.1, dtype="bfloat16",
        ),
        train=TrainConfig(
            steps=1000,
            batch_size=128,
            log_every=50,
            eval_every=100,
            eval_batches=20,
            # 10 on-device steps per dispatch (lax.scan window): amortizes
            # host dispatch latency, bit-identical to sequential stepping
            scan_steps=10,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=1e-3, warmup_steps=0, total_steps=1000,
                weight_decay=0.1, grad_clip=1.0,
            ),
            tokens_per_step=128 * 256,
        ),
        data={"kind": "char", "path": None, "block_size": 256},
        notes="gpt/gpt-jax.ipynb cells 8-19; val loss 1.8871 @ step 1000 on T4",
    )


@register("llama3_shakespeare")
def _llama3_shakespeare() -> RunConfig:
    """The reference's llama3/LLaMA-jax.ipynb cell 9 hyperparameters.

    The notebook trains with hand-rolled SGD (cell 29) over 30 epochs x
    1000 steps (cell 31); optimizer name 'sgd' preserves that parity while
    `adamw` remains a config switch. The notebook tokenizes with tiktoken
    gpt2 BPE; this config defaults to the char pipeline (vocab resized by
    the factory) since the BPE merges table is not bundled offline.
    """
    from solvingpapers_tpu.models.llama3 import LlamaConfig

    return RunConfig(
        name="llama3_shakespeare",
        model_family="llama3",
        model=LlamaConfig(
            vocab_size=50257, max_seq_len=128, dim=256, n_layers=2, n_heads=4,
            n_kv_heads=2, hidden_dim=1024, dropout=0.0, dtype="bfloat16",
        ),
        train=TrainConfig(
            steps=30_000,  # 30 epochs x 1000 steps (cell 31)
            batch_size=16,
            log_every=100,
            eval_every=1000,
            eval_batches=20,
            optimizer=OptimizerConfig(
                name="sgd", max_lr=3e-4, warmup_steps=0, total_steps=30_000,
                grad_clip=0.0, weight_decay=0.0, min_lr_ratio=1.0,
            ),
            tokens_per_step=16 * 128,
        ),
        data={"kind": "char", "path": None, "block_size": 128},
        notes="LLaMA-jax.ipynb cells 9, 29-31; epoch-avg loss 8.10→5.47 over 30k steps",
    )


@register("dsv3_tinystories")
def _dsv3_tinystories() -> RunConfig:
    """deepseekv3/deepseekv3.ipynb cells 4, 42-44, 54: the reference flagship.

    196.08M params; 10k steps x 4,096 tok/step (bs 16 x block 256); AdamW
    6e-4 beta=(0.9,0.95) wd 0.1 clip 1.0, warmup 400 -> cosine to 0.1*max;
    final train loss 2.90068 / ppl 18.18644 on 2xT4 (readme tables).
    The notebook tokenizes TinyStories with GPT-2 BPE; offline default here
    is the char pipeline (factory resizes the vocab).
    """
    from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3Config

    return RunConfig(
        name="dsv3_tinystories",
        model_family="deepseekv3",
        # pe_scale=0.02: balances PE vs token signal (DeepSeekV3Config);
        # with the notebook's raw PE the routing gate specializes experts
        # by position — the drop_fraction 0.196 collapse in the round-2
        # artifacts/dsv3_run traces to it. capacity_factor 4 + the
        # sequence-wise balance term absorb the residual clustering skew of
        # the memorization corpus (r3 measured: drop 0.196 -> 0.072 from
        # pe_scale alone; the two knobs take it to ~0)
        model=DeepSeekV3Config(dtype="bfloat16", pe_scale=0.02,
                               capacity_factor=4.0,
                               balance_loss_weight=1e-2),
        train=TrainConfig(
            steps=10_000,
            batch_size=16,
            log_every=100,
            eval_every=500,
            eval_batches=20,
            ckpt_every=1000,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=6e-4, warmup_steps=400, total_steps=10_000,
                b1=0.9, b2=0.95, weight_decay=0.1, grad_clip=1.0, eps=1e-8,
            ),
            tokens_per_step=16 * 256,
        ),
        data={"kind": "char", "path": None, "block_size": 256},
        notes="deepseekv3 readme: loss 2.90068 / ppl 18.18644 @ 10k steps",
    )


@register("gemma_char")
def _gemma_char() -> RunConfig:
    """gemma/gemma.ipynb hyperparameters (char Tiny-Shakespeare).

    Reference: dim 768, 12 layers, 4/2 heads, block 128, batch 64. The
    notebook's cell-1 beta/wd knobs are DEAD — cell 17 constructs plain
    torch AdamW(lr=2.5e-4), i.e. betas (0.9, 0.999), wd 0.01, constant LR,
    no clipping; those actually-used values are what this config encodes.
    Run stopped at step 3500 of 5000 (markdown cell 19).
    """
    from solvingpapers_tpu.models.gemma import GemmaConfig

    return RunConfig(
        name="gemma_char",
        model_family="gemma",
        model=GemmaConfig(dtype="bfloat16"),
        train=TrainConfig(
            steps=5000,
            batch_size=64,
            log_every=100,
            eval_every=500,
            eval_batches=20,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=2.5e-4, warmup_steps=0, total_steps=5000,
                b1=0.9, b2=0.999, weight_decay=0.01, grad_clip=0.0,
                min_lr_ratio=1.0,
            ),
            tokens_per_step=64 * 128,
        ),
        data={"kind": "char", "path": None, "block_size": 128},
        notes="gemma.ipynb cells 1, 17-18; 127.5M params, stopped at 3500 steps",
    )


# --------------------------------------------------- entropy-calibrated rows
# Quality-parity workloads on the order-2 Markov corpus (data/synthetic.py
# MarkovSource): the corpus' exact entropy rate (~2.362 nats for the pinned
# vocab=64/alpha=0.1/seed=1234 chain) is an ABSOLUTE val-loss target — the
# offline stand-in for the reference's real-data val numbers
# (gpt-jax.ipynb cell 18 val 1.8871; deepseekv3 readme loss 2.90068).
# tools/parity_suite.py reports val_loss - H per row and gates on it.

_MARKOV_DATA = {"kind": "char", "source": "markov", "block_size": 256,
                "n_chars": 4_000_000}


def _markov_train(steps: int, batch_size: int, block: int,
                  max_lr: float = 1e-3) -> TrainConfig:
    return TrainConfig(
        steps=steps, batch_size=batch_size, log_every=100,
        eval_every=max(steps // 4, 1), eval_batches=20,
        optimizer=OptimizerConfig(
            name="adamw", max_lr=max_lr, warmup_steps=min(100, steps // 10),
            total_steps=steps, weight_decay=0.01, grad_clip=1.0,
        ),
        tokens_per_step=batch_size * block,
    )


@register("gpt_markov")
def _gpt_markov() -> RunConfig:
    from solvingpapers_tpu.models.gpt import GPTConfig

    return RunConfig(
        name="gpt_markov",
        model_family="gpt",
        model=GPTConfig(vocab_size=64, block_size=256, dim=256, n_layers=4,
                        n_heads=4, dropout=0.0, dtype="bfloat16"),
        train=_markov_train(3000, 64, 256),
        data=dict(_MARKOV_DATA),
        notes="entropy-calibrated quality row; target val_loss -> H ~= 2.362",
    )


@register("llama3_markov")
def _llama3_markov() -> RunConfig:
    from solvingpapers_tpu.models.llama3 import LlamaConfig

    return RunConfig(
        name="llama3_markov",
        model_family="llama3",
        model=LlamaConfig(vocab_size=64, max_seq_len=256, dim=256, n_layers=3,
                          n_heads=4, n_kv_heads=2, dropout=0.0, dtype="bfloat16"),
        train=_markov_train(3000, 64, 256),
        data=dict(_MARKOV_DATA),
        notes="entropy-calibrated quality row; target val_loss -> H ~= 2.362",
    )


@register("gemma_markov")
def _gemma_markov() -> RunConfig:
    from solvingpapers_tpu.models.gemma import GemmaConfig

    return RunConfig(
        name="gemma_markov",
        model_family="gemma",
        model=GemmaConfig(vocab_size=64, max_seq_len=256, dim=256, n_layers=4,
                          n_heads=4, n_kv_heads=2, dropout=0.0, dtype="bfloat16"),
        train=_markov_train(3000, 64, 256),
        # capacity-matched corpus (VERDICT r4 ask 6 — the 0.139-nat outlier
        # diagnosed): the round-5 ablation (tools/gemma_markov_ablation.py,
        # 3000 steps each on the v5e) cleared the verdict's suspect list —
        # full-MHA 0.144 and SwiGLU-activation 0.132 sit AT the 0.139
        # baseline, so neither grouped-MQA nor GeGLU is the cause — while
        # 16M chars drops the gap to 0.044, best of the dense zoo. Gemma's
        # FFN carries ~2.25x llama3_markov's FFN params (4*dim GeGLU hidden
        # vs (2/3)*4*dim SwiGLU, 4 layers vs 3), so on the shared 4M-char
        # corpus it memorizes like dsv3 did in r4; the honest fix is the
        # same capacity-matched 16M-char source, not a schedule or
        # architecture change (supporting evidence: lr 5e-4 and 3-layer
        # variants land at 0.093/0.097 by REDUCING fit, not generalizing).
        data={**_MARKOV_DATA, "n_chars": 16_000_000},
        notes="entropy-calibrated quality row; target val_loss -> H ~= 2.362",
    )


@register("dsv3_markov")
def _dsv3_markov() -> RunConfig:
    from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3Config

    return RunConfig(
        name="dsv3_markov",
        model_family="deepseekv3",
        # pe_scale + rope_dim: see DeepSeekV3Config — position-critical
        # data is unlearnable (gap 1.80 nats) with the notebook's raw
        # sinusoidal PE and no relative-position channel
        model=DeepSeekV3Config(vocab_size=64, block_size=256, dim=256,
                               n_layers=4, n_heads=4, latent_dim=32,
                               rope_dim=32, pe_scale=0.02,
                               n_experts=8, top_experts=2, dropout=0.0,
                               attn_dropout=0.0, dtype="bfloat16"),
        train=_markov_train(3000, 64, 256),
        # capacity-matched corpus: the MoE carries ~5x the dense peers'
        # params (8 experts x SwiGLU per layer) and memorizes the shared
        # 4M-char corpus past ~2k steps (r4 measured gap 0.335 at 3000
        # steps there — the r3 1200-step pin was hiding this). The chain
        # is an unbounded synthetic source, so the honest fix is more
        # held-out-equivalent data, not a shorter schedule: at 16M chars
        # the same 3000-step run generalizes (gap 0.032, load entropy
        # 0.996, zero drops).
        data={**_MARKOV_DATA, "n_chars": 16_000_000},
        notes="entropy-calibrated quality row; target val_loss -> H ~= 2.362",
    )


@register("llama3_long")
def _llama3_long() -> RunConfig:
    """Long-context capability demo (nothing comparable in the reference —
    its max context is 256 tokens): llama with context_parallel=True for
    ring-attention training over a 'context' mesh axis. Driven end-to-end
    by the stock Trainer/CLI: the train step runs the whole loss inside
    shard_map with the sequence sharded (TrainConfig.context_parallel)."""
    from solvingpapers_tpu.models.llama3 import LlamaConfig

    return RunConfig(
        name="llama3_long",
        model_family="llama3",
        model=LlamaConfig(
            vocab_size=50257, max_seq_len=32_768, dim=1024, n_layers=16,
            n_heads=16, n_kv_heads=8, dropout=0.0, dtype="bfloat16",
            context_parallel=True, use_flash=True,
        ),
        train=TrainConfig(
            steps=10_000, batch_size=8, log_every=50, eval_every=500,
            eval_batches=8, ckpt_every=1000,
            mesh=MeshConfig(data=-1, context=4),
            context_parallel=True,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=3e-4, warmup_steps=200, total_steps=10_000,
                weight_decay=0.1, grad_clip=1.0,
            ),
            tokens_per_step=8 * 32_768,
        ),
        data={"kind": "bpe", "path": None, "block_size": 32_768,
              "bpe_vocab_size": 32_000, "synthetic_chars": 4_000_000},
        notes="beyond-reference long-context config; sequence sharded over "
              "the context axis, ring attention over ICI",
    )


@register("gpt_pp")
def _gpt_pp() -> RunConfig:
    """Pipeline-parallel GPT (SURVEY.md §2.3 PP row; nothing comparable in
    the reference): the reference GPT-jax architecture with its 8 decoder
    blocks split into 4 stages over the 'pipe' mesh axis, GPipe microbatch
    schedule inside shard_map, composed with data parallelism."""
    from solvingpapers_tpu.models.gpt_pipe import GPTPipeConfig

    return RunConfig(
        name="gpt_pp",
        model_family="gpt_pipe",
        model=GPTPipeConfig(
            vocab_size=65, block_size=256, dim=256, n_layers=8, n_heads=4,
            dtype="bfloat16", n_stages=4, n_microbatches=8,
            pipeline_parallel=True,
            # the reference GPT recipe's dropout (gpt-jax.ipynb cell 8)
            # trains under the schedule via per-(stage, microbatch, layer)
            # keys
            dropout=0.1,
        ),
        train=TrainConfig(
            steps=1000, batch_size=64, log_every=50, eval_every=200,
            eval_batches=10,
            mesh=MeshConfig(data=-1, pipe=4),
            pipeline_parallel=True,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=1e-3, warmup_steps=100, total_steps=1000,
                weight_decay=0.1, grad_clip=1.0,
            ),
            tokens_per_step=64 * 256,
        ),
        data={"kind": "char", "path": None, "block_size": 256},
        notes="GPipe over 4 stages x data parallel; stage params stored "
              "sharded over 'pipe' (PP_RULES)",
    )


@register("gpt_pp_smoke")
def _gpt_pp_smoke() -> RunConfig:
    """CPU-mesh-sized gpt_pp (virtual 8-device mesh: data=2 x pipe=4)."""
    from solvingpapers_tpu.models.gpt_pipe import GPTPipeConfig

    return RunConfig(
        name="gpt_pp_smoke",
        model_family="gpt_pipe",
        model=GPTPipeConfig(
            vocab_size=256, block_size=64, dim=32, n_layers=4, n_heads=2,
            dtype="float32", n_stages=4, n_microbatches=4,
            pipeline_parallel=True,
            dropout=0.1,  # smoke the schedule-keyed dropout path too
        ),
        train=TrainConfig(
            steps=20, batch_size=8, log_every=5, eval_every=10,
            eval_batches=2,
            mesh=MeshConfig(data=-1, pipe=4),
            pipeline_parallel=True,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=1e-3, warmup_steps=5, total_steps=20,
                weight_decay=0.1, grad_clip=1.0,
            ),
            tokens_per_step=8 * 64,
        ),
        data={"kind": "char", "path": None, "block_size": 64},
        notes="gpt_pp at smoke scale for the virtual CPU mesh",
    )


@register("dsv3_pp")
def _dsv3_pp() -> RunConfig:
    """The flagship pipelined: DSV3Pipe (MLA + MoE staged over 'pipe' with
    shard-invariant routing-state updates) at the dsv3_tinystories scale,
    on a data x pipe mesh (8 real chips: data=2 x pipe=4). PP x FSDP (the
    embedding ZeRO-gathered in-step) is exercised by dsv3_pp_smoke's
    data=2 x fsdp=2 x pipe=2 mesh; add fsdp=2 here when chip count
    allows."""
    from solvingpapers_tpu.models.deepseekv3_pipe import DSV3PipeConfig

    return RunConfig(
        name="dsv3_pp",
        model_family="dsv3_pipe",
        model=DSV3PipeConfig(
            vocab_size=50257, block_size=256, dim=512, n_layers=8, n_heads=8,
            latent_dim=64, rope_dim=32, pe_scale=0.02, n_experts=8,
            top_experts=2, dtype="bfloat16", n_stages=4, n_microbatches=8,
            pipeline_parallel=True,
            # the reference recipe's dropout 0.1 (deepseekv3.ipynb cell 4)
            # now trains under the schedule (per-(stage, microbatch, layer)
            # mask keys)
            dropout=0.1, attn_dropout=0.1,
        ),
        train=TrainConfig(
            steps=10_000, batch_size=32, log_every=100, eval_every=500,
            eval_batches=8, ckpt_every=1000,
            mesh=MeshConfig(data=-1, pipe=4),
            pipeline_parallel=True,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=6e-4, warmup_steps=400,
                total_steps=10_000, b1=0.9, b2=0.95, weight_decay=0.1,
                grad_clip=1.0,
            ),
            tokens_per_step=32 * 256,
        ),
        data={"kind": "char", "path": None, "block_size": 256},
        notes="flagship staged over the pipe axis; beyond-reference scale-out",
    )


@register("dsv3_pp_smoke")
def _dsv3_pp_smoke() -> RunConfig:
    """CPU-mesh-sized dsv3_pp (virtual 8-device mesh: data=2 x fsdp=2 x
    pipe=2 — exercises PP x FSDP with the MoE state recombination)."""
    from solvingpapers_tpu.models.deepseekv3_pipe import DSV3PipeConfig

    return RunConfig(
        name="dsv3_pp_smoke",
        model_family="dsv3_pipe",
        model=DSV3PipeConfig(
            vocab_size=256, block_size=64, dim=32, n_layers=4, n_heads=4,
            latent_dim=8, rope_dim=8, pe_scale=0.02, n_experts=4,
            top_experts=2, n_stages=2, n_microbatches=2,
            pipeline_parallel=True,
            # smoke the r4 paths: schedule-keyed dropout + replicated MTP
            dropout=0.1, attn_dropout=0.1, mtp_heads=1,
        ),
        train=TrainConfig(
            steps=20, batch_size=8, log_every=5, eval_every=10,
            eval_batches=2,
            mesh=MeshConfig(data=-1, fsdp=2, pipe=2),
            pipeline_parallel=True,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=1e-3, warmup_steps=5, total_steps=20,
                weight_decay=0.1, grad_clip=1.0,
            ),
            tokens_per_step=8 * 64,
        ),
        data={"kind": "char", "path": None, "block_size": 64},
        notes="dsv3_pp at smoke scale (PP x FSDP) for the virtual CPU mesh",
    )


@register("llama3_pp_smoke")
def _llama3_pp_smoke() -> RunConfig:
    """CPU-mesh-sized llama3 pipeline run (data=2 x pipe=4)."""
    from solvingpapers_tpu.models.llama3_pipe import LlamaPipeConfig

    return RunConfig(
        name="llama3_pp_smoke",
        model_family="llama3_pipe",
        model=LlamaPipeConfig(
            vocab_size=256, max_seq_len=64, dim=32, n_layers=4, n_heads=4,
            n_kv_heads=2, n_stages=4, n_microbatches=4,
            pipeline_parallel=True,
        ),
        train=TrainConfig(
            steps=20, batch_size=8, log_every=5, eval_every=10,
            eval_batches=2,
            mesh=MeshConfig(data=-1, pipe=4),
            pipeline_parallel=True,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=1e-3, warmup_steps=5, total_steps=20,
                weight_decay=0.1, grad_clip=1.0,
            ),
            tokens_per_step=8 * 64,
        ),
        data={"kind": "char", "path": None, "block_size": 64},
        notes="llama3 staged over the pipe axis at smoke scale",
    )


@register("llama3_long_smoke")
def _llama3_long_smoke() -> RunConfig:
    """CPU-mesh-sized llama3_long: the same context-parallel Trainer/CLI
    path (ring attention inside shard_map over data=2 x context=4) at toy
    dims, runnable on the virtual 8-device mesh in seconds. Release smoke
    test for the CP front door."""
    from solvingpapers_tpu.models.llama3 import LlamaConfig

    return RunConfig(
        name="llama3_long_smoke",
        model_family="llama3",
        model=LlamaConfig(
            vocab_size=256, max_seq_len=256, dim=64, n_layers=2,
            n_heads=4, n_kv_heads=2, dropout=0.0, dtype="float32",
            # flash on: the smoke exercises the same ring-flash core as
            # llama3_long (interpret-mode kernel on the CPU mesh)
            context_parallel=True, use_flash=True,
        ),
        train=TrainConfig(
            steps=20, batch_size=4, log_every=5, eval_every=10,
            eval_batches=2,
            mesh=MeshConfig(data=-1, context=4),
            context_parallel=True,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=1e-3, warmup_steps=5, total_steps=20,
                weight_decay=0.1, grad_clip=1.0,
            ),
            tokens_per_step=4 * 256,
        ),
        data={"kind": "char", "path": None, "block_size": 256},
        notes="llama3_long at smoke scale for the virtual CPU mesh",
    )


@register("dsv3_long")
def _dsv3_long() -> RunConfig:
    """Long-context flagship demo (nothing comparable in the reference):
    DeepSeekV3 (MLA + MoE) at 16,384-token context on a single chip via
    flash-MLA (absorbed-query attention through the Pallas kernel; the
    dense einsum path cannot even compile at this length) + per-layer
    remat. Benchmark cell `dsv3_long.train_16k` (PERF.md)."""
    from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3Config

    return RunConfig(
        name="dsv3_long",
        model_family="deepseekv3",
        model=DeepSeekV3Config(
            vocab_size=50257, block_size=16_384, dtype="bfloat16",
            use_flash=True, remat=True, pe_scale=0.02, rope_dim=64,
        ),
        train=TrainConfig(
            steps=10_000, batch_size=1, log_every=50, eval_every=500,
            eval_batches=4, ckpt_every=1000,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=3e-4, warmup_steps=200, total_steps=10_000,
                b1=0.9, b2=0.95, weight_decay=0.1, grad_clip=1.0,
            ),
            tokens_per_step=16_384,
        ),
        data={"kind": "bpe", "path": None, "block_size": 16_384,
              "bpe_vocab_size": 32_000, "synthetic_chars": 2_000_000},
        notes="beyond-reference: 64x the reference's maximum context for "
              "its own flagship architecture, one chip",
    )


@register("qwen3next_80b_a3b")
def _qwen3next_80b_a3b() -> RunConfig:
    """Qwen3-Next-80B-A3B-Instruct at its published size
    (huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct config.json): 48
    layers, three Gated DeltaNet to one gated attention, hidden 2048, 512
    experts of width 512 with 10 a token and a shared one, vocabulary
    151,936. Far more than one chip holds (80B parameters): what runs is a
    cut of it, one expert-parallel rank's share of a few layers
    (benchmarks/configs/qwen3next_ep16.json sets `num_hidden_layers`,
    `num_experts`, the experts held, and `vocab_size`; the router keeps its
    512 outputs). Training only: no decode cache holds recurrent state yet.

    The job (assumed, the source states none): one sequence of 16,384
    tokens a step, AdamW 3e-4 beta=(0.9, 0.95) wd 0.1 clip 1.0, 100 steps
    of warm-up -> cosine to 0.1*max; capacity factor 2; remat a layer."""
    from solvingpapers_tpu.models.qwen3next import Qwen3NextConfig

    return RunConfig(
        name="qwen3next_80b_a3b",
        model_family="qwen3next",
        model=Qwen3NextConfig(),
        train=TrainConfig(
            steps=10_000, batch_size=1, log_every=50, eval_every=500,
            eval_batches=4, ckpt_every=1000,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=3e-4, warmup_steps=100,
                total_steps=10_000, b1=0.9, b2=0.95, weight_decay=0.1,
                grad_clip=1.0,
            ),
            tokens_per_step=16_384,
        ),
        data={"kind": "bpe", "path": None, "block_size": 16_384,
              "bpe_vocab_size": 32_000, "synthetic_chars": 2_000_000},
        notes="published widths; run through a cut (experts held, layers, "
              "vocabulary slice), see benchmarks/configs/qwen3next_ep16.json",
    )


@register("kimi_linear_48b_a3b")
def _kimi_linear_48b_a3b() -> RunConfig:
    """Kimi-Linear-48B-A3B-Instruct at its published size
    (huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct config.json):
    27 layers, Kimi Delta Attention (32 heads of 128, a decay per key
    channel) three to one with latent attention that carries no positions
    (keys 192 wide, values 128), hidden 2304, one dense layer of width 9216,
    then 256 experts of width 1024 with 8 a token behind a sigmoid router
    and a shared one, vocabulary 163,840. Far more than one chip holds (48B
    parameters): what runs is a cut of it, one expert-parallel rank's share
    of a few layers (benchmarks/configs/kimi_linear_ep32.json sets
    `num_hidden_layers`, `num_experts`, the experts held, and `vocab_size`;
    the router keeps its 256 outputs, and the layer pattern is read from the
    published `full_attn_layers` / `kda_layers`). Training only: no decode
    cache holds recurrent state yet.

    The job (assumed, the source states none): one sequence of 16,384
    tokens a step, AdamW 3e-4 beta=(0.9, 0.95) wd 0.1 clip 1.0, 100 steps
    of warm-up -> cosine to 0.1*max; capacity factor 4 (at 2 the first
    steps dropped up to 2% of the pairs routed to one rank's experts);
    remat a layer."""
    from solvingpapers_tpu.models.kimi_linear import KimiLinearConfig

    return RunConfig(
        name="kimi_linear_48b_a3b",
        model_family="kimi_linear",
        model=KimiLinearConfig(),
        train=TrainConfig(
            steps=10_000, batch_size=1, log_every=50, eval_every=500,
            eval_batches=4, ckpt_every=1000,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=3e-4, warmup_steps=100,
                total_steps=10_000, b1=0.9, b2=0.95, weight_decay=0.1,
                grad_clip=1.0,
            ),
            tokens_per_step=16_384,
        ),
        data={"kind": "bpe", "path": None, "block_size": 16_384,
              "bpe_vocab_size": 32_000, "synthetic_chars": 2_000_000},
        notes="published widths; run through a cut (experts held, layers, "
              "vocabulary slice), see benchmarks/configs/kimi_linear_ep32.json",
    )


@register("nemotron3_nano_30b_a3b")
def _nemotron3_nano_30b_a3b() -> RunConfig:
    """NVIDIA-Nemotron-3-Nano-30B-A3B at its published size
    (huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json):
    52 layers, each ONE sub-block of the kind its pattern says: 23 Mamba-2
    (64 heads of 64, 8 groups, state 128, convolution of 4 with bias), 23
    MoE (128 ungated squared-ReLU experts of width 1856 with 6 a token
    behind a sigmoid router, and a shared one of 3712), 6 attention (32
    heads on 2 of width 128, no positions), hidden 2688, vocabulary
    131,072. Far more than one chip holds (31.6B parameters): what runs is
    a cut of it, one expert-parallel rank's share of a few layers
    (benchmarks/configs/nemotron3_nano_ep16.json sets `num_hidden_layers`,
    `n_routed_experts`, the experts held, and `vocab_size`; the router
    keeps its 128 outputs, and the kinds of the layers are read from the
    published `hybrid_override_pattern`). Training only: no decode cache
    holds recurrent state yet.

    The job (assumed, the source states none): one sequence of 16,384
    tokens a step, AdamW 3e-4 beta=(0.9, 0.95) wd 0.1 clip 1.0, 100 steps
    of warm-up -> cosine to 0.1*max; capacity factor 8 (at 4 the first
    steps dropped up to 6% of the pairs routed to one rank's experts on two
    seeds of fourteen); remat a layer."""
    from solvingpapers_tpu.models.nemotron_h import NemotronHConfig

    return RunConfig(
        name="nemotron3_nano_30b_a3b",
        model_family="nemotron_h",
        model=NemotronHConfig(),
        train=TrainConfig(
            steps=10_000, batch_size=1, log_every=50, eval_every=500,
            eval_batches=4, ckpt_every=1000,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=3e-4, warmup_steps=100,
                total_steps=10_000, b1=0.9, b2=0.95, weight_decay=0.1,
                grad_clip=1.0,
            ),
            tokens_per_step=16_384,
        ),
        data={"kind": "bpe", "path": None, "block_size": 16_384,
              "bpe_vocab_size": 32_000, "synthetic_chars": 2_000_000},
        notes="published widths; run through a cut (experts held, layers, "
              "vocabulary slice), see "
              "benchmarks/configs/nemotron3_nano_ep16.json",
    )


@register("ouro_2p6b")
def _ouro_2p6b() -> RunConfig:
    """Ouro-2.6B at its published size
    (huggingface.co/ByteDance/Ouro-2.6B config.json): 48 sandwich-normed
    layers (16 heads on 16 of width 128, RoPE theta 1e6 over the whole
    width, SwiGLU 5632), hidden 2048, vocabulary 49,152 untied, the whole
    stack run `total_ut_steps` = 4 times with the same weights, a final
    norm, the head and an exit gate after every pass. 2.67B parameters,
    42.7 GB of training state at 16 bytes: more than one chip holds; what
    runs is a cut in depth, one pipeline stage's layers
    (benchmarks/configs/ouro_2p6b_pp6.json sets `num_hidden_layers`).
    Training only: no decode cache a (pass, layer) yet (ROADMAP R-M15).

    The job (assumed, the source states none): two sequences of 4,096
    tokens a step, AdamW 3e-4 beta=(0.9, 0.95) wd 0.1 clip 1.0, 100 steps
    of warm-up -> cosine to 0.1*max; the loss's entropy weight 0.1; remat a
    layer application."""
    from solvingpapers_tpu.models.ouro import OuroConfig

    return RunConfig(
        name="ouro_2p6b",
        model_family="ouro",
        model=OuroConfig(),
        train=TrainConfig(
            steps=10_000, batch_size=2, log_every=50, eval_every=500,
            eval_batches=4, ckpt_every=1000,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=3e-4, warmup_steps=100,
                total_steps=10_000, b1=0.9, b2=0.95, weight_decay=0.1,
                grad_clip=1.0,
            ),
            tokens_per_step=8_192,
        ),
        data={"kind": "bpe", "path": None, "block_size": 4_096,
              "bpe_vocab_size": 32_000, "synthetic_chars": 2_000_000},
        notes="published widths; run through a cut in depth (one pipeline "
              "stage's layers), see benchmarks/configs/ouro_2p6b_pp6.json",
    )


@register("granite4_h_micro")
def _granite4_h_micro() -> RunConfig:
    """granite-4.0-h-micro at its published size
    (huggingface.co/ibm-granite/granite-4.0-h-micro config.json,
    `granitemoehybrid` with no routed experts): 40 layers, each a mixer AND
    a dense SwiGLU of 8,192 behind scaled residual adds (0.22); 36 Mamba-2
    mixers (64 heads of 64 that share ONE group's B and C, state 128,
    convolution of 4 with bias, chunks of 256) and 4 attention (32 heads on
    8 of width 64, softmax at 1/64, no positions) at layers 5, 15, 25, 35;
    hidden 2048, vocabulary 100,352, the embedding times 12 and tied to a
    head whose logits are divided by 8. 3.19B parameters, 51 GB of training
    state at 16 bytes: more than one chip holds; what runs is a cut, one
    pipeline stage's layers and a slice of the vocabulary
    (benchmarks/configs/granite4_h_micro_pp4.json sets `num_hidden_layers`
    and `vocab_size`; the kinds of the layers are read from the published
    `layer_types`). Training only: no decode cache holds recurrent state
    yet (ROADMAP R-M7).

    The job (assumed, the source states none): one sequence of 8,192 tokens
    a step, AdamW 3e-4 beta=(0.9, 0.95) wd 0.1 clip 1.0, 100 steps of
    warm-up -> cosine to 0.1*max; remat a layer."""
    from solvingpapers_tpu.models.granite_hybrid import GraniteHybridConfig

    return RunConfig(
        name="granite4_h_micro",
        model_family="granite_hybrid",
        model=GraniteHybridConfig(),
        train=TrainConfig(
            steps=10_000, batch_size=1, log_every=50, eval_every=500,
            eval_batches=4, ckpt_every=1000,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=3e-4, warmup_steps=100,
                total_steps=10_000, b1=0.9, b2=0.95, weight_decay=0.1,
                grad_clip=1.0,
            ),
            tokens_per_step=8_192,
        ),
        data={"kind": "bpe", "path": None, "block_size": 8_192,
              "bpe_vocab_size": 32_000, "synthetic_chars": 2_000_000},
        notes="published widths; run through a cut (one pipeline stage's "
              "layers, a vocabulary slice), see "
              "benchmarks/configs/granite4_h_micro_pp4.json",
    )


@register("keye_vl2_30b_a3b")
def _keye_vl2_30b_a3b() -> RunConfig:
    """Keye-VL-2.0-30B-A3B's language model at its published size
    (huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B config.json, `KeyeVL2`;
    text tokens only, the vision tower is not built): 48 layers, every one
    grouped-query attention (32 heads on 4 of width 128, q- and k-norm,
    rotary at theta 1e7) over the 2,048 keys a lightning indexer (16 heads
    of 64 on one key head) picks for each query, then 128 experts of width
    768 with 8 a token behind a softmax router and no shared expert; hidden
    2048, vocabulary 151,936. 30.6B parameters: more than one chip holds;
    what runs is a cut of it, one expert-parallel rank's share of a few
    layers (benchmarks/configs/keye_vl2_ep8.json sets `num_hidden_layers`,
    `num_experts` / `num_local_experts`, the experts held, and `vocab_size`;
    the router keeps its 128 outputs). Training only: no cache holds the
    indexer's keys and no decode step selects (ROADMAP R-M13).

    The job (assumed, the source states none): the SPARSE training stage,
    indexer and model together (cross-entropy + 0.001 x balance + the
    indexer's KL, a mean over layers); one sequence of 16,384 tokens a step,
    AdamW 3e-4 beta=(0.9, 0.95) wd 0.1 clip 1.0, 100 steps of warm-up ->
    cosine to 0.1*max; capacity factor 2; remat a layer."""
    from solvingpapers_tpu.models.keye_vl import KeyeVLConfig

    return RunConfig(
        name="keye_vl2_30b_a3b",
        model_family="keye_vl",
        model=KeyeVLConfig(),
        train=TrainConfig(
            steps=10_000, batch_size=1, log_every=50, eval_every=500,
            eval_batches=4, ckpt_every=1000,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=3e-4, warmup_steps=100,
                total_steps=10_000, b1=0.9, b2=0.95, weight_decay=0.1,
                grad_clip=1.0,
            ),
            tokens_per_step=16_384,
        ),
        data={"kind": "bpe", "path": None, "block_size": 16_384,
              "bpe_vocab_size": 32_000, "synthetic_chars": 2_000_000},
        notes="published widths; run through a cut (experts held, layers, "
              "vocabulary slice), see benchmarks/configs/keye_vl2_ep8.json",
    )


@register("dsv3_mtp")
def _dsv3_mtp() -> RunConfig:
    """The flagship with multi-token prediction ENABLED (2 extra heads,
    loss weight 0.3). The reference builds the full MTP machinery but ships
    mtp_heads=0 (deepseekv3.ipynb cells 33, 46 — the else-branch runs);
    this config exercises the capability the notebook only gestures at."""
    from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3Config

    return RunConfig(
        name="dsv3_mtp",
        model_family="deepseekv3",
        model=DeepSeekV3Config(dtype="bfloat16", mtp_heads=2, pe_scale=0.02),
        train=TrainConfig(
            steps=10_000, batch_size=16, log_every=50, eval_every=500,
            eval_batches=8, ckpt_every=1000,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=6e-4, warmup_steps=400, total_steps=10_000,
                b1=0.9, b2=0.95, weight_decay=0.1, grad_clip=1.0,
            ),
            tokens_per_step=16 * 256,
        ),
        data={"kind": "char", "path": None, "block_size": 256},
        notes="deepseekv3 with mtp_heads=2 live (the reference's dormant "
              "branch); main CE + 0.3 x MTP loss",
    )


@register("dsv3_long_cp")
def _dsv3_long_cp() -> RunConfig:
    """The flagship at 65,536-token context via context parallelism: MLA
    rings over the latent stream across a 4-way 'context' axis (flash
    kernel per chunk), MoE routing state psum'd shard-invariant — 4x the
    single-chip dsv3_long ceiling, 256x the reference's maximum context.
    MTP (2 heads) composes: the i+k shift is a ppermute halo from the
    right neighbor (sharding.cp_halo_right), so long-context CP and the
    reference's MTP training feature are no longer mutually exclusive."""
    from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3Config

    return RunConfig(
        name="dsv3_long_cp",
        model_family="deepseekv3",
        model=DeepSeekV3Config(
            vocab_size=50257, block_size=65_536, dtype="bfloat16",
            use_flash=True, remat=True, context_parallel=True,
            dropout=0.0, attn_dropout=0.0, pe_scale=0.02, rope_dim=64,
            mtp_heads=2,
        ),
        train=TrainConfig(
            steps=10_000, batch_size=4, log_every=50, eval_every=500,
            eval_batches=4, ckpt_every=1000,
            mesh=MeshConfig(data=-1, context=4),
            context_parallel=True,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=3e-4, warmup_steps=200, total_steps=10_000,
                b1=0.9, b2=0.95, weight_decay=0.1, grad_clip=1.0,
            ),
            tokens_per_step=4 * 65_536,
        ),
        data={"kind": "bpe", "path": None, "block_size": 65_536,
              "bpe_vocab_size": 32_000, "synthetic_chars": 8_000_000},
        notes="flagship long-context over the context axis (ring flash-MLA)",
    )


@register("dsv3_long_cp_smoke")
def _dsv3_long_cp_smoke() -> RunConfig:
    """CPU-mesh-sized dsv3_long_cp (virtual 8-device mesh: data=2 x
    context=4): same CP Trainer path — ring flash-MLA + psum'd MoE state —
    at toy dims."""
    from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3Config

    return RunConfig(
        name="dsv3_long_cp_smoke",
        model_family="deepseekv3",
        model=DeepSeekV3Config(
            vocab_size=256, block_size=256, dim=32, n_layers=2, n_heads=4,
            latent_dim=8, n_experts=4, top_experts=2, dropout=0.0,
            attn_dropout=0.0, use_flash=True, context_parallel=True,
            pe_scale=0.02, rope_dim=8,
        ),
        train=TrainConfig(
            steps=20, batch_size=4, log_every=5, eval_every=10,
            eval_batches=2,
            mesh=MeshConfig(data=-1, context=4),
            context_parallel=True,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=1e-3, warmup_steps=5, total_steps=20,
                weight_decay=0.1, grad_clip=1.0,
            ),
            tokens_per_step=4 * 256,
        ),
        data={"kind": "char", "path": None, "block_size": 256},
        notes="dsv3_long_cp at smoke scale for the virtual CPU mesh",
    )


@register("vit_mnist")
def _vit_mnist() -> RunConfig:
    """vision transformer/ViT.ipynb cells 4-15: tiny ViT on MNIST-shaped data.

    Reference: 28x28 patch 7, dim 64, 4 heads, 4 blocks, MLP 2x, Adam 1e-3,
    batch 128, 5 epochs -> 97.25% test accuracy.
    """
    from solvingpapers_tpu.models.vit import ViTConfig

    return RunConfig(
        name="vit_mnist",
        model_family="vit",
        model=ViTConfig(),
        train=TrainConfig(
            steps=2000, batch_size=128, log_every=100, eval_every=500,
            eval_batches=16,
            optimizer=OptimizerConfig(
                name="adam", max_lr=1e-3, warmup_steps=0, total_steps=2000,
                min_lr_ratio=1.0, weight_decay=0.0, grad_clip=0.0,
            ),
        ),
        data={"kind": "images", "path": None, "side": 28, "n_classes": 10},
        notes="ViT.ipynb; MNIST via local npz path, else synthetic fallback",
    )


@register("vit_bayes")
def _vit_bayes() -> RunConfig:
    """vit_mnist on the Bayes-calibrated Gaussian image set
    (data/synthetic.GaussianImageSource): Bayes-optimal accuracy 0.8703 at
    snr 2.8 / 10 classes, computed exactly from the generative model — the
    vision analogue of the Markov corpus's entropy floor. val_accuracy has
    an absolute ceiling no model beats and a calibrated target a good one
    approaches; the separable set saturates at 1.0 and can't fail for the
    interesting reason (VERDICT r3)."""
    from solvingpapers_tpu.models.vit import ViTConfig

    return RunConfig(
        name="vit_bayes",
        model_family="vit",
        model=ViTConfig(),
        train=TrainConfig(
            # weight decay + cosine decay matter here: the Bayes rule is a
            # matched filter and unregularized nets overfit the per-pixel
            # noise (measured: wd 0.1 closes the val gap 0.085 -> 0.022 on
            # the MLP); 32k train samples bound the estimation error
            # eval_batches 64 (8192 samples): binomial eval noise at
            # p~0.84 is sigma~0.004, so the parity gate's 0.02 tolerance
            # sits 5 sigma out instead of 2.5 (VERDICT r4 ask 9 — the
            # steps stay pinned at 2000 so the row remains gate-comparable
            # across rounds; only the eval got less noisy)
            steps=2000, batch_size=128, log_every=100, eval_every=500,
            eval_batches=64,
            optimizer=OptimizerConfig(
                name="adamw", max_lr=1e-3, warmup_steps=0, total_steps=2000,
                min_lr_ratio=0.1, weight_decay=0.1, grad_clip=1.0,
            ),
        ),
        data={"kind": "images", "path": None, "side": 28, "n_classes": 10,
              "source": "bayes", "snr": 2.8, "n_train": 32768},
        notes="ViT on the computable-Bayes Gaussian set (ceiling 0.8703)",
    )


@register("kd_bayes")
def _kd_bayes() -> RunConfig:
    """kd_mnist on the Bayes-calibrated Gaussian set (see vit_bayes): the
    distilled student's accuracy is measured against the computable 0.8703
    Bayes ceiling instead of a saturating 1.0."""
    from solvingpapers_tpu.models.kd import student_config

    return RunConfig(
        name="kd_bayes",
        model_family="kd",
        model=student_config(),
        train=TrainConfig(
            # see vit_bayes: wd + cosine + 32k samples keep the student at
            # the matched filter instead of the training noise;
            # eval_batches widened like vit_bayes (gate-noise margin)
            steps=4000, batch_size=64, log_every=200, eval_every=1000,
            eval_batches=64,
            optimizer=OptimizerConfig(name="adamw", max_lr=1e-3, warmup_steps=0,
                                      total_steps=4000, weight_decay=0.1,
                                      grad_clip=1.0, min_lr_ratio=0.1),
        ),
        data={"kind": "images", "path": None, "flatten": True,
              "teacher_steps": 1200, "temperature": 7.0, "alpha": 0.3,
              "source": "bayes", "snr": 2.8, "n_train": 32768},
        notes="KD on the computable-Bayes Gaussian set (ceiling 0.8703)",
    )


@register("alexnet_images")
def _alexnet_images() -> RunConfig:
    """alexnet/alexnet.py model (no train loop in reference); trained here
    with the shared engine on 224px 3-channel images."""
    from solvingpapers_tpu.models.alexnet import AlexNetConfig

    return RunConfig(
        name="alexnet_images",
        model_family="alexnet",
        model=AlexNetConfig(n_classes=10, in_channels=3),
        train=TrainConfig(
            steps=1000, batch_size=64, log_every=50, eval_every=250,
            eval_batches=8,
            optimizer=OptimizerConfig(name="adam", max_lr=1e-4, warmup_steps=0,
                                      total_steps=1000, weight_decay=0.0,
                                      grad_clip=0.0, min_lr_ratio=1.0),
        ),
        data={"kind": "images", "path": None, "side": 224, "n_classes": 10,
              "n_train": 2048, "n_test": 512},
        notes="alexnet.py:5-44 (classifier flatten size derived, not 256*5*5)",
    )


@register("ae_mnist")
def _ae_mnist() -> RunConfig:
    """autoencoder/autoencoder.ipynb: 784-256-32 AE, MSE+Adam(1e-3), 5 epochs."""
    from solvingpapers_tpu.models.autoencoder import AutoEncoderConfig

    return RunConfig(
        name="ae_mnist",
        model_family="ae",
        model=AutoEncoderConfig(),
        train=TrainConfig(
            steps=2000, batch_size=128, log_every=100, eval_every=500,
            eval_batches=16,
            optimizer=OptimizerConfig(name="adam", max_lr=1e-3, warmup_steps=0,
                                      total_steps=2000, weight_decay=0.0,
                                      grad_clip=0.0, min_lr_ratio=1.0),
        ),
        data={"kind": "images", "path": None, "flatten": True},
        notes="autoencoder.ipynb cells 4-9; reference MSE 0.012954 @ epoch 5",
    )


@register("vae_mnist")
def _vae_mnist() -> RunConfig:
    """autoencoder/variational autoencoder.ipynb: VAE(784,256,128), 10 epochs."""
    from solvingpapers_tpu.models.autoencoder import VAEConfig

    return RunConfig(
        name="vae_mnist",
        model_family="vae",
        model=VAEConfig(),
        train=TrainConfig(
            steps=4000, batch_size=128, log_every=100, eval_every=1000,
            eval_batches=16,
            optimizer=OptimizerConfig(name="adam", max_lr=1e-3, warmup_steps=0,
                                      total_steps=4000, weight_decay=0.0,
                                      grad_clip=0.0, min_lr_ratio=1.0),
        ),
        data={"kind": "images", "path": None, "flatten": True},
        notes="variational autoencoder.ipynb cells 5-8; summed ELBO 13881 @ ep10",
    )


@register("kd_mnist")
def _kd_mnist() -> RunConfig:
    """knowledge distillation/kd.py: teacher 3 epochs -> frozen -> student
    10 epochs with T=7, alpha=0.3 distillation; 97.50% student accuracy."""
    from solvingpapers_tpu.models.kd import student_config

    return RunConfig(
        name="kd_mnist",
        model_family="kd",
        model=student_config(),
        train=TrainConfig(
            steps=4000, batch_size=64, log_every=200, eval_every=1000,
            eval_batches=16,
            optimizer=OptimizerConfig(name="adam", max_lr=1e-3, warmup_steps=0,
                                      total_steps=4000, weight_decay=0.0,
                                      grad_clip=0.0, min_lr_ratio=1.0),
        ),
        data={"kind": "images", "path": None, "flatten": True,
              "teacher_steps": 1200, "temperature": 7.0, "alpha": 0.3},
        notes="kd.py:85-160; student target 97.50% (run screenshot)",
    )
