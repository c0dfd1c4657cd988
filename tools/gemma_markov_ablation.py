"""Diagnose the gemma_markov quality gap (VERDICT r4 ask 6).

gemma_markov posts gap-to-entropy 0.139 nats vs llama3's 0.088 and gpt's
0.093 at near-identical scale. The suspect list from the verdict: the
grouped-MQA formulation, GeGLU init/activation, and the RoPE path. The
attention/RoPE stack is literally the same shared module as llama3's
(models/layers.py Attention), so the ablation matrix focuses on what
actually differs: activation (gelu_tanh vs silu), FFN width (4*dim vs
SwiGLU's (2/3)*4*dim), kv grouping, corpus size (memorization — the dsv3
diagnosis), and learning rate.

Usage: python tools/gemma_markov_ablation.py [--steps 3000] [variants...]
Prints one JSON line per variant: {"variant", "val_loss", "gap", ...}.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time


def run_variant(name: str, steps: int) -> dict:
    import jax  # noqa: F401

    from solvingpapers_tpu.configs import get_config
    from solvingpapers_tpu.configs.factory import (
        build_char_lm_run, init_fn_for, loss_fn_for, rules_for,
    )
    from solvingpapers_tpu.data.synthetic import markov_entropy_nats
    from solvingpapers_tpu.sharding import batch_sharding, create_mesh
    from solvingpapers_tpu.train import Trainer

    cfg = get_config("gemma_markov", steps=steps)
    model_over: dict = {}
    data_over: dict = {}
    train_over: dict = {}

    if name == "base":
        pass
    elif name == "silu":
        # GeGLU -> SwiGLU activation at equal width (GemmaConfig knob)
        model_over["activation"] = "silu"
    elif name == "swiglu_width":
        # llama's (2/3)*4*dim hidden at gemma's gelu gating
        from solvingpapers_tpu.models.layers import swiglu_hidden_dim

        model_over["hidden_dim"] = swiglu_hidden_dim(cfg.model.dim)
    elif name == "mha":
        model_over["n_kv_heads"] = cfg.model.n_heads
    elif name == "data16m":
        data_over["n_chars"] = 16_000_000
    elif name == "lr5e-4":
        train_over["optimizer"] = dataclasses.replace(
            cfg.train.optimizer, max_lr=5e-4
        )
    elif name == "layers3":
        model_over["n_layers"] = 3
    else:
        raise SystemExit(f"unknown variant {name}")

    if model_over:
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, **model_over)
        )
    if data_over:
        cfg = dataclasses.replace(cfg, data={**cfg.data, **data_over})
    if train_over:
        cfg = dataclasses.replace(
            cfg, train=dataclasses.replace(cfg.train, **train_over)
        )
    mesh = create_mesh(cfg.train.mesh)
    cfg, model, _, train_iter, eval_iter_fn = build_char_lm_run(
        cfg, sharding=batch_sharding(mesh)
    )
    trainer = Trainer(model, cfg.train, loss_fn=loss_fn_for(cfg),
                      init_fn=init_fn_for(cfg), mesh=mesh,
                      rules=rules_for(cfg))
    t0 = time.perf_counter()
    state = trainer.fit(train_iter)
    val = trainer.evaluate(state, eval_iter_fn())
    wall = time.perf_counter() - t0
    h = markov_entropy_nats(cfg.data)
    return {
        "variant": name,
        "steps": steps,
        "val_loss": round(float(val["val_loss"]), 5),
        "entropy_nats": round(h, 5),
        "gap": round(float(val["val_loss"]) - h, 5),
        "wall_s": round(wall, 1),
    }


def main() -> None:
    from solvingpapers_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("variants", nargs="*", default=None)
    ap.add_argument("--steps", type=int, default=3000)
    args = ap.parse_args()
    variants = args.variants or [
        "base", "silu", "swiglu_width", "mha", "data16m", "lr5e-4", "layers3",
    ]
    for v in variants:
        print(json.dumps(run_variant(v, args.steps)), flush=True)


if __name__ == "__main__":
    main()
