"""Reproducible 350M llama3 single-chip scaling study.

Measures steady-state training step time / tokens-per-sec / MFU for the
342M-param llama3 config (dim 1024, 24 layers, 16 q / 8 kv heads, seq 1024,
vocab 32000, bf16) on the attached TPU. Each timed segment ends with a
device_get of a value that depends on the computation, so the host clock
stops only after the device has finished.

Usage: python tools/scale_350m.py [--bs 8] [--flash 1] [--remat 0]
       [--block-q N] [--block-k N] [--steps 20] [--seq 1024]
       [--profile-dir DIR]
--block-q/--block-k default to the kernel's DEFAULT_BLOCK (512). Timing:
long warmup to fill the dispatch queue, then best of 3 windows, each fenced
by a device_get.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import time

import jax
import numpy as np


def main() -> None:
    from solvingpapers_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()

    p = argparse.ArgumentParser()
    p.add_argument("--bs", type=int, default=8)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--flash", type=int, default=1)
    p.add_argument("--remat", type=int, default=0)
    p.add_argument("--block-q", type=int, default=None,
                   help="override kernel DEFAULT_BLOCK")
    p.add_argument("--block-k", type=int, default=None)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--dropout", type=float, default=0.0)
    p.add_argument("--profile-dir", default=None)
    args = p.parse_args()

    from solvingpapers_tpu import kernels
    from solvingpapers_tpu.data.batches import lm_batch_iterator
    from solvingpapers_tpu.metrics.mfu import (
        chip_peak_flops,
        transformer_flops_per_token,
    )
    from solvingpapers_tpu.models.llama3 import Llama, LlamaConfig
    from solvingpapers_tpu.train import OptimizerConfig, TrainConfig, Trainer

    import importlib

    # kernels/__init__ re-exports a function named flash_attention that
    # shadows the submodule on attribute access; go through importlib
    _fa_mod = importlib.import_module(
        "solvingpapers_tpu.kernels.flash_attention"
    )
    _sf_mod = importlib.import_module("solvingpapers_tpu.kernels.sharded_flash")

    block_q = args.block_q or _fa_mod.DEFAULT_BLOCK
    block_k = args.block_k or _fa_mod.DEFAULT_BLOCK
    if (block_q, block_k) != (_fa_mod.DEFAULT_BLOCK, _fa_mod.DEFAULT_BLOCK):
        # experiment knob: route every flash call site through custom block
        # sizes. models/layers.py re-imports kernels.flash_attention per
        # call; sharded_flash bound the name at import, so patch both.
        patched = functools.partial(
            _fa_mod.flash_attention, block_q=block_q, block_k=block_k
        )
        kernels.flash_attention = patched
        _sf_mod.flash_attention = patched

    cfg = LlamaConfig(
        vocab_size=32000, dim=1024, n_layers=24, n_heads=16, n_kv_heads=8,
        max_seq_len=args.seq, dropout=args.dropout, dtype="bfloat16",
        use_flash=bool(args.flash), remat=bool(args.remat),
    )
    tcfg = TrainConfig(
        steps=args.steps, batch_size=args.bs, log_every=10_000, eval_every=0,
        optimizer=OptimizerConfig(max_lr=3e-4, total_steps=1000),
    )
    trainer = Trainer(Llama(cfg), tcfg)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, size=2_000_000)
    it = lm_batch_iterator(toks, args.bs, args.seq, seed=0)
    batch = next(it)
    state = trainer.init_state(batch)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    trainer._build_steps()

    # compile + warmup long enough to fill the dispatch queue, fenced by
    # a value fetch
    for _ in range(10):
        state, m = trainer._train_step(state, next(it))
    _ = float(jax.device_get(m["train_loss"]))

    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    best = float("inf")
    for _ in range(3):  # best of 3 windows
        t0 = time.time()
        for _ in range(args.steps):
            state, m = trainer._train_step(state, next(it))
        _ = float(jax.device_get(m["train_loss"]))
        best = min(best, time.time() - t0)
    dt = best / args.steps
    if args.profile_dir:
        jax.profiler.stop_trace()

    tok_s = args.bs * args.seq / dt
    fpt = transformer_flops_per_token(n_params, cfg.n_layers, cfg.dim, args.seq)
    peak = chip_peak_flops()
    print(json.dumps({
        "params_m": round(n_params / 1e6, 1), "bs": args.bs, "seq": args.seq,
        "flash": bool(args.flash), "remat": bool(args.remat),
        "block_q": block_q, "block_k": block_k,
        "step_ms": round(dt * 1e3, 1), "tokens_per_sec": round(tok_s),
        # unknown chips have no peak entry (NaN sentinel): omit the key —
        # json.dumps would emit a bare non-RFC-8259 NaN token
        **({"mfu": round(tok_s * fpt / peak, 4)}
           if math.isfinite(peak) else {}),
    }))


if __name__ == "__main__":
    main()
