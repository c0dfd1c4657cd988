"""Decode-throughput benchmark: cached scan decode vs reference-style
full-prefix recompute (BENCHMARKS.md).

All four reference LMs generate by re-running the forward on the whole
prefix per token with no cache (SURVEY.md §3.4). Here that costs O(T) full
forwards vs the framework's prefill + lax.scan single-token steps. Both
arms below run jitted on-chip at static shapes — the recompute arm is the
most charitable possible rendition of the reference's pattern (its actual
loops are unjitted python); the gap measured is purely the cache.

Usage: python tools/bench_decode.py [--bs 8] [--prompt 128] [--new 256]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    from solvingpapers_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()

    p = argparse.ArgumentParser()
    p.add_argument("--bs", type=int, default=8)
    p.add_argument("--prompt", type=int, default=128)
    p.add_argument("--new", type=int, default=256)
    p.add_argument("--dim", type=int, default=1024)
    p.add_argument("--layers", type=int, default=24)
    p.add_argument("--model", choices=("llama3", "dsv3"), default="llama3",
                   help="dsv3 = flash-MLA long-context decode (16k prompts "
                        "prefill through the Pallas kernel end-aligned mode)")
    p.add_argument("--prefill-chunk", type=int, default=None)
    p.add_argument("--skip-recompute", action="store_true",
                   help="only measure the cached arm")
    args = p.parse_args()

    from solvingpapers_tpu import ops
    from solvingpapers_tpu.infer import generate

    total = args.prompt + args.new
    extra_variables = None
    if args.model == "dsv3":
        from solvingpapers_tpu.models.deepseekv3 import (
            DeepSeekV3, DeepSeekV3Config,
        )

        # --dim/--layers apply to the dsv3 arm too (heads scale with dim)
        cfg = DeepSeekV3Config(
            vocab_size=32000, block_size=total, dtype="bfloat16",
            dim=args.dim if args.dim != 1024 else 512,
            n_layers=args.layers if args.layers != 24 else 6,
            n_heads=max((args.dim if args.dim != 1024 else 512) // 64, 1),
            use_flash=True, pe_scale=0.02, rope_dim=64,
            dropout=0.0, attn_dropout=0.0,
        )
        model = DeepSeekV3(cfg)
    else:
        from solvingpapers_tpu.models.llama3 import Llama, LlamaConfig

        cfg = LlamaConfig(
            vocab_size=32000, dim=args.dim, n_layers=args.layers,
            n_heads=args.dim // 64, n_kv_heads=args.dim // 128,
            max_seq_len=total, dropout=0.0, dtype="bfloat16",
        )
        model = Llama(cfg)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size,
                                          (args.bs, args.prompt)),
        jnp.int32,
    )
    variables = model.init({"params": jax.random.key(0)}, prompt)
    params = variables["params"]
    if args.model == "dsv3":
        extra_variables = {"moe_state": variables["moe_state"]}
    rng = jax.random.key(1)

    def timed(fn, *a, reps=3):
        # fence by fetching a scalar reduced on the device from the
        # output's last column: the host cannot have it before the
        # program has finished, and only four bytes cross to the host
        fence = lambda out: float(jnp.sum(out[..., -1]))  # noqa: E731
        out = fn(*a)            # compile
        fence(out)
        best = float("inf")
        for _ in range(reps):
            t0 = time.time()
            out = fn(*a)
            fence(out)
            best = min(best, time.time() - t0)
        return best, out

    # arm 1: cached decode (prefill + scan); generate is already one jitted
    # XLA program, so it is called as it is, not wrapped in another jit
    cached = lambda p_, r: generate(  # noqa: E731
        model, params, p_, r, max_new_tokens=args.new,
        sampler=ops.sample_greedy, extra_variables=extra_variables,
        prefill_chunk=args.prefill_chunk,
    )
    t_cached, out = timed(cached, prompt, rng)

    # prefill-only arm (max_new_tokens=1): isolates the end-aligned
    # flash/causal prefill from the scan decode
    prefill_only = lambda p_, r: generate(  # noqa: E731
        model, params, p_, r, max_new_tokens=1,
        sampler=ops.sample_greedy, extra_variables=extra_variables,
        prefill_chunk=args.prefill_chunk,
    )
    t_prefill, _ = timed(prefill_only, prompt, rng)

    # arm 2: reference-style — a full forward over the final-length prefix
    # per new token. Measured as one jitted full-length forward x `new`
    # (the charitable rendition: the reference's actual loops are
    # unjitted python with no batching of compile costs)
    t_full = None
    if not args.skip_recompute:
        toks_full = jnp.pad(prompt, ((0, 0), (0, args.new)))
        fwd = jax.jit(lambda t: model.apply({"params": params}, t,
                                            deterministic=True)[0])
        t_one, _ = timed(fwd, toks_full)
        t_full = t_one * args.new

    new_toks = args.bs * args.new
    name = (
        f"dsv3-flash-mla-d{cfg.dim}-L{cfg.n_layers}" if args.model == "dsv3"
        else f"llama3-d{args.dim}-L{args.layers}"
    )
    decode_s = max(t_cached - t_prefill, 1e-9)
    decoded = max(args.new - 1, 1)  # prefill emits token 0; --new 1 is
    out = {                         # effectively a prefill-only run
        "model": name, "bs": args.bs,
        "prompt": args.prompt, "new": args.new,
        "prefill_s": round(t_prefill, 3),
        "prefill_tokens_per_sec": round(args.bs * args.prompt / t_prefill),
        "cached_tokens_per_sec": round(args.bs * decoded / decode_s),
        "cached_ms_per_token": round(decode_s / decoded * 1e3, 3),
    }
    if t_full is not None:
        out["recompute_tokens_per_sec"] = round(new_toks / t_full)
        out["speedup"] = round(t_full / t_cached, 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
