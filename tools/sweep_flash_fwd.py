"""Flash-attention FORWARD tile sweep at the shapes the benchmark's cells run.

`tools/sweep_flash_bwd.py` chose the tiles the three kernels shared; this
times the forward kernel alone, (block_q, block_k) by (block_q, block_k), so
that `kernels/flash_attention.py` `flash_blocks` can give it a pair of its
own. One row a pair: ms a call and the share of the call's compute roofline
(causal QK^T and PV at the v5e's 197 TFLOP/s; bytes never bound it).

A call's time is the slope between two trip counts of ONE compiled loop, so
a program's fixed cost (dispatch, launch, the fence's round trip) cancels;
each call's dropout seed (unused at rate 0, but an operand) depends on the
last call's output, so no call can be hoisted or dropped.

A shape in MASKED runs with a selection mask (B, S, S) int8 beside K and V
(`keye_vl2_ep8`'s attention over the indexer's keys; the mask's values move
no time: no tile is skipped for them). `--kernel probs` times
`selected_probs` there, the forward-only kernel of the heads' mean
probability (QK^T and the exponential, no values: half the operations).

Usage, on the chip:   python tools/sweep_flash_fwd.py [--shape NAME ...]
without one:          python tools/sweep_flash_fwd.py --describe
(`--describe` compiles every pair for a described v5e and says which ones
Mosaic takes in the default scoped VMEM; nothing runs, no time is printed.)
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

PEAK_FLOPS = 197e12  # TPU v5e, bf16 (Google Cloud documentation, "TPU v5e")
# (batch, seq, q heads, kv heads, key width, value width): the six cells
# whose step holds the kernel, then two short study points (the 342M llama3
# run of `tools/scale_350m.py`, and ouro's heads at half its length)
SHAPES = {
    "ouro_2p6b_pp6": (2, 4096, 16, 16, 128, 128),
    "kimi_linear_ep32": (1, 16_384, 32, 32, 192, 128),
    "dsv3_long": (1, 16_384, 8, 1, 128, 128),
    "qwen3next_ep16": (1, 16_384, 16, 2, 256, 256),
    "nemotron3_nano_ep16": (1, 16_384, 32, 2, 128, 128),
    "granite4_h_micro_pp4": (1, 8192, 32, 8, 64, 64),
    "keye_vl2_ep8": (1, 16_384, 32, 4, 128, 128),
    "llama_gqa_1k": (8, 1024, 16, 8, 64, 64),
    "mha_2k": (4, 2048, 16, 16, 128, 128),
}
MASKED = ("keye_vl2_ep8",)
BLOCKS_Q = (256, 512, 1024, 2048)
BLOCKS_K = (512, 1024, 2048, 4096)
TRIPS = (4, 24)


def least_ms(shape, kernel: str = "fwd") -> float:
    b, s, n, _, d, dv = shape
    width = d if kernel == "probs" else d + dv
    return 2.0 * b * n * (s * s / 2.0) * width / PEAK_FLOPS * 1e3


def ms_a_call(run, arrays) -> float:
    """The slope between the two trip counts, each the best of three timed
    runs after one that warms; a run ends by fetching the loop's result."""
    import jax
    import jax.numpy as jnp

    best = []
    for trips in TRIPS:
        took = []
        for _ in range(4):
            t0 = time.perf_counter()
            int(jax.device_get(run(*arrays, jnp.int32(trips))))
            took.append(time.perf_counter() - t0)
        best.append(min(took[1:]))
    return (best[1] - best[0]) / (TRIPS[1] - TRIPS[0]) * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", nargs="*", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--kernel", default="fwd", choices=["fwd", "probs"])
    ap.add_argument("--vmem-mib", type=int, default=None,
                    help="scoped VMEM of the masked calls, for this sweep "
                    "(default: the module's MASKED_VMEM_BYTES)")
    ap.add_argument("--out", default="chiprun_out/sweep_flash_fwd.jsonl")
    args = ap.parse_args()
    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp

    from solvingpapers_tpu.kernels.flash_attention import (
        PROBS_BLOCKS,
        flash_attention,
        flash_blocks,
        selected_probs,
    )

    # the module: the package re-exports the function under its name
    flash_module = sys.modules["solvingpapers_tpu.kernels.flash_attention"]
    if args.vmem_mib:
        flash_module.MASKED_VMEM_BYTES = args.vmem_mib << 20
    vmem_mib = flash_module.MASKED_VMEM_BYTES >> 20
    sharding = None
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        jax.config.update("jax_enable_compilation_cache", False)
        sharding = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])

    def loop(block_q, block_k):
        def run(q, k, v, *rest):  # rest: [mask,] trips
            def body(_, seed):
                o = flash_attention(
                    q, k, v, causal=True, block_q=block_q, block_k=block_k,
                    dropout_seed=seed, interpret=False,
                    mask=rest[0] if len(rest) == 2 else None)
                return seed + (o[0, 0, 0, 0] > 1e30).astype(jnp.int32)
            return jax.lax.fori_loop(0, rest[-1], body, jnp.int32(0))
        return jax.jit(run)

    def probs_loop(block_q, block_k):
        def run(q, k, v, mask, trips):
            lse = jnp.full(q.shape[:1] + (q.shape[2], q.shape[1]), 40.0)

            def body(_, bump):  # the last call's result moves this call's lse
                p = selected_probs(
                    q, k, lse + bump, mask, causal=True, block_q=block_q,
                    block_k=block_k, interpret=False)
                return bump + (p[0, 0, 0] > 1e30).astype(jnp.float32)
            return jax.lax.fori_loop(
                0, trips, body, jnp.float32(0)).astype(jnp.int32)
        return jax.jit(run)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as out:
        for name in args.shape:
            shape = SHAPES[name]
            b, s, n, n_kv, d, dv = shape
            sds = [jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=sharding)
                   for dims in ((b, s, n, d), (b, s, n_kv, d),
                                (b, s, n_kv, dv))]
            trips = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
            arrays = None if args.describe else [
                jax.random.normal(jax.random.key(i), x.shape, x.dtype)
                for i, x in enumerate(sds)]
            if name in MASKED:
                sds.append(jax.ShapeDtypeStruct((b, s, s), jnp.int8,
                                                sharding=sharding))
                if arrays is not None:  # one pair in eight, and the diagonal
                    arrays.append((jax.random.bits(
                        jax.random.key(3), (b, s, s), jnp.uint8) < 32
                    ).astype(jnp.int8) | jnp.eye(s, dtype=jnp.int8))
            elif args.kernel == "probs":
                continue
            make = probs_loop if args.kernel == "probs" else loop
            resolver = PROBS_BLOCKS if args.kernel == "probs" else (
                flash_blocks(s, s, d, dv,
                             mask=sds[3] if name in MASKED else None)[0])
            for bq, bk in itertools.product(BLOCKS_Q, BLOCKS_K):
                if s % bq or s % bk:
                    continue
                row = {"shape": name, "dims": shape, "kernel": args.kernel,
                       "block_q": bq, "block_k": bk,
                       "resolver": list(resolver), "masked_vmem_mib": vmem_mib}
                try:
                    run = make(bq, bk).lower(*sds, trips).compile()
                except Exception as e:  # noqa: BLE001 — Mosaic's refusal
                    row["refused"] = str(e).splitlines()[0][:160]
                else:
                    if arrays is not None:
                        ms = ms_a_call(run, arrays)
                        row["ms"] = round(ms, 4)
                        row["roofline_pct"] = round(
                            100.0 * least_ms(shape, args.kernel) / ms, 2)
                line = json.dumps(row)
                print(line, flush=True)
                out.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
