"""Flash-attention BACKWARD block sweep at long sequence (VERDICT r4 ask 8).

The forward sweep (tools/scale_350m.py) moved 1k-seq MFU 35.9% -> 52.2% and
pinned DEFAULT_BLOCK=512; nothing equivalent exists for the backward at the
16k sequence the kernel was rebuilt for (16k-context training MFU 40.4% vs
the >=45% north star). This times value_and_grad of the kernel itself at
the flagship's 16k MLA shape (q (1,16k,8,128) vs MQA latents (1,16k,1,128))
and the GQA llama shape, across (block_q, block_k) grids, fwd-only vs
fwd+bwd, so the step-level number can be attributed.

Usage: python tools/sweep_flash_bwd.py [--seq 16384]
"""

from __future__ import annotations

import argparse
import json
import time


def main() -> int:
    from solvingpapers_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=16384)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from solvingpapers_tpu.kernels.flash_attention import flash_attention

    seq = args.seq

    REPS = 20  # in-program repeats: the kernel is scanned inside ONE
    # program, so the fixed cost of a program execution (dispatch, launch,
    # the fence's round trip) is paid once per REPS kernel calls

    def bench(shape_name, n_heads, n_kv, d, block_q, block_k, mode):
        q = jax.random.normal(jax.random.key(0), (1, seq, n_heads, d),
                              jnp.bfloat16)
        k = jax.random.normal(jax.random.key(1), (1, seq, n_kv, d),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.key(2), (1, seq, n_kv, d),
                              jnp.bfloat16)

        def one(q):
            return flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k
            )

        if mode == "fwd":
            @jax.jit
            def run(q):
                def body(c, _):
                    # feed the output back so iterations can't be collapsed
                    return one(c).astype(c.dtype), None
                out, _ = jax.lax.scan(body, q, None, length=REPS)
                return jnp.sum(out.astype(jnp.float32))
        else:
            @jax.jit
            def run(q):
                def body(c, _):
                    g = jax.grad(lambda q: jnp.sum(
                        one(q).astype(jnp.float32)))(c)
                    return g.astype(c.dtype), None
                out, _ = jax.lax.scan(body, q, None, length=REPS)
                return jnp.sum(out.astype(jnp.float32))

        out = run(q)
        float(jax.device_get(out))  # compile + real sync
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            out = run(q)
            float(jax.device_get(out))
            best = min(best, time.perf_counter() - t0)
        # subtract the measured fixed program latency so rows are the
        # kernel's own time
        return (best - 0.110) / REPS * 1e3

    for shape_name, n_heads, n_kv, d in (
        ("mla_16k", 8, 1, 128),
        ("gqa_16k", 16, 4, 64),
    ):
        for mode in ("fwd", "fwd+bwd"):
            for bq, bk in ((256, 256), (256, 512), (512, 256), (512, 512),
                           (512, 1024), (1024, 512), (1024, 1024)):
                ms = bench(shape_name, n_heads, n_kv, d, bq, bk, mode)
                print(json.dumps({
                    "shape": shape_name, "mode": mode, "block_q": bq,
                    "block_k": bk, "ms": round(ms, 2),
                }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
