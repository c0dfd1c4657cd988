"""Flash-attention BACKWARD block sweep at long sequence (VERDICT r4 ask 8).

The forward sweep (tools/scale_350m.py) moved 1k-seq MFU 35.9% -> 52.2% and
pinned DEFAULT_BLOCK=512; nothing equivalent exists for the backward at the
16k sequence the kernel was rebuilt for (16k-context training MFU 40.4% vs
the >=45% north star). This times value_and_grad of the kernel itself at
the flagship's 16k MLA shape (q (1,16k,8,128) vs MQA latents (1,16k,1,128))
and the GQA llama shape, across (block_q, block_k) grids, fwd-only vs
fwd+bwd, so the step-level number can be attributed. Since PR 48 the
gradient is taken for q, k and v (for q alone the dk/dv kernel is dead code
and XLA drops it), and `keye_32on4_masked` is `keye_vl2_ep8`'s call: 32
heads on 4 of width 128 with a (1, S, S) int8 selection mask beside K and V
in all three kernels (the mask's values move no time: no tile is skipped
for them). A pair Mosaic refuses prints `refused`.

Usage: python tools/sweep_flash_bwd.py [--seq 16384] [--shape NAME ...]
"""

from __future__ import annotations

import argparse
import json
import sys
import time


# name: (q heads, kv heads, width, with a selection mask)
SHAPES = {
    "mla_16k": (8, 1, 128, False),
    "gqa_16k": (16, 4, 64, False),
    "keye_32on4": (32, 4, 128, False),
    "keye_32on4_masked": (32, 4, 128, True),
}
PAIRS = ((256, 256), (256, 512), (512, 256), (512, 512), (512, 1024),
         (1024, 512), (1024, 1024))


def main() -> int:
    from solvingpapers_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--shape", nargs="*", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--vmem-mib", type=int, default=None,
                    help="scoped VMEM of the masked calls, for this sweep "
                    "(default: the module's MASKED_VMEM_BYTES)")
    ap.add_argument("--pairs", nargs="*", default=None,
                    help="block_q x block_k, as 512x1024; default: PAIRS")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from solvingpapers_tpu.kernels.flash_attention import flash_attention

    # the module: the package re-exports the function under its name
    flash_module = sys.modules["solvingpapers_tpu.kernels.flash_attention"]
    if args.vmem_mib:
        flash_module.MASKED_VMEM_BYTES = args.vmem_mib << 20
    vmem_mib = flash_module.MASKED_VMEM_BYTES >> 20
    pairs = PAIRS if args.pairs is None else [
        tuple(int(n) for n in pair.split("x")) for pair in args.pairs]
    seq = args.seq

    REPS = 20  # in-program repeats: the kernel is scanned inside ONE
    # program, so the fixed cost of a program execution (dispatch, launch,
    # the fence's round trip) is paid once per REPS kernel calls

    def bench(n_heads, n_kv, d, masked, block_q, block_k, mode):
        q = jax.random.normal(jax.random.key(0), (1, seq, n_heads, d),
                              jnp.bfloat16)
        k = jax.random.normal(jax.random.key(1), (1, seq, n_kv, d),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.key(2), (1, seq, n_kv, d),
                              jnp.bfloat16)

        mask = None
        if masked:  # one pair in eight, and the diagonal
            mask = (jax.random.bits(jax.random.key(3), (1, seq, seq),
                                    jnp.uint8) < 32).astype(jnp.int8)
            mask = mask | jnp.eye(seq, dtype=jnp.int8)

        def one(q, k=k, v=v):
            return flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k,
                mask=mask)

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
                    g, gk, gv = jax.grad(lambda *qkv: jnp.sum(
                        one(*qkv).astype(jnp.float32)), (0, 1, 2))(c, k, v)
                    # dk and dv feed the carry, so their kernel is not dead
                    return (g + (gk[0, 0, 0, 0] + gv[0, 0, 0, 0]).astype(
                        g.dtype)).astype(c.dtype), None
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

    for shape_name in args.shape:
        for mode in ("fwd", "fwd+bwd"):
            for bq, bk in pairs:
                row = {"shape": shape_name, "mode": mode, "block_q": bq,
                       "block_k": bk, "masked_vmem_mib": vmem_mib}
                try:
                    row["ms"] = round(
                        bench(*SHAPES[shape_name], bq, bk, mode), 2)
                except Exception as e:  # noqa: BLE001 — Mosaic's refusal
                    row["refused"] = str(e).splitlines()[0][:160]
                print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
