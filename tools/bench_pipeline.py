"""GPipe bubble-overhead measurement (BENCHMARKS.md PP row).

The ppermute schedule runs `m + S - 1` ticks for m microbatches over S
stages; (S-1) of them are bubbles, so the analytic bubble fraction is
(S-1)/(m+S-1) of every step — amortized away as m grows at fixed global
batch (each tick's compute shrinks by the same factor the tick count
grows, up to per-tick overheads).

Multi-chip hardware is not attached here, so this measures on the virtual
CPU mesh (same schedule, same collectives, host math): the MEASURED
step-time trend vs m validates the schedule's amortization shape, while
the analytic fraction is the hardware-independent number. Run with
JAX_PLATFORMS=cpu and XLA_FLAGS=--xla_force_host_platform_device_count=8
(tests/conftest.py's recipe), or let this script set them via a subprocess
re-exec (the default unless the environment already provisions 8
virtual CPU devices; decided without touching JAX in the parent).

Usage: python tools/bench_pipeline.py [--stages 4] [--batch 32]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys
import time


def _body(n_stages: int, batch: int) -> None:
    import jax

    from solvingpapers_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    import numpy as np

    from solvingpapers_tpu.data.batches import lm_batch_iterator
    from solvingpapers_tpu.models.gpt_pipe import GPTPipe, GPTPipeConfig
    from solvingpapers_tpu.sharding import MeshConfig, PP_RULES, batch_sharding, create_mesh
    from solvingpapers_tpu.train import OptimizerConfig, TrainConfig, Trainer

    mesh_cfg = MeshConfig(data=8 // n_stages, pipe=n_stages)
    mesh = create_mesh(mesh_cfg, jax.devices()[:8])
    rows = []
    # (n_micro, virtual_stages): v > 1 = interleaved schedule, bubble
    # (P-1)/(m*v + P - 1) — same total layers, thinner stages
    plan = [(1, 1), (2, 1), (4, 1), (8, 1), (4, 2), (8, 2)]
    for n_micro, v in plan:
        if (batch // (8 // n_stages)) % n_micro:
            continue
        if v > 1 and n_micro % n_stages:  # interleaved: groups of P
            continue
        cfg = GPTPipeConfig(
            vocab_size=256, block_size=128, dim=128, n_layers=n_stages * 2,
            n_heads=4, n_stages=n_stages * v, n_microbatches=n_micro,
            virtual_stages=v, pipeline_parallel=True,
        )
        tcfg = TrainConfig(
            steps=0, batch_size=batch, log_every=10_000, eval_every=0,
            mesh=mesh_cfg, pipeline_parallel=True,
            optimizer=OptimizerConfig(max_lr=1e-3, total_steps=10),
        )
        trainer = Trainer(GPTPipe(cfg), tcfg, rules=PP_RULES, mesh=mesh)
        toks = np.random.default_rng(0).integers(0, 256, size=100_000)
        it = lm_batch_iterator(toks, batch, cfg.block_size,
                               sharding=batch_sharding(mesh))
        b0 = next(it)
        state = trainer.init_state(b0)
        trainer._build_steps()
        for _ in range(3):
            state, m = trainer._train_step(state, next(it))
        float(jax.device_get(m["train_loss"]))
        t0 = time.perf_counter()
        n = 10
        for _ in range(n):
            state, m = trainer._train_step(state, next(it))
        float(jax.device_get(m["train_loss"]))
        dt = (time.perf_counter() - t0) / n
        ticks = n_micro * v + n_stages - 1
        rows.append({
            "n_stages": n_stages, "n_micro": n_micro, "virtual": v,
            "ticks": ticks,
            "bubble_fraction": round((n_stages - 1) / ticks, 4),
            "step_time_ms": round(1000 * dt, 2),
        })
        print(json.dumps(rows[-1]), flush=True)
    # amortization check: more microbatches must not be slower than m=1
    if len(rows) >= 2 and rows[-1]["step_time_ms"] > rows[0]["step_time_ms"] * 1.2:
        print(json.dumps({"warning": "no amortization measured "
                          "(per-tick overhead dominates at this scale)"}))

    _memory_body(n_stages)
    _memory_body_1f1b(n_stages)
    # production-ish scale (~100M stage stack, dim 1024, seq 1024):
    # memory_analysis is compile-only, so the CPU mesh measures it fine
    _memory_body(n_stages, batch=16, seq=1024, dim=1024)
    _memory_body_1f1b(n_stages, batch=16, seq=1024, dim=1024)


def _memory_body(n_stages: int, batch: int = 64, seq: int = 512,
                 dim: int = 256) -> None:
    """Live-memory study (BENCHMARKS.md PP memory table): XLA's compiled
    memory_analysis for the PP train step — temp_size is the peak live
    temp-buffer footprint per device, which is where the backward's saved
    activations land. Compares one full-batch GPipe flush against
    pp_grad_groups sequential flushes (loss+backward per group, grads
    accumulated): with n_microbatches = pipe size per flush, residual
    memory covers one group's ticks instead of the whole batch's —
    live activations scale with n_stages, not total microbatches.
    Compile-only (no execution), so production-scale dims are measurable
    on the CPU mesh."""
    import jax
    import numpy as np

    from solvingpapers_tpu.models.gpt_pipe import GPTPipe, GPTPipeConfig
    from solvingpapers_tpu.sharding import MeshConfig, PP_RULES, create_mesh
    from solvingpapers_tpu.train import OptimizerConfig, TrainConfig, Trainer

    n_micro_total = 16
    mesh_cfg = MeshConfig(data=1, pipe=n_stages)
    mesh = create_mesh(mesh_cfg, jax.devices()[:n_stages])
    x = np.random.default_rng(0).integers(0, 256, size=(batch, seq))
    b0 = {"x": x.astype(np.int32), "y": np.roll(x, -1, 1).astype(np.int32)}

    for groups in (1, n_micro_total // n_stages):
        cfg = GPTPipeConfig(
            vocab_size=256, block_size=seq, dim=dim, n_layers=n_stages * 2,
            n_heads=4, n_stages=n_stages,
            n_microbatches=n_micro_total // groups,
            pipeline_parallel=True, remat=True,
        )
        tcfg = TrainConfig(
            steps=0, batch_size=batch, log_every=10_000, eval_every=0,
            mesh=mesh_cfg, pipeline_parallel=True, pp_grad_groups=groups,
            optimizer=OptimizerConfig(max_lr=1e-3, total_steps=10),
        )
        trainer = Trainer(GPTPipe(cfg), tcfg, rules=PP_RULES, mesh=mesh)
        state = trainer.init_state(b0)
        trainer._build_steps()
        stats = trainer._train_step.lower(state, b0).compile().memory_analysis()
        print(json.dumps({
            "memory_study": {
                "dim": dim, "seq": seq,
                "pp_grad_groups": groups,
                "n_microbatches_per_flush": n_micro_total // groups,
                "temp_bytes_per_device": int(stats.temp_size_in_bytes),
                "temp_mb_per_device":
                    round(stats.temp_size_in_bytes / 2**20, 1),
                "argument_mb": round(stats.argument_size_in_bytes / 2**20, 1),
            }
        }), flush=True)


def _memory_body_1f1b(n_stages: int, batch: int = 64, seq: int = 512,
                      dim: int = 256) -> None:
    """1F1B memory row (VERDICT r4 ask 4): same GPT stages, same 16
    microbatches, loss+grads in ONE pass via
    sharding.pipeline.pipeline_1f1b_value_and_grad — peak temp memory must
    beat both the single-flush GPipe backward (residuals ∝ total
    microbatches) and pp_grad_groups (residuals ∝ one group, but one
    fill+drain bubble per group) at equal microbatch count."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from solvingpapers_tpu import ops
    from solvingpapers_tpu.models.gpt_pipe import GPTPipe, GPTPipeConfig
    from solvingpapers_tpu.models.layers import LayerNorm
    from solvingpapers_tpu.sharding import MeshConfig, create_mesh
    from solvingpapers_tpu.sharding.pipeline import (
        pipeline_1f1b_value_and_grad,
    )

    m = 16
    mesh = create_mesh(MeshConfig(data=1, pipe=n_stages),
                       jax.devices()[:n_stages])
    cfg = GPTPipeConfig(
        vocab_size=256, block_size=seq, dim=dim, n_layers=n_stages * 2,
        n_heads=4, n_stages=n_stages, n_microbatches=m,
        pipeline_parallel=True, remat=True,
    )
    model = GPTPipe(cfg)
    x = np.random.default_rng(0).integers(0, 256, (batch, seq)).astype(np.int32)
    y = np.roll(x, -1, 1).astype(np.int32)
    variables = model.init({"params": jax.random.key(0)}, jnp.asarray(x))
    p = variables["params"]
    head = {"ln_f": p["ln_f"], "lm_head": p["lm_head"]}

    def loss_fn(hp, h, target):
        z = LayerNorm().apply({"params": hp["ln_f"]}, h)
        return ops.cross_entropy(z @ hp["lm_head"]["kernel"], target)

    def step(stages_local, head, emb, pos, xx, yy):
        xe = jnp.take(emb["embedding"], xx, axis=0) + pos[None, :seq]
        micro = xe.reshape(m, batch // m, seq, dim)
        targets = yy.reshape(m, batch // m, seq)
        return pipeline_1f1b_value_and_grad(
            stages_local, head, micro, targets, model._stage_fn, loss_fn
        )

    pipe_spec = jax.tree.map(lambda _: P("pipe"), p["stages"])
    fn = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(pipe_spec, P(), P(), P(), P(), P()),
        out_specs=(P(), pipe_spec, P(), P()),
    ))
    stats = fn.lower(
        p["stages"], head, p["tok_emb"], p["pos_emb"], jnp.asarray(x),
        jnp.asarray(y),
    ).compile().memory_analysis()
    print(json.dumps({
        "memory_study": {
            "dim": dim, "seq": seq,
            "schedule": "1f1b",
            "n_microbatches_per_flush": m,
            "temp_bytes_per_device": int(stats.temp_size_in_bytes),
            "temp_mb_per_device": round(stats.temp_size_in_bytes / 2**20, 1),
            "argument_mb": round(stats.argument_size_in_bytes / 2**20, 1),
        }
    }), flush=True)


def _mesh_obs_overhead_body(n_steps: int = 24) -> None:
    """Paired ABBA mesh-obs overhead arm (the BENCH_serve.json
    trace/obs-overhead convention): a 2-stage 1F1B GPTPipe fit with
    TrainConfig.mesh_obs off (A) and on (B), run A B B A so monotonic
    load drift cancels, comparing the engine's own logged steady-state
    step_time_s. mesh_obs is observability mode (fenced dispatches +
    collective-ledger parse at compile + one stage probe outside the
    timed window); the budget it must hold is the established 2%."""
    import jax
    import numpy as np

    from solvingpapers_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()

    from solvingpapers_tpu.data.batches import lm_batch_iterator
    from solvingpapers_tpu.models.gpt_pipe import GPTPipe, GPTPipeConfig
    from solvingpapers_tpu.sharding import (
        MeshConfig, PP_RULES, batch_sharding, create_mesh,
    )
    from solvingpapers_tpu.train import OptimizerConfig, TrainConfig, Trainer

    mesh_cfg = MeshConfig(data=4, pipe=2)
    mesh = create_mesh(mesh_cfg, jax.devices()[:8])

    class _Last:
        def __init__(self):
            self.step_time = None

        def write(self, step, metrics):
            if "step_time_s" in metrics:
                self.step_time = metrics["step_time_s"]

        def close(self):
            pass

    def arm(mesh_obs: bool) -> float:
        cfg = GPTPipeConfig(
            vocab_size=256, block_size=128, dim=128, n_layers=2, n_heads=4,
            n_stages=2, n_microbatches=4, pipeline_parallel=True,
        )
        tcfg = TrainConfig(
            steps=n_steps, batch_size=32, log_every=n_steps, eval_every=0,
            mesh=mesh_cfg, pipeline_parallel=True, pp_schedule="1f1b",
            mesh_obs=mesh_obs,
            optimizer=OptimizerConfig(max_lr=1e-3, total_steps=n_steps),
        )
        trainer = Trainer(GPTPipe(cfg), tcfg, rules=PP_RULES, mesh=mesh)
        toks = np.random.default_rng(0).integers(0, 256, size=200_000)
        it = lm_batch_iterator(toks, 32, cfg.block_size,
                               sharding=batch_sharding(mesh))
        w = _Last()
        trainer.fit(it, writer=w)
        return float(w.step_time)

    walls = [arm(obs) for obs in (False, True, True, False)]  # A B B A
    off = (walls[0] + walls[3]) / 2
    on = (walls[1] + walls[2]) / 2
    print(json.dumps({
        "mesh_obs_overhead": {
            "steps_per_arm": n_steps,
            "step_time_s_off": round(off, 6),
            "step_time_s_on": round(on, 6),
            "mesh_obs_overhead_pct": round(100 * (on - off) / off, 2),
        }
    }), flush=True)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--stages", type=int, default=4)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--mesh-obs", action="store_true",
                   help="run only the paired ABBA mesh-obs overhead arm")
    args = p.parse_args()

    here = pathlib.Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(here))
    from solvingpapers_tpu.hostenv import virtual_cpu_devices, virtual_cpu_env

    # decided from the environment, not from jax.devices(): a parent that
    # initialised JAX would hold the accelerator while the child runs
    if virtual_cpu_devices() >= 8:
        if args.mesh_obs:
            _mesh_obs_overhead_body()
        else:
            _body(args.stages, args.batch)
        return 0
    # re-exec on the virtual CPU mesh (same recipe as __graft_entry__)
    if args.mesh_obs:
        snippet = (
            f"import sys; sys.path.insert(0, {str(here)!r}); "
            "from tools.bench_pipeline import _mesh_obs_overhead_body; "
            "_mesh_obs_overhead_body()"
        )
    else:
        snippet = (
            f"import sys; sys.path.insert(0, {str(here)!r}); "
            "from tools.bench_pipeline import _body; "
            f"_body({args.stages}, {args.batch})"
        )
    proc = subprocess.run([sys.executable, "-c", snippet],
                          env=virtual_cpu_env(8), cwd=str(here))
    return proc.returncode


if __name__ == "__main__":
    raise SystemExit(main())
