"""Mesh observatory tests (metrics/mesh_obs.py + wiring through
sharding/, train/engine.py, metrics/trace.py, metrics/xla_obs.py).

Contracts under test:
  * `parse_hlo_collectives` counts and sizes collectives — pinned on
    synthetic HLO text (tuple outputs, async -start/-done pairs,
    operand references) and on a REAL TP-sharded compiled program
    against an independent hand-count of its HLO text; a single-device
    program reports a TRUE zero (not an absence).
  * `pytree_device_bytes` books `Sharding.shard_shape` bytes per device:
    replicated, TP-sharded, pipeline-stage-stacked, and mixed pytrees
    pinned against analytic byte counts.
  * the schedule algebra (sharding/pipeline.py) matches the schedules'
    tick math, and `bubble_report` reduces to the analytic
    (S-1)/(M+S-1) for balanced stages.
  * a deliberately imbalanced 2-stage pipeline (one stage 2x heavier)
    names the straggler and its MEASURED bubble fraction lands within
    tolerance of the prediction from probed stage costs.
  * `mesh/*` gauges are present IFF mesh_obs is enabled (the PR-5
    `mem/*`/`compile/*` key-surface pattern) and Prometheus-renderable.
  * mesh trace tracks round-trip: per-tick stage spans + bubble_report
    instant -> export -> `summarize_trace` mesh section -> formatter;
    traces recorded WITHOUT mesh events (PR-4/5 era) summarize with the
    mesh key absent — no crash, no invented zeros.
  * the Trainer's 1F1B wiring: a 2-stage pipeline fit with mesh_obs on
    emits bubble + comm gauges and a trace whose summary prints the
    bubble report.
"""

import functools
import json
import re
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from solvingpapers_tpu.metrics.mesh_obs import (
    MeshObservatory,
    PipelineScheduleInfo,
    bubble_report,
    parse_hlo_collectives,
    probe_stage_costs,
)
from solvingpapers_tpu.metrics.trace import (
    FlightRecorder,
    format_mesh,
    format_summary,
    summarize_trace,
)
from solvingpapers_tpu.metrics.writer import PrometheusTextWriter
from solvingpapers_tpu.metrics.xla_obs import (
    CompileRegistry,
    clear_aot_cache,
    pytree_bytes,
    pytree_device_bytes,
)
from solvingpapers_tpu.sharding import (
    MeshConfig,
    analytic_bubble_fraction,
    create_mesh,
    schedule_ticks,
    tick_unit,
)
from solvingpapers_tpu.sharding.pipeline import (
    pipeline_apply,
    stack_stage_params,
)

pytestmark = pytest.mark.fast


# ----------------------------------------------------- collective ledger


def test_parse_hlo_collectives_synthetic():
    """Hand-built HLO text: defining ops count (async pairs once, at the
    -start), operand references and -done lines never do, tuple output
    shapes sum their atoms."""
    hlo = "\n".join([
        "ENTRY %main {",
        "  %p = f32[8,128]{1,0} parameter(0)",
        "  %ar = f32[8,128]{1,0} all-reduce(f32[8,128]{1,0} %p), "
        "to_apply=%add",
        "  %ags = (f32[4,8]{1,0}, f32[8,8]{1,0}) all-gather-start("
        "f32[4,8]{1,0} %p), dimensions={0}",
        "  %agd = f32[8,8]{1,0} all-gather-done((f32[4,8], f32[8,8]) "
        "%ags)",
        "  %t = (f32[2,2]{1,0}) tuple(%ar)",
        "  %gte = f32[8,8]{1,0} get-tuple-element(%ags), index=1",
        "  %cp = f32[4]{0} collective-permute(f32[4]{0} %p2), "
        "source_target_pairs={{0,1}}",
        "  %rs = bf16[16]{0} reduce-scatter(bf16[32]{0} %y), "
        "dimensions={0}",
        "}",
    ])
    stats = parse_hlo_collectives(hlo)
    assert stats["ops"] == 4
    assert stats["by_type"]["all-reduce"] == {"ops": 1, "bytes": 8 * 128 * 4}
    # the -start's tuple output: f32[4,8] + f32[8,8]
    assert stats["by_type"]["all-gather"] == {
        "ops": 1, "bytes": (4 * 8 + 8 * 8) * 4,
    }
    assert stats["by_type"]["collective-permute"] == {"ops": 1, "bytes": 16}
    assert stats["by_type"]["reduce-scatter"] == {"ops": 1, "bytes": 32}
    assert stats["bytes"] == sum(
        d["bytes"] for d in stats["by_type"].values()
    )
    # a program with no collectives is a TRUE zero
    empty = parse_hlo_collectives("ENTRY %m {\n  ROOT %d = f32[4]{0} "
                                  "dot(%a, %b)\n}")
    assert empty == {"ops": 0, "bytes": 0, "by_type": {}}


def test_collective_ledger_tp_nonzero_single_device_zero(devices):
    """Acceptance pin: a TP-sharded program reports nonzero comm bytes
    (matching an independent hand-count of its compiled HLO text); a
    single-device program reports exactly zero."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    clear_aot_cache()
    mesh = create_mesh(MeshConfig(data=2, model=4), devices)
    reg = CompileRegistry(collectives=True)
    x = jax.device_put(
        jnp.ones((8, 128)), NamedSharding(mesh, P(("data", "fsdp"), "model"))
    )
    w = jax.device_put(
        jnp.ones((128, 64)), NamedSharding(mesh, P("model", None))
    )
    tp = jax.jit(lambda a, b: a @ b)
    reg.call("tp_matmul", ("sig",), tp, (x, w))
    single = jax.jit(lambda a: a @ a.T)
    reg.call("local_matmul", ("sig",), single, (jnp.ones((4, 4)),))

    stats = reg.collective_stats()
    assert stats["local_matmul"]["ops"] == 0
    assert stats["local_matmul"]["bytes"] == 0
    tp_stats = stats["tp_matmul"]
    assert tp_stats["ops"] >= 1 and tp_stats["bytes"] > 0
    assert "all-reduce" in tp_stats["by_type"]  # contracting-dim TP

    # hand-count: defining collective lines in the compiled HLO text,
    # independently of the parser's regex
    hlo = tp.lower(x, w).compile().as_text()
    hand = 0
    for line in hlo.splitlines():
        if "= " not in line:
            continue
        rhs = line.split("= ", 1)[1]
        for kind in ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute"):
            if re.search(rf"\s{kind}(-start)?\(", " " + rhs):
                hand += 1
                break
    assert tp_stats["ops"] == hand

    # gauges carry the ledger; the observatory key surface is floats
    obs = MeshObservatory(mesh=mesh, registry=reg)
    g = obs.gauges()
    assert g["mesh/comm_bytes_per_step"] == float(tp_stats["bytes"])
    assert g["mesh/comm_programs"] == 1.0  # only the TP program talks
    assert all(isinstance(v, float) for v in g.values())
    # /statusz carries the per-program join
    snap = obs.snapshot()
    assert snap["comm"]["tp_matmul"]["ops"] == tp_stats["ops"]
    assert reg.snapshot()["programs"]["tp_matmul"][
        "comm_bytes_per_call"] == tp_stats["bytes"]


# --------------------------------------------------- per-device HBM math


def test_pytree_device_bytes_sharded_pins(devices):
    """Replicated, TP-sharded, and pipeline-stage-stacked leaves book
    analytic shard_shape bytes per device; a mixed pytree sums them;
    host arrays fall back to global bytes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = create_mesh(MeshConfig(data=1, model=2, pipe=4), devices)

    def put(a, spec):
        return jax.device_put(a, NamedSharding(mesh, spec))

    rep = put(jnp.ones((16, 8), jnp.float32), P())           # 512 B global
    tp = put(jnp.ones((16, 8), jnp.float32), P(None, "model"))  # /2
    stacked = put(jnp.ones((4, 6, 4), jnp.float32), P("pipe"))  # /4

    assert pytree_device_bytes(rep) == 16 * 8 * 4
    assert pytree_device_bytes(tp) == 16 * 8 * 4 // 2
    assert pytree_device_bytes(stacked) == 4 * 6 * 4 * 4 // 4
    # global accounting is unchanged
    assert pytree_bytes(tp) == 16 * 8 * 4
    # mixed replicated + sharded pytree: the per-pool case the HBM
    # ledger books under a mesh
    tree = {"rep": rep, "tp": tp, "stages": {"w": stacked}}
    assert pytree_device_bytes(tree) == 512 + 256 + 96
    assert pytree_bytes(tree) == 512 + 512 + 384
    # host leaves: no sharding -> global bytes (single-device semantics)
    assert pytree_device_bytes({"h": np.ones((3, 3), np.float32)}) == 36


def test_hbm_ledger_books_per_device_bytes(devices):
    """The train engine registers per-device providers: a ledger over a
    pipe-stacked pool must report shard bytes, not global."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from solvingpapers_tpu.metrics.xla_obs import HBMLedger

    mesh = create_mesh(MeshConfig(data=1, model=2, pipe=4), devices)
    stages = jax.device_put(
        jnp.ones((4, 32, 32), jnp.float32), NamedSharding(mesh, P("pipe"))
    )
    ledger = HBMLedger(capacity_bytes=1 << 20)
    ledger.register("params", lambda: pytree_device_bytes(stages))
    assert ledger.pool_bytes()["params"] == 32 * 32 * 4  # one stage row
    assert ledger.headroom_bytes() == (1 << 20) - 32 * 32 * 4


# ----------------------------------------------------- schedule algebra


def test_schedule_algebra_pins():
    assert schedule_ticks(4, 4) == 7                      # gpipe m+P-1
    assert schedule_ticks(8, 2, n_virtual=2) == 17        # m*v+P-1
    assert schedule_ticks(4, 2, schedule="1f1b") == 10    # 2(m+P)-2
    assert analytic_bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert analytic_bubble_fraction(4, 2, 2) == pytest.approx(1 / 9)

    # gpipe: device d runs microbatch t-d, ramp/drain are bubbles
    assert tick_unit(0, 0, 4, 4) == "F0"
    assert tick_unit(2, 3, 4, 4) == "bubble"
    assert tick_unit(6, 3, 4, 4) == "F3"
    # 1f1b S=2, M=2 (mirrors the schedule: F at t=d+2i, B at
    # t=2P-1-d+2i, everything else a garbage-compute tick)
    labels = {d: [tick_unit(t, d, 2, 2, schedule="1f1b")
                  for t in range(schedule_ticks(2, 2, schedule="1f1b"))]
              for d in (0, 1)}
    assert labels[0] == ["F0", "bubble", "F1", "B0", "bubble", "B1"]
    assert labels[1] == ["bubble", "F0", "B0", "F1", "B1", "bubble"]
    # interleaved: group g member i on slice j
    assert tick_unit(0, 0, 4, 2, n_virtual=2) == "F0.v0"
    assert tick_unit(2, 0, 4, 2, n_virtual=2) == "F0.v1"
    assert tick_unit(4, 0, 4, 2, n_virtual=2) == "F2.v0"


def test_bubble_report_math():
    """Fabricated probe costs pin the report's algebra: balanced
    reduces to the analytic formula; imbalance folds into predicted;
    measured uses the same useful/capacity definition."""
    bal = bubble_report([1.0, 1.0], 4, schedule="gpipe")
    assert bal["predicted_bubble_fraction"] == pytest.approx(
        bal["analytic_bubble_fraction"]
    )
    assert bal["analytic_bubble_fraction"] == pytest.approx(0.2)

    rep = bubble_report([1.0, 2.0], 4, schedule="1f1b",
                        measured_step_s=10.0)
    assert rep["straggler_stage"] == 1
    assert rep["imbalance"] == pytest.approx(2 / 1.5, abs=1e-3)
    # useful = 4*3, capacity = 2 * (4+2-1)*2 -> 1 - 12/20
    assert rep["predicted_bubble_fraction"] == pytest.approx(0.4)
    assert rep["predicted_step_s"] == pytest.approx(10.0)
    # measured capacity = 2 * 10 -> same fraction at the predicted wall
    assert rep["measured_bubble_fraction"] == pytest.approx(0.4)
    with pytest.raises(ValueError, match="empty"):
        bubble_report([], 4)


# ------------------------------------- imbalanced-pipeline acceptance


def _mlp(p, x):
    return jnp.tanh(x @ p["w1"]) @ p["w2"]


def _light(p, x):
    return _mlp(p, x)


def _heavy(p, x):
    return _mlp(p, _mlp(p, x))  # 2x the flops, shape-preserving


def test_imbalanced_pipeline_names_straggler_and_measures_bubble(devices):
    """Acceptance pin: a 2-stage pipeline where stage 1 does 2x the work
    (a lax.switch on the pipe axis index — the schedule stays one SPMD
    program, the per-device cost differs). The probe must name stage 1
    the straggler, the prediction from the probed stage costs must lie
    above the balanced analytic, and the real ppermute-lockstep
    schedule's measured bubble fraction must be reported as a fraction.
    How close measurement and prediction land is a device question: both
    are host-clock readings here, taken beside other busy workers."""
    d, h, mb_rows, m = 384, 1536, 64, 4
    mesh = create_mesh(MeshConfig(data=1, pipe=2), devices[:2])

    def stage_fn(p, x):
        sid = jax.lax.axis_index("pipe")
        return jax.lax.switch(
            sid, [lambda xx: _light(p, xx), lambda xx: _heavy(p, xx)], x
        )

    key = jax.random.key(0)
    stages = [
        {"w1": jax.random.normal(jax.random.fold_in(key, i), (d, h)) * 0.02,
         "w2": jax.random.normal(jax.random.fold_in(key, i + 9),
                                 (h, d)) * 0.02}
        for i in range(2)
    ]
    stacked = stack_stage_params(stages)
    x_mb = jax.random.normal(jax.random.key(1), (mb_rows, d))

    # shared-box CPU contention can inflate one probe's min-of-reps;
    # re-probe (bounded) until the 2x structure is visible, then assert
    # a bound loose enough for a noisy box but tight enough to prove the
    # probe ranks the stages by their real cost
    for _ in range(3):
        stage_s = probe_stage_costs(stacked, x_mb, [_light, _heavy], reps=7)
        if stage_s[1] / stage_s[0] > 1.3:
            break
    assert len(stage_s) == 2 and all(t > 0 for t in stage_s)
    # stage 1 is the 2x stage; probe ratio must reflect it
    assert stage_s[1] > stage_s[0]
    assert 1.1 < stage_s[1] / stage_s[0] < 4.0

    batch = jax.random.normal(jax.random.key(2), (m * mb_rows, d))
    run = jax.jit(functools.partial(
        pipeline_apply, stage_fn=stage_fn, mesh=mesh, n_microbatches=m
    ))
    jax.block_until_ready(run(stacked, batch))  # compile outside timing
    measured = min(
        (lambda t0: (jax.block_until_ready(run(stacked, batch)),
                     time.monotonic() - t0)[1])(time.monotonic())
        for _ in range(5)
    )

    rep = bubble_report(stage_s, m, schedule="gpipe",
                        measured_step_s=measured)
    assert rep["straggler_stage"] == 1
    # imbalance pushes the prediction above the balanced analytic
    assert rep["analytic_bubble_fraction"] == pytest.approx(0.2)
    assert rep["predicted_bubble_fraction"] > rep["analytic_bubble_fraction"]
    # reported, and a fraction (NaN and inf fail the comparison too)
    assert 0.0 <= rep["measured_bubble_fraction"] < 1.0


# -------------------------------------------------- gauges key surface


class _RowWriter:
    def __init__(self):
        self.rows = []

    def write(self, step, metrics):
        self.rows.append((step, dict(metrics)))

    def close(self):
        pass


def _tiny_fit(mesh_obs: bool, devices, tmp_path=None, steps=2):
    from solvingpapers_tpu.data.batches import lm_batch_iterator
    from solvingpapers_tpu.models.gpt import GPT, GPTConfig
    from solvingpapers_tpu.sharding import batch_sharding
    from solvingpapers_tpu.train import (
        OptimizerConfig,
        TrainConfig,
        Trainer,
    )

    mesh_cfg = MeshConfig(data=8)
    mesh = create_mesh(mesh_cfg, devices)
    cfg = GPTConfig(vocab_size=64, block_size=16, dim=16, n_layers=1,
                    n_heads=2, dropout=0.0)
    tcfg = TrainConfig(
        steps=steps, batch_size=8, log_every=1, eval_every=0,
        mesh=mesh_cfg, mesh_obs=mesh_obs,
        optimizer=OptimizerConfig(max_lr=1e-3, total_steps=10),
    )
    trainer = Trainer(GPT(cfg), tcfg, mesh=mesh)
    toks = np.arange(2048) % 64
    it = lm_batch_iterator(toks, 8, 16, sharding=batch_sharding(mesh))
    w = _RowWriter()
    trainer.fit(it, writer=w)
    return w.rows[-1][1]


def test_mesh_gauges_present_iff_mesh_obs_enabled(devices):
    """The PR-5 key-surface contract extended to mesh/*: a fit without
    mesh_obs must never grow the keys; with it, the collective ledger
    rides every logged row (data-parallel grads all-reduce, so comm
    bytes are nonzero even without a pipeline) and the whole surface
    survives the Prometheus name grammar."""
    row_off = _tiny_fit(False, devices)
    assert not any(k.startswith("mesh/") for k in row_off)

    clear_aot_cache()
    row_on = _tiny_fit(True, devices)
    mesh_keys = {k: v for k, v in row_on.items() if k.startswith("mesh/")}
    assert mesh_keys["mesh/devices"] == 8.0
    assert mesh_keys["mesh/comm_bytes_per_step"] > 0  # DP grad all-reduce
    assert mesh_keys["mesh/comm_programs"] >= 1.0
    # mesh_obs implies the compile registry even with xla_obs off
    assert any(k.startswith("compile/") for k in row_on)
    # no pipeline -> no bubble gauges (absent, not zero)
    assert "mesh/bubble_fraction_analytic" not in mesh_keys
    name_re = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
    for k, v in mesh_keys.items():
        assert isinstance(v, float), k
        assert name_re.match(PrometheusTextWriter.sanitize(k)), k


# ------------------------------------------------- trace tracks + compat


def test_mesh_trace_roundtrip(tmp_path):
    """observe_step + set_stage_probe -> chrome export -> summarize:
    the per-stage tick timeline matches the schedule algebra, the bubble
    report instant survives, the formatter names the straggler."""
    rec = FlightRecorder()
    obs = MeshObservatory(
        mesh=None, registry=None, trace=rec,
        schedule=PipelineScheduleInfo(n_stages=2, n_microbatches=2,
                                      schedule="1f1b"),
    )
    obs.set_stage_probe([0.001, 0.002], 2)
    obs.observe_step(ts=0.0, dur_s=0.6)
    path = str(tmp_path / "mesh_trace.json")
    rec.export_chrome(path)

    summary = summarize_trace(path)
    mesh = summary["mesh"]
    # 6 ticks per device; per device: 2 F, 2 B, 2 bubbles (pinned above)
    for stage in ("stage0", "stage1"):
        d = mesh["stages"][stage]
        assert d["ticks"] == 6
        assert d["fwd"] == 2 and d["bwd"] == 2 and d["bubble"] == 2
        assert d["busy_s"] + d["bubble_s"] == pytest.approx(0.6, rel=1e-3)
    assert mesh["bubble"]["straggler_stage"] == 1
    assert mesh["bubble"]["measured_bubble_fraction"] is not None
    text = format_mesh(mesh)
    assert "straggler: stage1" in text
    assert "bubble fraction" in text
    # and through the full formatter (request-less serve summary)
    assert "straggler: stage1" in format_summary(summary)


def test_mesh_span_synthesis_is_capped():
    rec = FlightRecorder()
    obs = MeshObservatory(
        trace=rec,
        schedule=PipelineScheduleInfo(n_stages=2, n_microbatches=2),
        max_step_traces=2,
    )
    for i in range(5):
        obs.observe_step(ts=float(i), dur_s=0.1)
    ticks = schedule_ticks(2, 2)
    assert len(rec) == 2 * 2 * ticks  # 2 steps x 2 stages x ticks


def test_pre_mesh_traces_summarize_without_mesh_key(tmp_path):
    """Backward compat: a PR-4/5-era trace (request lifecycle spans, no
    mesh events) must summarize with NO mesh key — sections absent, not
    zeroed — and `cli trace-summary` must exit 0 on both serve- and
    train-shaped old traces."""
    rec = FlightRecorder()
    rec.instant("submit", "request", "queue", req=1)
    rec.complete("queue", "request", "queue", ts=0.0, dur=0.1, req=1)
    rec.complete("prefill", "request", "slot0", ts=0.1, dur=0.2, req=1,
                 tokens=4)
    rec.complete("decode", "request", "slot0", ts=0.3, dur=0.3, req=1)
    rec.instant("finish", "request", "engine", req=1, reason="eos")
    serve_path = str(tmp_path / "old_serve_trace.json")
    rec.export_chrome(serve_path)

    summary = summarize_trace(serve_path)
    assert "mesh" not in summary
    assert summary["n_requests"] == 1
    out = format_summary(summary)
    assert "bubble" not in out and "collective" not in out

    rec2 = FlightRecorder()
    rec2.complete("step", "train", "train", ts=0.0, dur=0.5, steps=1)
    rec2.instant("goodput", "train", "train", goodput=0.9, step_s=0.5,
                 wall_s=0.55)
    train_path = str(tmp_path / "old_train_trace.json")
    rec2.export_chrome(train_path)
    assert "mesh" not in summarize_trace(train_path)

    from solvingpapers_tpu.cli import cmd_trace_summary

    for p in (serve_path, train_path):
        rc = cmd_trace_summary(types.SimpleNamespace(trace=p, top=5))
        assert rc == 0


# --------------------------------------------------- trainer 1F1B wiring


def test_trainer_1f1b_mesh_obs_end_to_end(devices, tmp_path):
    """A 2-stage 1F1B fit with mesh_obs on: bubble + comm gauges ride
    the log rows, /statusz-shaped snapshots carry the mesh section, and
    the exported trace's summary prints the bubble report."""
    from solvingpapers_tpu.data.batches import lm_batch_iterator
    from solvingpapers_tpu.models.gpt_pipe import GPTPipe, GPTPipeConfig
    from solvingpapers_tpu.sharding import PP_RULES, batch_sharding
    from solvingpapers_tpu.train import (
        OptimizerConfig,
        TrainConfig,
        Trainer,
    )

    clear_aot_cache()
    mesh_cfg = MeshConfig(data=4, pipe=2)
    mesh = create_mesh(mesh_cfg, devices)
    cfg = GPTPipeConfig(vocab_size=64, block_size=32, dim=32, n_layers=2,
                        n_heads=2, n_stages=2, n_microbatches=4,
                        pipeline_parallel=True)
    trace_path = str(tmp_path / "mesh_train_trace.json")
    tcfg = TrainConfig(
        steps=3, batch_size=16, log_every=1, eval_every=0,
        mesh=mesh_cfg, pipeline_parallel=True, pp_schedule="1f1b",
        mesh_obs=True, trace_path=trace_path,
        optimizer=OptimizerConfig(max_lr=1e-3, total_steps=10),
    )
    trainer = Trainer(GPTPipe(cfg), tcfg, rules=PP_RULES, mesh=mesh)
    toks = np.arange(8192) % 64
    it = lm_batch_iterator(toks, 16, 32, sharding=batch_sharding(mesh))
    w = _RowWriter()
    trainer.fit(it, writer=w)

    # the goodput row is last; the last metrics row carries the gauges
    row = next(m for _, m in reversed(w.rows) if "mesh/devices" in m)
    assert row["mesh/bubble_fraction_analytic"] == pytest.approx(0.2)
    assert "mesh/bubble_fraction_measured" in row
    assert row["mesh/straggler_stage"] in (0.0, 1.0)
    assert row["mesh/stage_imbalance"] >= 1.0
    assert row["mesh/comm_bytes_per_step"] > 0
    assert "mesh/comm_collective_permute_ops" in row  # the ppermute ring

    snap = trainer._mesh_obs.snapshot()
    assert snap["mesh_axes"]["pipe"] == 2
    assert snap["bubble"]["n_devices"] == 2
    json.dumps(snap)  # /statusz-serializable

    summary = summarize_trace(trace_path)
    mesh_section = summary["mesh"]
    assert "stage0" in mesh_section["stages"]
    assert "train_step" in mesh_section["comm"]
    text = format_mesh(mesh_section)
    assert "bubble fraction" in text and "collective ledger" in text
