"""The path from the program's layers to a device trace.

  * the compiled train step of a tiny DeepSeekV3 names its own layers:
    `hlo_cost.device_scopes` finds every scope of the vocabulary that the
    configuration exercises, forward, backward and (with remat) recomputed,
    on nearly all top-level instructions;
  * a `while` is top-level, the instructions of its body are not;
  * the map comes from a compile of the CURRENT lowering: the persistent
    compilation cache leaves metadata out of its key and hands back the old
    names after a scope moves;
  * `TrainConfig.profile_dir` traces exactly the steps it was given, with
    the loop's annotations on the host plane and `device_scopes.json`
    beside the trace;
  * the logged row counts the host's wait for data and the rest of its loop.
"""

import collections
import glob
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import equations

from solvingpapers_tpu import ops
from solvingpapers_tpu.metrics import hlo_cost
from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3, DeepSeekV3Config
from solvingpapers_tpu.models.gpt import GPT, GPTConfig
from solvingpapers_tpu.sharding import MeshConfig, create_mesh
from solvingpapers_tpu.train import Trainer
from solvingpapers_tpu.train.engine import TrainConfig
from solvingpapers_tpu.train.objectives import dsv3_init_fn, dsv3_loss_fn

pytestmark = pytest.mark.fast

TINY = dict(vocab_size=64, block_size=32, dim=32, n_layers=2, n_heads=2,
            latent_dim=16, n_experts=4, dropout=0.0, attn_dropout=0.0)
MOE = {"L_moe_gate", "L_moe_dispatch", "L_moe_experts", "L_moe_combine",
       "L_moe_shared", "L_moe_stats"}
ALWAYS = MOE | {"L_embed", "L_attn_proj", "L_attn_core", "L_loss_head",
                "L_optimizer"}
# name -> (model settings, scopes the step must hold, passes it must hold)
STEPS = {
    # dsv3_tinystories' shape: dense attention, balance loss, no remat
    "dense": (dict(balance_loss_weight=0.01), ALWAYS, {"fwd", "bwd"}),
    # dsv3_long's shape, dense attention in place of the kernels
    "remat": (dict(remat=True, rope_dim=8), ALWAYS, {"fwd", "bwd", "remat"}),
    # the flash kernels by their `name=`, through Pallas' interpreter
    "flash": (dict(remat=True, rope_dim=8, use_flash=True, block_size=128,
                   n_layers=1, dtype="bfloat16"),
              ALWAYS | set(hlo_cost.KERNEL_SCOPES), {"fwd", "bwd", "remat"}),
}


def one_device_mesh():
    return create_mesh(MeshConfig(), devices=jax.devices()[:1])


def dsv3_trainer(**model):
    cfg = DeepSeekV3Config(**{**TINY, **model})
    trainer = Trainer(
        DeepSeekV3(cfg), TrainConfig(steps=2, batch_size=2, log_every=1),
        loss_fn=dsv3_loss_fn, init_fn=dsv3_init_fn, mesh=one_device_mesh())
    batch = {k: np.zeros((2, cfg.block_size), np.int32) for k in "xy"}
    return trainer, batch


@pytest.mark.parametrize("name", sorted(STEPS))
def test_train_step_names_its_layers(name):
    model, want_layers, want_passes = STEPS[name]
    trainer, batch = dsv3_trainer(**model)
    state = trainer.init_state(batch)
    trainer._build_steps()
    # registered as the first dispatch would, without running the step
    hlo_cost.register_program("jit_train_step", trainer._train_step,
                              (state, batch))
    scopes = hlo_cost.program_scopes("jit_train_step")
    assert hlo_cost.program_scopes("jit_train_step") is scopes  # kept
    top = [s for s in scopes.values() if s.top_level]
    assert {s.layer for s in top} - {None} == want_layers
    assert {s.pass_ for s in top} == want_passes
    # forward and backward of the layers that have weights
    for layer in ("L_attn_proj", "L_moe_experts", "L_moe_shared",
                  "L_loss_head"):
        assert {s.pass_ for s in top if s.layer == layer} >= {"fwd", "bwd"}
    # the optimizer is no part of the differentiated function
    assert {s.pass_ for s in top if s.layer == "L_optimizer"} == {"fwd"}
    # `maybe_remat` has no policy: the flash forward kernel runs again
    assert {s.pass_ for s in top if s.layer == "flash_mla_fwd"} == (
        {"fwd", "remat"} if name == "flash" else set())
    covered = sum(s.layer is not None for s in top) / len(top)
    assert covered >= 0.9, f"{covered:.3f} of {len(top)} top-level instructions"


@pytest.mark.parametrize("name", ["dense", "remat"])
def test_moe_rows_move_by_gather_forward_and_backward(name):
    """Dispatch and combine of the compiled step move rows through index
    maps: no product that contracts over tokens or slots, nothing shaped
    (T, E, C), no scatter in the backward, and the backward rules'
    gathers carry their layer."""
    # T, E, C, D and the experts' hidden size are five different numbers
    sizes = dict(n_experts=4, top_experts=2, capacity_factor=1.5)
    trainer, batch = dsv3_trainer(**STEPS[name][0], **sizes)
    cfg = trainer.model.cfg
    t, e = batch["x"].size, cfg.n_experts
    c = ops.moe.expert_capacity(t, e, cfg.top_experts, cfg.capacity_factor)
    assert len({t, e, c, cfg.dim, cfg.expert_hidden}) == 5
    state = trainer.init_state(batch)
    trainer._build_steps()
    text = trainer._train_step.lower(state, batch).compile().as_text()
    scopes = hlo_cost.device_scopes(text)
    defs = {m.name: (m, line) for _, _, m, line in hlo_cost._scan_defs(text)}
    dims_of = lambda shape: [  # noqa: E731  (each atom's dims, 1s left out)
        sorted(int(d) for d in atom.group("dims").split(",") if d and d != "1")
        for atom in hlo_cost._SHAPE_RE.finditer(shape)]
    routed = {n: s for n, s in scopes.items()
              if s.layer in ("L_moe_dispatch", "L_moe_combine")}
    assert {s.pass_ for s in routed.values()} == STEPS[name][2]
    for n in routed:
        m, line = defs[n]
        assert m.op != "scatter", line
        operands = hlo_cost._OPERAND_NAMES_RE.findall(
            line[m.end:].split(")", 1)[0])
        for shape in [m.out] + [defs[o][0].out for o in operands if o in defs]:
            assert sorted((t, e, c)) not in dims_of(shape), line
        if m.op in ("dot", "convolution"):
            lhs = hlo_cost._first_operand(
                line[m.end:], {k: d.out for k, (d, _) in defs.items()})
            lhs_dims = [int(d) for d in lhs.group("dims").split(",")]
            contracted = {lhs_dims[int(i)] for i in hlo_cost._CONTRACT_RE
                          .search(line).group("dims").split(",") if i}
            assert not contracted & {t, c}, line
    # every gather of the step has a layer, and the backward's are there:
    # dx in the dispatch's rule, dye and the rows of dprobs in the combine's
    gathers = {n: scopes[n] for n, (m, _) in defs.items() if m.op == "gather"}
    assert all(s.layer is not None for s in gathers.values()), gathers
    bwd = collections.Counter(
        s.layer for s in gathers.values() if s.pass_ == "bwd")
    assert bwd["L_moe_dispatch"] >= cfg.n_layers
    assert bwd["L_moe_combine"] >= cfg.n_layers


def _kernel_passes(trainer, state, batch, kernels, layer="L_moe_experts"):
    """{kernel: the passes of the compiled step in which what it lowers to
    appears}, every such instruction under `layer` (or, with `kernels` a
    dict, under the layer it names for that kernel); and the names of the
    step's `pallas_call`s, counted."""
    if not isinstance(kernels, dict):
        kernels = dict.fromkeys(kernels, layer)
    hlo_cost.register_program("jit_train_step", trainer._train_step,
                              (state, batch))
    scopes = hlo_cost.program_scopes("jit_train_step")
    text = trainer._train_step.lower(state, batch).compile().as_text()
    passes = collections.defaultdict(set)
    for _, _, m, line in hlo_cost._scan_defs(text):
        src = hlo_cost._OP_NAME_RE.search(line)
        if src is None or m.name not in scopes:  # a parameter, a constant
            continue
        for kernel, its_layer in kernels.items():
            if kernel in src.group("src"):
                assert scopes[m.name].layer == its_layer, line
                passes[kernel].add(scopes[m.name].pass_)
    assert not set(hlo_cost.LAYER_SCOPES + hlo_cost.KERNEL_SCOPES) & set(passes)
    calls = [eqn for eqn in equations(
        jax.make_jaxpr(trainer._train_step)(state, batch).jaxpr)
        if eqn.primitive.name == "pallas_call"]
    return dict(passes), calls


def test_grouped_expert_kernels_keep_the_experts_scope(monkeypatch):
    """On one TPU the routed experts run as `kernels/moe_grouped.py`'s two
    kernels (here through the interpreter, steered as the chip would): what
    they lower to stays `L_moe_experts`' in all three passes, since their
    `name=` is no scope of the vocabulary, so `moe_experts_ms` keeps reading
    them; and each call returns three arrays or more, because the
    benchmark's `benchmarks/kernels/flash_mla.kind_of` reads any Mosaic
    call with one or two results as a flash-attention kernel."""
    from solvingpapers_tpu.kernels import moe_grouped

    monkeypatch.setattr(moe_grouped, "ROW_TILE", 8)
    monkeypatch.setattr(moe_grouped, "is_tpu_backend", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    trainer, batch = dsv3_trainer(remat=True, rope_dim=8, dim=128, n_layers=1)
    state = trainer.init_state(batch)
    trainer._build_steps()
    passes, calls = _kernel_passes(
        trainer, state, batch, ("moe_glu_fwd", "moe_glu_bwd"))
    assert passes == {"moe_glu_fwd": {"fwd", "remat"}, "moe_glu_bwd": {"bwd"}}
    names = collections.Counter(c.params["name"] for c in calls)
    assert names == {"moe_glu_fwd": 2, "moe_glu_bwd": 1}, names  # fwd, remat
    assert all(len(c.outvars) >= 3 for c in calls)


def test_held_experts_kernels_keep_the_experts_scope(monkeypatch):
    """The held experts on one steered TPU (a Mamba-2 layer and an MoE layer
    of the `nemotron_h` family, ungated squared-ReLU experts, `SUMS_VMEM`
    shrunk so that the backward is the two kernels the published widths
    take): what all three kernels lower to is `L_moe_experts`', forward,
    recomputed and backward, and none of it is unscoped."""
    from solvingpapers_tpu.kernels import moe_grouped
    from solvingpapers_tpu.models.nemotron_h import NemotronH, NemotronHConfig
    from solvingpapers_tpu.ops import ssd
    from solvingpapers_tpu.train.objectives import chunked_head_loss_fn

    monkeypatch.setattr(moe_grouped, "ROW_TILE", 8)
    monkeypatch.setattr(moe_grouped, "SUMS_VMEM", 700_000)
    monkeypatch.setattr(moe_grouped, "is_tpu_backend", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(ssd, "SEGMENT", 16)
    cfg = NemotronHConfig(
        vocab_size=64, block_size=32, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        mamba_num_heads=8, mamba_head_dim=4, n_groups=2, ssm_state_size=8,
        chunk_size=8, n_routed_experts=4, router_experts=16, first_expert=4,
        num_experts_per_tok=3, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=48, use_flash=False,
        capacity_factor=2.0, dtype="float32", remat=True)
    assert cfg.hybrid_override_pattern[:2] == "ME"
    trainer = Trainer(
        NemotronH(cfg), TrainConfig(steps=2, batch_size=2, log_every=1),
        loss_fn=chunked_head_loss_fn, mesh=one_device_mesh())
    batch = {k: np.zeros((2, cfg.block_size), np.int32) for k in "xy"}
    state = trainer.init_state(batch)
    trainer._build_steps()
    # the state-space rule's kernels in the same step: their `name=` is no
    # scope of the vocabulary either, so `ssm_core_ms` keeps reading them;
    # the layers' remat keeps the forward one's results (SSD_RESIDUALS)
    passes, calls = _kernel_passes(trainer, state, batch, {
        **dict.fromkeys(("moe_glu_fwd", "moe_glu_bwd_dw", "moe_glu_bwd_dx"),
                        "L_moe_experts"),
        "ssd_fwd": "L_ssm_core", "ssd_bwd": "L_ssm_core"})
    assert passes == {"moe_glu_fwd": {"fwd", "remat"},
                      "moe_glu_bwd_dw": {"bwd"}, "moe_glu_bwd_dx": {"bwd"},
                      "ssd_fwd": {"fwd"}, "ssd_bwd": {"bwd"}}
    names = collections.Counter(c.params["name"] for c in calls)
    assert names == {"moe_glu_fwd": 2, "moe_glu_bwd_dw": 1,
                     "moe_glu_bwd_dx": 1,  # fwd, remat
                     "ssd_fwd": 1, "ssd_bwd": 1}, names  # the Mamba-2 layer


def test_program_scopes_knows_only_registered_programs():
    assert hlo_cost.program_scopes("jit_nobody_dispatched_this") is None


def test_chunked_loss_while_is_top_level_and_its_body_is_not():
    def loss(logits, labels):
        return ops.cross_entropy(logits, labels, chunk_size=8)

    text = jax.jit(jax.grad(loss)).lower(
        jnp.zeros((4, 16, 64), jnp.bfloat16), jnp.zeros((4, 16), jnp.int32)
    ).compile().as_text()
    scopes = hlo_cost.device_scopes(text)
    whiles = {n: s for n, s in scopes.items() if n.startswith("while")}
    assert {s.layer for s in whiles.values()} == {"L_loss_head"}
    # the scan over chunks, forward and backward; a loop inside one of
    # their bodies (the CPU backend makes one) is not top-level
    assert {s.pass_ for s in whiles.values() if s.top_level} == {"fwd", "bwd"}
    # the bodies hold the softmax's exponential; ENTRY does not
    inner = [n for n, s in scopes.items()
             if not s.top_level and s.layer == "L_loss_head"]
    assert len(inner) > len(whiles)
    body_text = text.split("ENTRY")[0]
    assert any(f"%{n} = " in body_text for n in inner)


HLO = """\
HloModule jit_f, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %tanh.1 = f32[4]{0} tanh(%p), metadata={op_name="jit(f)/jvp(M)/layer_0/moe/L_moe_experts/tanh"}
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element(%t), index=1
  %exp.2 = f32[4]{0} exponential(%x), metadata={op_name="jit(f)/transpose(jvp(L_loss_head))/while/body/exp"}
  ROOT %tuple.3 = (s32[], f32[4]{0}) tuple(%i, %exp.2)
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0), metadata={op_name="a"}
  %copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%a)
  %copy-done.1 = f32[4]{0} copy-done(%copy-start.1)
  %fusion.7 = f32[4]{0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation
  %copy.8 = f32[4]{0} copy(%fusion.7)
  %bitcast.10 = f32[4]{0} bitcast(%a)
  %k.9 = f32[4]{0} custom-call(%bitcast.10, %copy.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/transpose(jvp(M))/jvp(M)/checkpoint/rematted_computation/layer_0/mla/L_attn_core/flash_mla_fwd/pallas_call"}
  %bitcast.12 = f32[4]{0} bitcast(%a)
  %k.11 = f32[4]{0} custom-call(%bitcast.12), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/transpose(jvp(M))/layer_1/mixer/L_gdn_core/gated_delta_bwd/pallas_call"}
  %while.4 = (s32[], f32[4]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(f)/transpose(jvp(L_loss_head))/while"}
  %add.5 = f32[4]{0} add(%a, %a), metadata={op_name="jit(f)/add"}
  ROOT %mul.6 = f32[4]{0} multiply(%add.5, %add.5), metadata={op_name="jit(f)/L_optimizer/mul"}
}
"""


@pytest.mark.parametrize("name, want", [
    # a fusion without metadata of its own: its computation's root
    ("fusion.7", ("L_moe_experts", "fwd", True)),
    # made by the compiler: the layer of the operand it moves
    ("copy.8", ("L_moe_experts", "fwd", True)),
    # made by the compiler to feed an instruction: the layer of its user
    ("copy-done.1", ("L_moe_experts", "fwd", True)),
    ("copy-start.1", ("L_moe_experts", "fwd", True)),
    # a kernel by its own name, inside the layer that calls it; recomputed
    ("k.9", ("flash_mla_fwd", "remat", True)),
    # what the compiler made to feed a kernel: the kernel's layer, since
    # the kernel's name is for the kernel's own time
    ("bitcast.10", ("L_attn_core", "remat", True)),
    # a kernel whose name is no vocabulary (the gated delta rule's): its
    # time is its layer's, as `gdn_core_ms` reads it
    ("k.11", ("L_gdn_core", "bwd", True)),
    ("bitcast.12", ("L_gdn_core", "bwd", True)),
    ("while.4", ("L_loss_head", "bwd", True)),
    ("exp.2", ("L_loss_head", "bwd", False)),
    ("add.5", (None, "fwd", True)),
    ("mul.6", ("L_optimizer", "fwd", True)),
])
def test_device_scopes_on_crafted_hlo(name, want):
    scopes = hlo_cost.device_scopes(HLO)
    assert tuple(scopes[name]) == want
    assert "a" not in scopes and "p" not in scopes  # parameters run nothing


def test_map_is_of_the_current_lowering_not_of_the_cache(tmp_path):
    """Compile, rename one scope, compile again with the persistent cache
    on: the executable JAX runs is the cached one with the OLD name in its
    text, and `program_scopes` still shows the new name."""
    from jax.experimental.compilation_cache import compilation_cache

    def step_with(scope):
        def step(x):
            with jax.named_scope(scope):
                return jnp.tanh(x @ x) * 0.5
        return jax.jit(step)

    x = jnp.ones((32, 32))
    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        step_with("L_moe_dispatch").lower(x).compile()  # fills the cache
        assert any("step" in p.name for p in tmp_path.iterdir())
        moved = step_with("L_moe_combine")
        stale = moved.lower(x).compile().as_text()
        # the hazard itself: if this ever fails, a cache hit has begun to
        # carry current metadata and the bypass can go
        assert "L_moe_dispatch" in stale and "L_moe_combine" not in stale
        moved(x).block_until_ready()  # runs the cached executable
        hlo_cost.register_program("jit_step", moved, (x,))
        layers = {s.layer for s in hlo_cost.program_scopes("jit_step").values()}
        assert "L_moe_combine" in layers and "L_moe_dispatch" not in layers
        # and the cache is on again for whoever compiles next
        assert jax.config.jax_enable_compilation_cache is True
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


# ------------------------------------------------------------- the trainer

GPT_TINY = GPTConfig(vocab_size=32, block_size=16, dim=16, n_layers=1,
                     n_heads=2, dropout=0.0)


def gpt_batches(sleep_s=0.0):
    rng = np.random.default_rng(0)
    while True:
        if sleep_s:
            time.sleep(sleep_s)
        x = rng.integers(0, 32, size=(4, 16)).astype(np.int32)
        yield {"x": x, "y": x}


class Rows:
    def __init__(self):
        self.rows = []

    def write(self, step, row):
        self.rows.append((step, row))

    def close(self):
        pass


def gpt_fit(writer, batches, **train):
    cfg = TrainConfig(batch_size=4, eval_every=0, **train)
    trainer = Trainer(GPT(GPT_TINY), cfg, mesh=one_device_mesh())
    trainer.fit(batches, writer=writer)
    return trainer


@pytest.mark.parametrize("window", [(3, 8), (2, 4)])
def test_profile_dir_holds_whole_steps_annotations_and_the_map(
        tmp_path, window):
    from jax.profiler import ProfileData

    n = window[1] - window[0]
    gpt_fit(Rows(), gpt_batches(), steps=10, log_every=2,
            profile_dir=str(tmp_path), profile_steps=window)
    with open(tmp_path / "device_scopes.json") as f:
        scopes = json.load(f)
    assert set(scopes) == {"jit_train_step"}
    top_level = {name for name, (layer, pass_, top) in
                 scopes["jit_train_step"].items() if top}
    layers = {layer for layer, _, _ in scopes["jit_train_step"].values()}
    assert "L_optimizer" in layers and "L_loss_head" in layers
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    counts = collections.Counter(
        e.name for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events)
    # the loop's annotations, one of each for every step in the window
    assert counts["train"] == n  # StepTraceAnnotation
    assert counts["train_dispatch"] == n and counts["data_wait"] == n
    assert counts["log_fetch"] == len(
        [s for s in range(window[0] + 1, window[1] + 1) if s % 2 == 0])
    # exactly n whole executions of the train step: the CPU client has no
    # `XLA Modules` line, it shows each instruction of the program it runs
    ran = {name: c for name, c in counts.items() if name in top_level}
    assert len(ran) > 10 and set(ran.values()) == {n}, ran


def test_logged_row_counts_data_wait_and_host_loop():
    rows = Rows()
    gpt_fit(rows, gpt_batches(sleep_s=0.02), steps=9, log_every=3)
    timed = [row for _, row in rows.rows if "step_time_s" in row]
    assert len(timed) == 3
    for row in timed:
        # a feed that sleeps 20 ms reads 20 ms (host clock: within 2x)
        assert 15.0 <= row["data_wait_ms"] <= 40.0, row
        assert 0.0 <= row["host_loop_ms"] <= 1e3 * row["step_time_s"]
        assert row["data_wait_ms"] + row["host_loop_ms"] \
            <= 1e3 * row["step_time_s"] + 1e-6


def test_first_dispatch_registers_the_program_under_the_profilers_name():
    trainer = gpt_fit(Rows(), gpt_batches(), steps=2, log_every=1)
    jitted, args = hlo_cost._PROGRAMS["jit_train_step"]
    assert jitted is trainer._train_step
    # shapes, not buffers
    assert all(isinstance(a, jax.ShapeDtypeStruct)
               for a in jax.tree.leaves(args))
