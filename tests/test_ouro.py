"""The Ouro family (a stack of sandwich-normed RoPE layers run T times with
shared weights, an exit gate after every pass) at a tiny size on the CPU,
seeded weights:

  * the published parameter counts, what the config refuses;
  * the model's loss and every gradient leaf against the plain reference
    (`benchmarks/reference/ouro_ref.py`) through the benchmark's adapter,
    in float32, dense and flash paths, where the two are the same function;
  * the first three `Trainer.fit` steps in bfloat16 against the reference's
    `follow_training`;
  * the gradient of a shared weight is the sum of the gradients of its T
    copies in an UNSHARED model (T x L layers with the same values);
  * T = 1 is the plain sandwich-normed transformer (p = 1, H = 0); the exit
    probabilities sum to one and the loss falls to the last pass's
    cross-entropy as the gate's bias goes to minus infinity;
  * a model run with T = 3, or one whose loss drops the gate's term, fails
    the limits a sound run passes;
  * `ops.head_cross_entropy` is the mean of `ops.head_nll_rows`;
  * the compiled train step names every layer application at top level,
    the exit gate's scope and the passes; `cli serve` refuses the family;
    the trainer's MFU gauge counts T passes and T heads.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import keep_against_plain_remat

from benchmarks.adapters import ouro as adapter
from benchmarks.drivers.train_job import Rows
from benchmarks.kernels import ouro_model
from benchmarks.reference import ouro_ref as ref
from solvingpapers_tpu import ops
from solvingpapers_tpu.configs import get_config
from solvingpapers_tpu.configs.factory import (
    build_model, init_fn_for, loss_fn_for,
)
from solvingpapers_tpu.metrics import hlo_cost
from solvingpapers_tpu.metrics.mfu import looped_flops_per_token
from solvingpapers_tpu.models import ouro
from solvingpapers_tpu.models.ouro import Ouro, OuroConfig
from solvingpapers_tpu.sharding import MeshConfig, create_mesh
from solvingpapers_tpu.train import Trainer
from solvingpapers_tpu.train.engine import TrainConfig
from solvingpapers_tpu.train.objectives import (
    exit_distribution, ouro_loss_fn,
)
from solvingpapers_tpu.train.optim import OptimizerConfig

pytestmark = pytest.mark.fast

TINY = dict(
    vocab_size=97, block_size=64, hidden_size=32, intermediate_size=48,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
    head_dim=8, total_ut_steps=4, use_flash=False)
B, S = 2, 48


@pytest.fixture(autouse=True)
def one_segment(monkeypatch):
    """S = 48 runs as one block of the per-token stages (a loop a stage
    and layer application less to compile);
    `test_stages_block_by_block_equal_the_whole_sequence` runs three."""
    monkeypatch.setattr(ouro, "SEGMENT", 64)


def tiny(**over):
    return OuroConfig(**{**TINY, **over})


def batch(seed=1):
    x = jax.random.randint(jax.random.key(seed), (B, S + 1), 0, 97)
    return {"x": x[:, :-1], "y": x[:, 1:]}


def shapes_of(cfg):
    return jax.eval_shape(lambda: Ouro(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]


def count(tree):
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))


def seeded(cfg, seed=5, init_std=0.2, gate_bias=0.3):
    """(reference sizes, reference weights, the same as the program's
    tree). A wide init and a gate's bias off zero, so that at this width
    every layer and the gate matter."""
    sz = dataclasses.replace(adapter.sizes_of(cfg), init_std=init_std)
    w = ref.make_weights(seed, sz)
    w["gate_b"] = np.float32(gate_bias)
    return sz, w, adapter.to_program_tree(w, shapes_of(cfg))


@functools.lru_cache(maxsize=None)
def _loss_and_grads(cfg):
    model = Ouro(cfg)

    def loss_fn(p, b):
        loss, aux, _ = ouro_loss_fn(model, p, b, jax.random.key(0), None,
                                    True)
        return loss, aux

    return jax.jit(jax.value_and_grad(loss_fn, has_aux=True))


def loss_and_grads(cfg, tree, b):
    """((loss, aux), gradients); one compile a config and SEGMENT."""
    return _loss_and_grads(cfg)(tree, b)


def test_published_parameter_counts():
    full = OuroConfig()
    assert (full.num_hidden_layers, full.total_ut_steps) == (48, 4)
    # the passes share one set of weights: counted from one pass
    whole = shapes_of(dataclasses.replace(full, total_ut_steps=1))
    here = shapes_of(dataclasses.replace(full, num_hidden_layers=8))
    assert count(here["layer_0"]) == 51_388_416
    assert count(here["tok_emb"]) + count(here["lm_head"]) == 201_326_592
    assert count(here) == 612_438_017  # 9.80 GB at 16 bytes
    assert count(whole) == 2_667_974_657  # the published "2.6B"
    # ONE set of layers however many passes run
    assert count(shapes_of(dataclasses.replace(
        full, num_hidden_layers=8, total_ut_steps=1))) == count(here)


@pytest.mark.parametrize("field, value", [
    ("use_sliding_window", True), ("sliding_window", 4096),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("layer_types", ("full_attention", "sliding_attention")),
    ("total_ut_steps", 0)])
def test_config_refuses_what_has_no_path_here(field, value):
    with pytest.raises(ValueError, match=field):
        tiny(**{field: value})


def test_model_refuses_a_cache_and_a_sequence_past_its_block():
    cfg = tiny()
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          shapes_of(cfg))
    with pytest.raises(NotImplementedError, match="R-M15"):
        Ouro(cfg).apply({"params": params}, jnp.zeros((1, 8), jnp.int32),
                        caches=[])
    with pytest.raises(ValueError, match="block_size"):
        Ouro(cfg).apply({"params": params}, jnp.zeros((1, 65), jnp.int32))


@pytest.mark.parametrize("use_flash", [False, True])
def test_loss_and_gradients_match_the_reference_float32(use_flash):
    cfg = tiny(dtype="float32", use_flash=use_flash)
    sz, w, tree = seeded(cfg)
    b = batch()
    reference = jax.jit(jax.value_and_grad(
        lambda w: ref.loss_fn(w, b["x"], b["y"], sz), has_aux=True))
    (loss, aux), g_model = loss_and_grads(cfg, tree, b)
    (want, (ce, entropy)), g_ref = reference(w)
    assert float(loss) == pytest.approx(float(want), abs=2e-5)
    for t in range(4):
        assert float(aux[f"ce_ut{t + 1}"]) == pytest.approx(float(ce[t]),
                                                            abs=2e-5)
    assert float(aux["exit_entropy"]) == pytest.approx(float(entropy),
                                                       abs=1e-5)
    assert 0.5 < float(entropy) < np.log(4)
    assert 1.0 < float(aux["exit_mean_step"]) < 4.0
    got = adapter.leaf_norms(jax.tree.map(
        lambda a, r: a - r, g_model, adapter.to_program_tree(g_ref, tree)))
    ref_norms = {k: float(jnp.linalg.norm(v)) for k, v in g_ref.items()}
    scale = float(np.median(list(ref_norms.values())))
    for name, gap in got.items():
        assert gap <= 2e-3 * max(ref_norms[name], scale), (name, gap)
    # every weight takes a gradient, the gate's two among them
    assert all(v > 0.0 for v in ref_norms.values()), ref_norms


def test_stages_block_by_block_equal_the_whole_sequence(monkeypatch):
    b = batch()
    cfg = tiny(dtype="float32")
    _, _, tree = seeded(cfg)
    (want, _), g_want = loss_and_grads(cfg, tree, b)  # S = 48: one piece
    monkeypatch.setattr(ouro, "SEGMENT", 16)  # three blocks
    _loss_and_grads.cache_clear()
    (got, _), g_got = loss_and_grads(cfg, tree, b)
    _loss_and_grads.cache_clear()
    assert float(got) == pytest.approx(float(want), abs=1e-5)
    for (path, a), c in zip(jax.tree_util.tree_flatten_with_path(g_got)[0],
                            jax.tree.leaves(g_want)):
        np.testing.assert_allclose(
            a, c, atol=1e-3 * max(float(jnp.max(jnp.abs(c))), 1e-3),
            err_msg=str(path))


def test_a_shared_weights_gradient_is_the_sum_over_its_unshared_copies():
    """An UNSHARED model: T x L layers, layer t * L + l a copy of layer l,
    each used once; norm, gate and head after every L of them. Its
    gradients, added up over a layer's T copies, are the looped model's."""
    cfg = tiny(dtype="float32")
    sz, w, tree = seeded(cfg)
    b = batch()
    n_l, n_t = sz.layers, sz.ut_steps
    flat = dict(w)
    for t in range(n_t):
        for i in range(n_l):
            for k, v in ref.layer_weights(w, i).items():
                flat[f"l{t * n_l + i}.{k}"] = v

    def unshared_loss(wu):
        h = wu["tok_emb"][b["x"]]
        nll, gates = [], []
        for t in range(n_t):
            for i in range(n_l):
                h = ref.layer(ref.layer_weights(wu, t * n_l + i), h, sz,
                              None, 4096)
            h = ref.norm(h, wu["norm_f"], sz.norm_eps)
            nll.append(ref.token_losses(wu, h, b["y"]))
            gates.append(jnp.sum(h * wu["gate_w"], -1) + wu["gate_b"])
        p = ref.exit_probabilities(gates)
        entropy = -sum(q * jnp.log(q) for q in p)
        return jnp.mean(sum(q * n for q, n in zip(p, nll))
                        - sz.entropy_weight * entropy)

    want, g_un = jax.jit(jax.value_and_grad(unshared_loss))(flat)
    (loss, _), g_model = loss_and_grads(cfg, tree, b)
    assert float(loss) == pytest.approx(float(want), abs=2e-5)
    for i in range(n_l):
        for k in ref.layer_weights(w, i):
            copies = [g_un[f"l{t * n_l + i}.{k}"] for t in range(n_t)]
            # no copy's gradient is the whole: each pass adds its own
            assert all(float(jnp.linalg.norm(c)) > 0 for c in copies)
            shared = adapter.to_program_tree(
                {**w, f"l{i}.{k}": sum(copies)}, tree)
            got = g_model[f"layer_{i}"]
            name = next(n for n, r in adapter._LAYER_LEAVES.items()
                        if r == k)
            np.testing.assert_allclose(
                got[name], shared[f"layer_{i}"][name], rtol=2e-3,
                atol=2e-3 * float(jnp.max(jnp.abs(got[name]))),
                err_msg=f"l{i}.{k}")
            assert float(jnp.linalg.norm(got[name] - copies[0])) > 0.1 * \
                float(jnp.linalg.norm(got[name]))


def test_one_pass_is_the_plain_sandwich_normed_transformer():
    cfg = tiny(dtype="float32", total_ut_steps=1)
    _, _, tree = seeded(cfg)
    b = batch()
    (loss, aux), grads = loss_and_grads(cfg, tree, b)
    logits, _ = Ouro(cfg).apply({"params": tree}, b["x"])
    plain = ops.cross_entropy(logits, b["y"])
    assert float(loss) == pytest.approx(float(plain), abs=1e-5)
    assert float(aux["ce_ut1"]) == pytest.approx(float(plain), abs=1e-5)
    assert float(aux["exit_entropy"]) == 0.0
    assert float(aux["exit_mean_step"]) == 1.0
    assert set(aux) == {"ce_ut1", "exit_entropy", "exit_mean_step"}
    # with one exit the gate decides nothing and takes no gradient
    assert float(jnp.linalg.norm(grads["exit_gate_kernel"])) == 0.0
    assert float(grads["exit_gate_bias"]) == 0.0


def test_exit_probabilities_sum_to_one_and_a_closed_gate_leaves_the_last():
    logits = 3.0 * jax.random.normal(jax.random.key(2), (4, 3, 7))
    p = jnp.exp(exit_distribution(logits))
    np.testing.assert_allclose(jnp.sum(p, 0), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(logits)
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), rtol=1e-4)
    np.testing.assert_allclose(
        p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=1e-4)
    np.testing.assert_allclose(
        p, jnp.stack(ref.exit_probabilities(list(logits))), rtol=1e-4,
        atol=1e-7)
    # the gate's bias towards minus infinity: lambda -> 0, all the mass on
    # the last exit, no entropy, the loss the last pass's cross-entropy
    cfg = tiny(dtype="float32")
    _, _, tree = seeded(cfg)
    b = batch()
    gaps = []
    for bias in (0.0, -8.0, -30.0):
        closed = {**tree, "exit_gate_bias": jnp.float32(bias)}
        (loss, aux), _ = loss_and_grads(cfg, closed, b)
        gaps.append(abs(float(loss) - float(aux["ce_ut4"])))
    assert gaps[0] > 1e-2 > gaps[1] > gaps[2]
    assert gaps[2] < 1e-5
    assert float(aux["exit_entropy"]) < 1e-6
    assert float(aux["exit_mean_step"]) == pytest.approx(4.0, abs=1e-5)


# what the benchmark's `correct` compares, at this size
LIMITS = {"loss_gap": 5e-3, "grad_norm_gap": 1e-2}


OPT = OptimizerConfig(name="adamw", max_lr=3e-3, warmup_steps=2,
                      total_steps=10, b1=0.9, b2=0.95, weight_decay=0.1,
                      grad_clip=1.0)


def fit_three_steps(cfg, tree, loss_fn=ouro_loss_fn):
    train = TrainConfig(steps=3, batch_size=B, log_every=1, eval_every=0,
                        ckpt_every=0, optimizer=OPT, seed=0)
    trainer = Trainer(
        Ouro(cfg), train, loss_fn=loss_fn,
        mesh=create_mesh(MeshConfig(), devices=jax.devices()[:1]))
    batches = [batch(seed) for seed in (1, 2, 3)]
    state = trainer.init_state(batches[0])
    # placed as the step keeps them: the step then compiles once, not
    # again at step 2 for another sharding of the same state
    state = state.replace(params=jax.device_put(
        jax.tree.map(jnp.array, tree), trainer._state_shardings.params))
    rows = Rows()
    state = trainer.fit(iter(batches), None, writer=rows, state=state)
    return [r for r in rows.rows if "train_loss" in r], state


@functools.lru_cache(maxsize=None)
def followed(seed=7):
    """The reference's three steps from the seeded weights, once."""
    cfg = tiny(dtype="bfloat16")
    sz, w, _ = seeded(cfg, seed=seed)
    host = [(np.asarray(b["x"]), np.asarray(b["y"]))
            for b in (batch(s) for s in (1, 2, 3))]
    return ref.follow_training(w, host, sz, adapter.adam_of(OPT))


def gaps(logged, want):
    return {
        "loss_gap": max(abs(r["train_loss"] - b)
                        for r, b in zip(logged, want["loss"])),
        "grad_norm_gap": max(abs(r["grad_norm"] - b) / b
                             for r, b in zip(logged, want["grad_norm"]))}


def test_first_three_fit_steps_follow_the_reference():
    cfg = tiny(dtype="bfloat16")
    _, _, tree = seeded(cfg, seed=7)
    logged, state = fit_three_steps(cfg, tree)
    assert [r["step"] for r in logged] == [1, 2, 3]
    assert all({"train_ce_ut1", "train_ce_ut4", "train_exit_entropy",
                "train_exit_mean_step"} <= set(r) for r in logged)
    want = followed()
    assert want["dropped"] == [0.0, 0.0, 0.0]
    sound = gaps(logged, want)
    assert all(sound[k] <= LIMITS[k] for k in LIMITS), sound
    moved = adapter.leaf_norms(jax.tree.map(
        lambda a, b: a - b, state.params, jax.tree.map(jnp.array, tree)))
    scale = float(np.median(list(want["delta"].values())))
    worst = max(abs(moved[k] - v) / max(v, scale)
                for k, v in want["delta"].items())
    assert worst <= 0.05, worst


def _last_exit_only(model, params, batch, rng, model_state, train):
    """A loss with the gate's term dropped: the last pass's cross-entropy
    alone."""
    (hidden, _), _ = model.apply({"params": params}, batch["x"], head=False)
    return ops.head_cross_entropy(
        hidden[-1], params["lm_head"]["kernel"], batch["y"]), {}, model_state


@pytest.mark.parametrize("fault", ["three_passes", "gate_dropped"])
def test_a_pass_less_or_a_loss_without_its_gate_fails_the_limits(fault):
    """The reference runs four passes under the whole loss; a program with
    three, or one whose loss forgets the exit distribution, is not within
    the limits a sound run keeps."""
    cfg = tiny(dtype="bfloat16")
    _, _, tree = seeded(cfg, seed=7)
    if fault == "three_passes":
        logged, _ = fit_three_steps(
            dataclasses.replace(cfg, total_ut_steps=3), tree)
    else:
        logged, _ = fit_three_steps(cfg, tree, _last_exit_only)
    got = gaps(logged, followed())
    assert any(got[k] > LIMITS[k] for k in LIMITS), got


def test_head_cross_entropy_is_the_mean_of_the_rows():
    h = jax.random.normal(jax.random.key(0), (3, 2, 16, 8), jnp.float32)
    kernel = jax.random.normal(jax.random.key(1), (8, 33), jnp.float32)
    labels = jax.random.randint(jax.random.key(2), (3, 2, 16), 0, 33)
    rows = ops.head_nll_rows(h, kernel, labels, chunk_size=8)
    assert rows.shape == labels.shape and rows.dtype == jnp.float32
    logp = jax.nn.log_softmax(h @ kernel)
    want = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    np.testing.assert_allclose(rows, want, rtol=1e-5, atol=1e-5)
    # rows that the chunk does not divide run as one chunk
    np.testing.assert_allclose(
        ops.head_nll_rows(h, kernel, labels, chunk_size=7), want, rtol=1e-5,
        atol=1e-5)
    mean = lambda h, k: ops.head_cross_entropy(  # noqa: E731
        h, k, labels, chunk_size=8)
    of_rows = lambda h, k: jnp.mean(  # noqa: E731
        ops.head_nll_rows(h, k, labels, chunk_size=8))
    np.testing.assert_allclose(mean(h, kernel), of_rows(h, kernel),
                               rtol=1e-6)
    for a, c in zip(jax.grad(mean, (0, 1))(h, kernel),
                    jax.grad(of_rows, (0, 1))(h, kernel)):
        np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-7)
    # a weight a row takes its gradient through the rows
    weights = jax.random.uniform(jax.random.key(3), labels.shape)
    g = jax.grad(lambda w: jnp.sum(w * ops.head_nll_rows(
        h, kernel, labels, chunk_size=8)))(weights)
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-5)


def test_registry_holds_the_published_sizes_and_the_factory_builds_it():
    cfg = get_config("ouro_2p6b")
    m = cfg.model
    assert cfg.model_family == "ouro"
    assert (m.num_hidden_layers, m.hidden_size, m.intermediate_size,
            m.vocab_size, m.total_ut_steps) == (48, 2048, 5632, 49_152, 4)
    assert (m.num_attention_heads, m.num_key_value_heads, m.head_dim,
            m.rope_theta, m.rms_norm_eps) == (16, 16, 128, 1e6, 1e-6)
    assert (m.block_size, m.remat, m.use_flash, m.dtype,
            m.exit_entropy_weight) == (4096, True, True, "bfloat16", 0.1)
    assert (cfg.train.batch_size, cfg.train.tokens_per_step) == (2, 8192)
    assert cfg.train.optimizer.name == "adamw"
    small = dataclasses.replace(cfg, model=tiny())
    assert isinstance(build_model(small), Ouro)
    assert loss_fn_for(small) is ouro_loss_fn
    assert init_fn_for(small) is None


@pytest.mark.parametrize("use_flash", [False, True],
                         ids=["dense_attention", "flash"])
def test_train_step_names_every_layer_application_the_gate_and_the_passes(
        use_flash):
    cfg = tiny(dtype="float32", remat=True, use_flash=use_flash)
    trainer = Trainer(
        Ouro(cfg), TrainConfig(steps=2, batch_size=B, log_every=1),
        loss_fn=ouro_loss_fn,
        mesh=create_mesh(MeshConfig(), devices=jax.devices()[:1]))
    b = {k: np.asarray(v) for k, v in batch().items()}
    state = trainer.init_state(b)
    trainer._build_steps()
    with hlo_cost._persistent_cache_off():
        text = trainer._train_step.lower(state, b).compile().as_text()
    scopes = hlo_cost.device_scopes(text)
    top = [s for s in scopes.values() if s.top_level]
    layers = {s.layer for s in top}
    assert {"L_attn_proj", "L_attn_core", "L_dense_ffn", "L_loss_head",
            "L_exit_gate", "L_optimizer", "L_embed"} <= layers
    assert "L_exit_gate" in hlo_cost.LAYER_SCOPES
    # (the CPU's lowering of a rematerialised layer carries no
    # `rematted_computation` label; the TPU's does)
    for layer in ("L_attn_proj", "L_dense_ffn", "L_exit_gate"):
        assert {s.pass_ for s in top if s.layer == layer} >= {"fwd", "bwd"}
    assert not layers & {"L_moe_gate", "L_moe_experts", "L_ssm_core",
                         "L_gdn_core", "L_kda_core"}
    # the flash kernels are scopes of their own; the layers' remat keeps
    # the forward kernel's o and lse (FLASH_RESIDUALS), so it stands in
    # the step once a layer application, while the projections around it
    # run again
    flash = {k: {s.pass_ for s in top if s.layer == k}
             for k in hlo_cost.KERNEL_SCOPES}
    assert flash == ({"flash_mla_fwd": {"fwd"}, "flash_mla_bwd_dq": {"bwd"},
                      "flash_mla_bwd_dkv": {"bwd"}} if use_flash
                     else dict.fromkeys(hlo_cost.KERNEL_SCOPES, set()))
    if use_flash:
        assert {s.pass_ for s in top if s.layer == "L_attn_proj"} == {
            "fwd", "remat", "bwd"}
    # the loop is unrolled: every pass's instructions carry its scope, and
    # no `while` holds a whole pass (the per-token stages' and the head's
    # loops are inside a layer's scope, each one event of that layer)
    for t in range(1, 5):
        assert re.search(rf"op_name=\"[^\"]*ut_{t}/", text), t
    for line in text.splitlines():
        if " while(" in line and "op_name=" in line:
            assert hlo_cost._SCOPE_RE.search(line), line[:200]
    covered = sum(s.layer is not None for s in top) / len(top)
    assert covered >= 0.9, f"{covered:.3f} of {len(top)} top-level instructions"


def test_keeping_the_flash_results_changes_no_bit_of_loss_or_gradient(
        monkeypatch):
    """The layers' remat with `save_only_these_names(*FLASH_RESIDUALS)`
    against the same model under a plain `nn.remat(..., prevent_cse=True)`:
    one forward kernel a layer application in the gradient (two layers,
    four passes) where the plain one holds two, the loss and every gradient
    leaf bit for bit."""
    cfg = tiny(dtype="float32", remat=True, use_flash=True)
    _, _, tree = seeded(cfg)
    model, b = Ouro(cfg), batch()
    uses = cfg.total_ut_steps * cfg.num_hidden_layers

    n_kept, n_plain = keep_against_plain_remat(
        monkeypatch, lambda: jax.value_and_grad(lambda p: ouro_loss_fn(
            model, p, b, jax.random.key(0), None, True)[0]),
        tree, ("flash_mla_fwd", "flash_mla_bwd_dq", "flash_mla_bwd_dkv"))
    assert n_kept == (uses, uses, uses)
    assert n_plain == (2 * uses, uses, uses)


def test_count_file_against_the_shapes_and_a_cost_analysis():
    """`benchmarks/kernels/ouro_model.py`: the weights a token multiplies
    with in one use are the parameter tree's matrices, and 6 N + the
    attention's products a token is what XLA counts for a forward and
    backward pass without remat, within the elementwise work it leaves
    out."""
    cfg = tiny(dtype="float32", remat=False)
    sz = adapter.sizes_of(cfg)
    shapes = shapes_of(cfg)
    p = ouro_model.stage_params(sz)
    layer = shapes["layer_0"]
    assert p["attn"] == sum(count(layer[k]) for k in (
        "q_proj", "k_proj", "v_proj", "o_proj"))
    assert p["ffn"] == sum(count(layer[k]) for k in (
        "gate_proj", "up_proj", "down_proj"))
    assert p["head"] == count(shapes["lm_head"])
    assert p["gate"] == count(shapes["exit_gate_kernel"])
    per_token = ouro_model.train_flops_per_token(sz, S)
    uses = sz.ut_steps * sz.layers
    assert per_token == 6.0 * (
        uses * (p["attn"] + p["ffn"]) + sz.ut_steps * (p["head"] + p["gate"])
        + uses * sz.heads * 2 * sz.head_dim * S / 2)
    # four times the work of one pass
    one = dataclasses.replace(sz, ut_steps=1)
    assert per_token == 4 * ouro_model.train_flops_per_token(one, S)
    # the trainer's gauge counts the same operations
    assert looped_flops_per_token(cfg, S) == per_token
    _, _, tree = seeded(cfg)
    b = batch()
    model = Ouro(cfg)
    step = jax.jit(jax.grad(lambda p: ouro_loss_fn(
        model, p, b, jax.random.key(0), None, True)[0]))
    cost = step.lower(tree).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    # dense causal attention computes the whole S x S square, the count
    # file its causal half: add the other half before comparing
    square = 6.0 * uses * sz.heads * 2 * sz.head_dim * S / 2
    counted = (per_token + square) * B * S
    assert 0.9 * counted < cost["flops"] < 1.35 * counted, (
        cost["flops"], counted)


def test_cli_train_runs_the_family_and_its_mfu_counts_every_pass(
        monkeypatch, tmp_path):
    """`cli train ouro_2p6b` at a tiny size: `build_char_lm_run`, `Trainer`,
    the family's counters in the logged row, and an `mfu` whose operations
    a token are the looped count (a quarter of it would be one pass)."""
    import json

    from solvingpapers_tpu import cli
    from solvingpapers_tpu.configs import registry
    from solvingpapers_tpu.metrics import mfu

    published = registry._REGISTRY["ouro_2p6b"]

    def small():
        cfg = published()
        return dataclasses.replace(
            cfg, model=tiny(), data={"kind": "char", "path": None,
                                     "block_size": 64},
            train=dataclasses.replace(
                cfg.train, steps=4, batch_size=8, log_every=2, eval_every=0,
                ckpt_every=0, tokens_per_step=8 * 64))  # eight CPU devices

    monkeypatch.setitem(registry._REGISTRY, "ouro_2p6b", small)
    monkeypatch.setattr(mfu, "chip_peak_flops", lambda device=None: 197e12)
    out = tmp_path / "rows.jsonl"
    assert cli.main(["train", "--config", "ouro_2p6b", "--steps", "4",
                     "--jsonl", str(out)]) == 0
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    last = [r for r in rows if "train_loss" in r][-1]
    assert {"train_ce_ut1", "train_ce_ut4", "train_exit_entropy",
            "train_exit_mean_step", "mfu"} <= set(last)
    # over the eight CPU devices' peak; the char corpus sets the vocabulary
    # (2 to 97 ids), and the gauge follows the model that was built
    flops = 8 * last["mfu"] * 197e12 / last["tokens_per_sec"]
    assert (looped_flops_per_token(tiny(vocab_size=2), 64) <= flops
            <= looped_flops_per_token(tiny(), 64))
    assert flops > 2 * looped_flops_per_token(tiny(total_ut_steps=1), 64)


def test_cli_serve_refuses_the_family_and_list_names_it(capsys):
    from solvingpapers_tpu import cli

    rc = cli.main(["serve", "--config", "ouro_2p6b", "--port", "0"])
    assert rc == 2
    assert "R-M15" in capsys.readouterr().err
    assert cli.main(["list"]) == 0
    assert "ouro_2p6b" in capsys.readouterr().out
