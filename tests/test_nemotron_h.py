"""The Nemotron-H family at a tiny size on the CPU, seeded weights:

  * the layer pattern (one sub-block a layer, its kind read from the
    published string), the published parameter counts, what the config
    refuses;
  * the model's loss and gradients against the plain reference
    (`benchmarks/reference/nemotron_h_ref.py`, token-by-token recurrence,
    every held expert on every token) through the benchmark's adapter, in
    float32, where the two are the same function;
  * the first three `Trainer.fit` steps in bfloat16 against the reference's
    `follow_training`;
  * the share test: what each expert-parallel rank computes of an MoE
    layer, the shared expert counted once, adds up to the uncut layer;
  * the convolution's bias enters before the SiLU; attention carries no
    position and runs through the flash kernels at 32-on-2's head mapping;
  * the compiled train step names the new layers; `cli serve` refuses the
    family with a plain error; `cli list` names the registry entry.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import keep_against_plain_remat, kernel_passes

from benchmarks.adapters import nemotron_h as adapter
from benchmarks.drivers.train_job import Rows
from benchmarks.reference import nemotron_h_ref as ref
from solvingpapers_tpu.configs import get_config
from solvingpapers_tpu.configs.factory import (
    build_model, init_fn_for, loss_fn_for,
)
from solvingpapers_tpu.metrics import hlo_cost
from solvingpapers_tpu.models.mixers import Mamba2Mixer, NoPEAttention
from solvingpapers_tpu.models.nemotron_h import (
    NemotronH, NemotronHConfig, held_moe,
)
from solvingpapers_tpu.ops import ssd
from solvingpapers_tpu.sharding import MeshConfig, create_mesh
from solvingpapers_tpu.train import Trainer
from solvingpapers_tpu.train.engine import TrainConfig
from solvingpapers_tpu.train.objectives import chunked_head_loss_fn
from solvingpapers_tpu.train.optim import OptimizerConfig

pytestmark = pytest.mark.fast

# published layers 0-8: M E M E M * E M E
TINY = dict(
    vocab_size=97, block_size=64, hidden_size=32, num_hidden_layers=9,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    mamba_num_heads=8, mamba_head_dim=4, n_groups=2, ssm_state_size=8,
    chunk_size=8, n_routed_experts=4, router_experts=16, first_expert=4,
    num_experts_per_tok=3, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, use_flash=False,
    capacity_factor=2.0)
B, S = 2, 48


@pytest.fixture(autouse=True)
def short_segments(monkeypatch):
    """Blocks of 16 tokens: the per-token stages and the rule's segments
    run three blocks of two chunks each."""
    monkeypatch.setattr(ssd, "SEGMENT", 16)


def tiny(**over):
    return NemotronHConfig(**{**TINY, **over})


def batch(seed=1):
    x = jax.random.randint(jax.random.key(seed), (B, S + 1), 0, 97)
    return {"x": x[:, :-1], "y": x[:, 1:]}


def shapes_of(cfg):
    return jax.eval_shape(lambda: NemotronH(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]


def count(tree):
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))


def seeded(cfg, seed=5, init_std=0.2):
    """(reference sizes, reference weights, the same as the program's
    tree). A wide init, so that at this width every layer matters."""
    sz = dataclasses.replace(adapter.sizes_of(cfg), init_std=init_std)
    w = ref.make_weights(seed, sz)
    return sz, w, adapter.to_program_tree(w, shapes_of(cfg))


def test_published_pattern_and_parameter_counts(monkeypatch):
    monkeypatch.setattr(ssd, "SEGMENT", 2048)  # the published chunk is 128
    full = NemotronHConfig()
    pattern = full.hybrid_override_pattern
    assert len(pattern) == 52 == full.num_hidden_layers
    assert [pattern.count(k) for k in "ME*"] == [23, 23, 6]
    cut = NemotronHConfig(num_hidden_layers=9, n_routed_experts=8,
                          vocab_size=16_384)
    assert cut.layer_pattern == "MEMEM*EME"
    assert [cut.layer_pattern.count(k) for k in "ME*"] == [4, 4, 1]
    assert cut.hybrid_override_pattern == pattern  # stays whole
    assert (full.d_inner, full.conv_dim) == (4096, 6144)
    whole, here = shapes_of(full), shapes_of(cut)
    assert count(whole) == 31_577_940_288
    assert count(here) == 666_963_456
    # a Mamba-2 layer, the attention layer, an MoE layer outside its
    # routed experts, one expert
    assert count(here["layer_0"]) == 38_744_896
    assert count(here["layer_5"]) == 23_399_040
    moe = here["layer_1"]
    assert count(moe) - count(moe["moe"]["w1"]) - count(
        moe["moe"]["w3"]) == 20_302_592
    assert (count(moe["moe"]["w1"]) + count(moe["moe"]["w3"])
            == 8 * 9_977_856)
    assert here["layer_0"]["mixer"]["in_proj"].shape == (2688, 10_304)


def test_one_sub_block_a_layer_of_the_patterns_kind():
    params = shapes_of(tiny())
    for i, kind in enumerate("MEMEM*EME"):
        name = {"M": "mixer", "E": "moe", "*": "attn"}[kind]
        assert set(params[f"layer_{i}"]) == {"norm", name}, i
    assert set(params["layer_0"]["mixer"]) == {
        "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
        "norm_weight", "out_proj"}
    # [z (32) | xBC (32 + 2*2*8) | dt (8)]
    assert params["layer_0"]["mixer"]["in_proj"].shape == (32, 32 + 64 + 8)
    assert params["layer_0"]["mixer"]["conv_b"].shape == (64,)
    moe = params["layer_1"]["moe"]
    assert set(moe) == {"gate", "select_bias", "w1", "w3", "shared_expert"}
    assert set(moe["shared_expert"]) == {"fc", "proj"}  # two matrices
    assert moe["w1"].shape == (4, 32, 24) and moe["w3"].shape == (4, 24, 32)
    attn = params["layer_5"]["attn"]
    assert attn["q_proj"].shape == (32, 32) and attn["k_proj"].shape == (
        32, 16)
    assert params["lm_head"]["kernel"].shape == (32, 97)


@pytest.mark.parametrize("field, value", [
    ("n_group", 8), ("topk_group", 4), ("use_conv_bias", False),
    ("mlp_hidden_act", "silu"), ("n_shared_experts", 2),
    ("hybrid_override_pattern", "ME-EM*EME")])
def test_config_refuses_what_has_no_path_here(field, value):
    with pytest.raises(ValueError, match=field):
        tiny(**{field: value})


def test_config_refuses_a_pattern_too_short_and_experts_out_of_range():
    with pytest.raises(ValueError, match="names 52 layers"):
        NemotronHConfig(num_hidden_layers=53)
    with pytest.raises(ValueError, match="not among the router"):
        NemotronHConfig(n_routed_experts=8, first_expert=121)


@pytest.mark.parametrize("capacity_factor", [8.0, 0.25])
def test_loss_and_gradients_match_the_reference_float32(capacity_factor):
    cfg = tiny(dtype="float32", capacity_factor=capacity_factor)
    sz, w, tree = seeded(cfg)
    model, b = NemotronH(cfg), batch()

    @jax.jit
    def program(p):
        def loss_fn(p):
            loss, aux, _ = chunked_head_loss_fn(model, p, b, jax.random.key(0),
                                               None, True)
            return loss, aux
        return jax.value_and_grad(loss_fn, has_aux=True)(p)

    reference = jax.jit(jax.value_and_grad(
        lambda w: ref.loss_fn(w, b["x"], b["y"], sz), has_aux=True))
    (loss, aux), g_model = program(tree)
    (want, (_, dropped)), g_ref = reference(w)
    assert float(loss) == pytest.approx(float(want), abs=2e-5)
    assert float(aux["moe_drop_fraction"]) == pytest.approx(float(dropped),
                                                            abs=1e-6)
    assert (float(dropped) > 0.1) == (capacity_factor < 1.0)
    # 4 of 16 experts held, 3 of 16 chosen a token: about a quarter here
    assert 0.1 < float(aux["moe_held_pair_fraction"]) < 0.4
    assert float(jnp.linalg.norm(
        adapter.to_program_tree(g_ref, tree)["lm_head"]["kernel"])) > 0
    got = adapter.leaf_norms(jax.tree.map(
        lambda a, r: a - r, g_model, adapter.to_program_tree(g_ref, tree)))
    ref_norms = {k: float(jnp.linalg.norm(v)) for k, v in g_ref.items()}
    scale = float(np.median(list(ref_norms.values())))
    for name, gap in got.items():
        assert gap <= 2e-3 * max(ref_norms[name], scale), (name, gap)
    # every weight but the selection bias takes a gradient
    assert {k for k, v in ref_norms.items() if v == 0.0} == {
        f"l{i}.bias" for i in (1, 3, 6, 8)}


def test_stages_block_by_block_equal_the_whole_sequence(monkeypatch):
    b = batch()
    cfg = tiny(dtype="float32")
    _, _, tree = seeded(cfg)

    def loss_and_grads():
        fn = lambda p: chunked_head_loss_fn(  # noqa: E731
            NemotronH(cfg), p, b, jax.random.key(0), None, True)[0]
        return jax.jit(jax.value_and_grad(fn))(tree)

    got, g_got = loss_and_grads()  # blocks of 16
    monkeypatch.setattr(ssd, "SEGMENT", 64)  # S = 48: one piece
    want, g_want = loss_and_grads()
    assert float(got) == pytest.approx(float(want), abs=1e-5)
    for (path, a), c in zip(jax.tree_util.tree_flatten_with_path(g_got)[0],
                            jax.tree.leaves(g_want)):
        np.testing.assert_allclose(
            a, c, atol=1e-3 * max(float(jnp.max(jnp.abs(c))), 1e-3),
            err_msg=str(path))


# what the benchmark's `correct` compares, at this size
LIMITS = {"loss_gap": 5e-3, "grad_norm_gap": 1e-2}


def test_first_three_fit_steps_follow_the_reference():
    cfg = tiny(dtype="bfloat16")
    sz, w, tree = seeded(cfg, seed=7, init_std=0.02)
    opt = OptimizerConfig(name="adamw", max_lr=3e-3, warmup_steps=2,
                          total_steps=10, b1=0.9, b2=0.95, weight_decay=0.1,
                          grad_clip=1.0)
    train = TrainConfig(steps=3, batch_size=B, log_every=1, eval_every=0,
                        ckpt_every=0, optimizer=opt, seed=0)
    trainer = Trainer(
        NemotronH(cfg), train, loss_fn=chunked_head_loss_fn,
        mesh=create_mesh(MeshConfig(), devices=jax.devices()[:1]))
    batches = [batch(seed) for seed in (1, 2, 3)]
    state = trainer.init_state(batches[0])
    # placed as the step keeps them: the step then compiles once, not
    # again at step 2 for another sharding of the same state
    state = state.replace(params=jax.device_put(
        jax.tree.map(jnp.array, tree), trainer._state_shardings.params))
    rows = Rows()
    state = trainer.fit(iter(batches), None, writer=rows, state=state)
    logged = [r for r in rows.rows if "train_loss" in r]
    assert [r["step"] for r in logged] == [1, 2, 3]
    assert all("train_moe_drop_fraction" in r
               and "train_moe_held_pair_fraction" in r for r in logged)
    host = [(np.asarray(b["x"]), np.asarray(b["y"])) for b in batches]
    want = ref.follow_training(w, host, sz, adapter.adam_of(opt))
    sound = {
        "loss_gap": max(abs(r["train_loss"] - b)
                        for r, b in zip(logged, want["loss"])),
        "grad_norm_gap": max(abs(r["grad_norm"] - b) / b
                             for r, b in zip(logged, want["grad_norm"]))}
    assert all(sound[k] <= LIMITS[k] for k in LIMITS), sound
    # the weights moved as the reference's did, the selection bias by
    # AdamW's decay alone on both sides
    moved = adapter.leaf_norms(jax.tree.map(
        lambda a, b: a - b, state.params, jax.tree.map(jnp.array, tree)))
    scale = float(np.median(list(want["delta"].values())))
    worst = max(abs(moved[k] - v) / max(v, scale)
                for k, v in want["delta"].items())
    assert worst <= 0.05, worst


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """Sixteen ranks hold one of sixteen experts each. Each routes over all
    sixteen and computes its own expert's part plus the shared expert;
    their routed parts, and the shared expert once, are the uncut layer of
    the reference."""
    ranks, held = 16, 1
    cfg0 = tiny(dtype="float32", n_routed_experts=held, first_expert=0,
                capacity_factor=16.0)
    sz = dataclasses.replace(adapter.sizes_of(cfg0), held=16, first=0,
                             capacity_factor=None, init_std=0.3)
    w = ref.layer_weights(ref.make_weights(3, sz), 1)
    x = jax.random.normal(jax.random.key(0), (B, S, 32))
    whole = jax.jit(lambda w, x: ref.moe(w, x, sz, None)[0])(
        w, x.reshape(B * S, 32)).reshape(B, S, 32)

    def rank_params(r, zero_experts=False):
        sl = slice(r * held, (r + 1) * held)
        w3 = w["w_down"][sl]
        return {"gate": {"kernel": w["gate"]}, "select_bias": w["bias"],
                "w1": w["w_up"][sl],
                "w3": jnp.zeros_like(w3) if zero_experts else w3,
                "shared_expert": {"fc": {"kernel": w["s_up"]},
                                  "proj": {"kernel": w["s_down"]}}}

    def rank_out(r, **kw):
        cfg = dataclasses.replace(cfg0, first_expert=r * held)
        return jax.jit(held_moe(cfg).apply)(
            {"params": rank_params(r, **kw)}, x)

    shared = rank_out(0, zero_experts=True)  # what every rank computes alike
    routed = [rank_out(r) - shared for r in range(ranks)]
    assert sum(float(jnp.max(jnp.abs(part))) > 1e-3 for part in routed) >= 8
    np.testing.assert_allclose(sum(routed) + shared, whole, atol=5e-5)


def test_the_convolutions_bias_enters_before_the_silu():
    """With the convolution's weights at zero the mixer sees SiLU(bias) in
    every channel of [x | B | C]: a bias added after the SiLU would give
    bias itself, and none at all SiLU(0) = 0 (and a zero output)."""
    cfg = tiny(dtype="float32")
    x = jax.random.normal(jax.random.key(0), (1, 16, 32))
    norm_w = jnp.ones(32)
    mixer = Mamba2Mixer(cfg)
    params = mixer.init(jax.random.key(1), x, norm_w)["params"]
    bias = jnp.linspace(-2.0, 2.0, 64)
    params = {**params, "conv_w": jnp.zeros_like(params["conv_w"]),
              "conv_b": bias}
    got = mixer.apply({"params": params}, x, norm_w)
    # what the reference's mixer gives for the same weights
    sz = adapter.sizes_of(cfg)
    lw = {"in_proj": params["in_proj"], "conv": params["conv_w"],
          "conv_b": bias, "dt_bias": params["dt_bias"],
          "A_log": params["A_log"], "D": params["D"],
          "ssm_norm": params["norm_weight"], "ssm_out": params["out_proj"]}
    want = ref.mamba2(lw, ref.norm(x, norm_w, 1e-5), sz, None)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.max(jnp.abs(got))) > 1e-3
    after = ref.mamba2({**lw, "conv_b": jnp.zeros(64)},
                       ref.norm(x, norm_w, 1e-5), sz, None)
    assert float(jnp.max(jnp.abs(after))) < 1e-6  # SiLU(0): nothing flows


@pytest.mark.parametrize("use_flash", [False, True])
def test_no_position_enters_attention(use_flash):
    """Under the causal mask the last token attends to every earlier one;
    with no positional encoding its output does not change when the earlier
    tokens change places. Through the flash kernels (interpreted here) each
    key-value head serves its own two query heads."""
    cfg = tiny(dtype="float32", use_flash=use_flash)
    x = jax.random.normal(jax.random.key(0), (1, 32, 32))
    norm_w = jnp.ones(32)
    attn = NoPEAttention(cfg)
    params = attn.init(jax.random.key(1), x, norm_w)
    out = attn.apply(params, x, norm_w)
    order = jnp.concatenate([jax.random.permutation(jax.random.key(2), 31),
                             jnp.array([31])])
    moved = attn.apply(params, x[:, order], norm_w)
    np.testing.assert_allclose(moved[:, -1], out[:, -1], atol=2e-6)
    assert float(jnp.max(jnp.abs(moved[:, 5] - out[:, 5]))) > 1e-3
    dense = NoPEAttention(tiny(dtype="float32")).apply(params, x, norm_w)
    np.testing.assert_allclose(out, dense, atol=2e-6)


def test_registry_holds_the_published_sizes_and_the_factory_builds_it():
    cfg = get_config("nemotron3_nano_30b_a3b")
    m = cfg.model
    assert cfg.model_family == "nemotron_h"
    assert (m.num_hidden_layers, m.hidden_size, m.vocab_size) == (
        52, 2688, 131_072)
    assert (m.n_routed_experts, m.router_experts, m.num_experts_per_tok,
            m.moe_intermediate_size, m.moe_shared_expert_intermediate_size,
            m.routed_scaling_factor) == (128, 128, 6, 1856, 3712, 2.5)
    assert (m.mamba_num_heads, m.mamba_head_dim, m.n_groups,
            m.ssm_state_size, m.conv_kernel, m.chunk_size) == (
        64, 64, 8, 128, 4, 128)
    assert (m.num_attention_heads, m.num_key_value_heads, m.head_dim) == (
        32, 2, 128)
    assert cfg.train.optimizer.name == "adamw"
    small = dataclasses.replace(cfg, model=tiny())
    assert isinstance(build_model(small), NemotronH)
    assert loss_fn_for(small) is chunked_head_loss_fn
    assert init_fn_for(small) is None


@pytest.mark.parametrize("use_flash", [False, True],
                         ids=["dense_attention", "flash"])
def test_train_step_names_the_new_layers(use_flash):
    cfg = tiny(dtype="float32", remat=True, use_flash=use_flash)
    trainer = Trainer(
        NemotronH(cfg), TrainConfig(steps=2, batch_size=B, log_every=1),
        loss_fn=chunked_head_loss_fn,
        mesh=create_mesh(MeshConfig(), devices=jax.devices()[:1]))
    b = {k: np.asarray(v) for k, v in batch().items()}
    state = trainer.init_state(b)
    trainer._build_steps()
    with hlo_cost._persistent_cache_off():
        text = trainer._train_step.lower(state, b).compile().as_text()
    scopes = hlo_cost.device_scopes(text)
    top = [s for s in scopes.values() if s.top_level]
    layers = {s.layer for s in top}
    assert {"L_ssm_proj", "L_ssm_conv", "L_ssm_core", "L_attn_proj",
            "L_attn_core", "L_moe_gate", "L_moe_dispatch", "L_moe_experts",
            "L_moe_combine", "L_moe_shared", "L_moe_stats", "L_loss_head",
            "L_optimizer", "L_embed"} <= layers
    assert {"L_ssm_proj", "L_ssm_conv", "L_ssm_core"} <= set(
        hlo_cost.LAYER_SCOPES)
    for layer in ("L_ssm_proj", "L_ssm_conv", "L_ssm_core"):
        assert {s.pass_ for s in top if s.layer == layer} >= {"bwd", "remat"}
    assert not layers & {"L_gdn_proj", "L_gdn_core", "L_kda_proj",
                         "L_kda_core", "L_dense_ffn"}
    # the rule's kernels stay the rule's own scope's; the layers' remat
    # keeps the forward kernel's y and entering states (SSD_RESIDUALS), so
    # it stands in the step alone, the backward kernel in the backward
    assert kernel_passes(text, scopes, "ssd_(?:fwd|bwd)", "L_ssm_core") == {
        "ssd_fwd": {"fwd"}, "ssd_bwd": {"bwd"}}
    # the flash kernels are scopes of their own; the layers' remat keeps
    # the forward kernel's o and lse (FLASH_RESIDUALS), so it stands in
    # the step alone, while the projections around it run again
    flash = {k: {s.pass_ for s in top if s.layer == k}
             for k in hlo_cost.KERNEL_SCOPES}
    assert flash == ({"flash_mla_fwd": {"fwd"}, "flash_mla_bwd_dq": {"bwd"},
                      "flash_mla_bwd_dkv": {"bwd"}} if use_flash
                     else dict.fromkeys(hlo_cost.KERNEL_SCOPES, set()))
    assert {s.pass_ for s in top if s.layer == "L_attn_proj"} == {
        "fwd", "remat", "bwd"}
    covered = sum(s.layer is not None for s in top) / len(top)
    assert covered >= 0.9, f"{covered:.3f} of {len(top)} top-level instructions"


def test_keeping_the_flash_results_changes_no_bit_of_loss_or_gradient(
        monkeypatch):
    """The layers' remat with `save_only_these_names(*FLASH_RESIDUALS,
    *SSD_RESIDUALS)` against the same model under a plain `nn.remat(...,
    prevent_cse=True)`: one forward kernel a layer in the gradient, the
    flash one and the recurrence's, where the plain one holds two, the loss
    and every gradient leaf bit for bit."""
    # published layers 0-5, M E M E M *: three Mamba-2 layers, the sixth
    # the attention layer
    cfg = tiny(dtype="float32", remat=True, use_flash=True,
               num_hidden_layers=6)
    _, _, tree = seeded(cfg)
    model, b = NemotronH(cfg), batch()

    n_kept, n_plain = keep_against_plain_remat(
        monkeypatch, lambda: jax.value_and_grad(lambda p: chunked_head_loss_fn(
            model, p, b, jax.random.key(0), None, True)[0]),
        tree, ("flash_mla_fwd", "ssd_fwd", "ssd_bwd"))
    assert (n_kept, n_plain) == ((1, 3, 3), (2, 6, 3))


def test_cli_serve_refuses_the_family_and_list_names_it(capsys):
    from solvingpapers_tpu import cli

    rc = cli.main(["serve", "--config", "nemotron3_nano_30b_a3b",
                   "--port", "0"])
    assert rc == 2
    assert "recurrent state" in capsys.readouterr().err
    assert cli.main(["list"]) == 0
    assert "nemotron3_nano_30b_a3b" in capsys.readouterr().out
