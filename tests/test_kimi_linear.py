"""The Kimi-Linear family at a tiny size on the CPU, seeded weights:

  * the layer pattern (KDA three to one with latent attention, read from
    the published lists; one dense layer, then the MoE), what the config
    refuses;
  * the model's loss and gradients against the plain reference
    (`benchmarks/reference/kimi_linear_ref.py`, token-by-token KDA, every
    held expert on every token) through the benchmark's adapter, in
    float32, where the two are the same function;
  * the first three `Trainer.fit` steps in bfloat16 against the reference's
    `follow_training`, within limits that the int8 control fails;
  * the share test: what each expert-parallel rank computes of an MoE
    layer, the shared expert counted once, adds up to the uncut layer;
  * the sigmoid router's pair weights; latent attention through the flash
    kernels (keys wider than values) against the dense product;
  * the compiled train step names the new layers; `cli serve` refuses the
    family with a plain error.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import keep_against_plain_remat, kernel_passes

from benchmarks.adapters import kimi_linear as adapter
from benchmarks.drivers.train_job import Rows
from benchmarks.reference import kimi_linear_ref as ref
from solvingpapers_tpu import ops
from solvingpapers_tpu.configs import get_config
from solvingpapers_tpu.configs.factory import (
    build_model, init_fn_for, loss_fn_for,
)
from solvingpapers_tpu.metrics import hlo_cost
from solvingpapers_tpu.models.kimi_linear import (
    KimiLinear, KimiLinearConfig, LatentAttention, held_moe,
)
from solvingpapers_tpu.ops import kda
from solvingpapers_tpu.sharding import MeshConfig, create_mesh
from solvingpapers_tpu.train import Trainer
from solvingpapers_tpu.train.engine import TrainConfig
from solvingpapers_tpu.train.objectives import chunked_head_loss_fn
from solvingpapers_tpu.train.optim import OptimizerConfig

pytestmark = pytest.mark.fast

# published layers 1-5: KDA + dense, KDA + MoE, KDA + MoE, MLA + MoE, KDA + MoE
TINY = dict(
    vocab_size=97, block_size=64, hidden_size=32, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    linear_num_heads=4, linear_head_dim=8, intermediate_size=48,
    num_experts=4, router_experts=16, first_expert=4,
    num_experts_per_token=3, moe_intermediate_size=24, use_flash=False,
    capacity_factor=2.0)
B, S = 2, 48


def tiny(**over):
    return KimiLinearConfig(**{**TINY, **over})


def batch(seed=1):
    x = jax.random.randint(jax.random.key(seed), (B, S + 1), 0, 97)
    return {"x": x[:, :-1], "y": x[:, 1:]}


def seeded(cfg, seed=5, init_std=0.2):
    """(reference sizes, reference weights, the same as the program's
    tree). A wide init, so that at this width every layer matters."""
    sz = dataclasses.replace(adapter.sizes_of(cfg), init_std=init_std)
    w = ref.make_weights(seed, sz)
    shapes = jax.eval_shape(
        lambda: KimiLinear(cfg).init(jax.random.key(0), batch()["x"]))
    return sz, w, adapter.to_program_tree(w, shapes["params"])


def test_layer_pattern_is_read_from_the_published_lists():
    cfg = tiny(num_hidden_layers=9)
    assert [cfg.is_attention_layer(i) for i in range(9)] == [
        False, False, False, True] * 2 + [False]
    assert [cfg.is_dense_layer(i) for i in range(3)] == [True, False, False]
    params = jax.eval_shape(
        lambda: KimiLinear(cfg).init(jax.random.key(0), batch()["x"]))["params"]
    for i in range(9):
        kind = "attn" if i % 4 == 3 else "kda"
        assert set(params[f"layer_{i}"]["mixer"]) == {"input_norm", kind}
        ffn = set(params[f"layer_{i}"]["ffn"])
        assert ffn == ({"post_norm", "mlp_gate", "mlp_up", "mlp_down"}
                       if i == 0 else {"post_norm", "moe"})
    moe = params["layer_1"]["ffn"]["moe"]
    assert set(moe) == {"gate", "select_bias", "w1", "w2", "w3",
                        "shared_expert"}  # no gate on the shared expert
    assert moe["select_bias"].shape == (16,)
    # keys 8 + 4 wide, values 8: the up-projection gives [k_n | v] a head
    attn = params["layer_3"]["mixer"]["attn"]
    assert attn["q_proj"].shape == (32, 4 * 12)
    assert attn["kv_a_proj"].shape == (32, 16 + 4)
    assert attn["kv_b_proj"].shape == (16, 4 * 16)
    # an untied head beside the embedding
    assert params["lm_head"]["kernel"].shape == (32, 97)
    assert params["tok_emb"]["embedding"].shape == (97, 32)
    # the published pattern: 27 layers, 7 of them attention, the last too
    full = KimiLinearConfig()
    assert sum(full.is_attention_layer(i) for i in range(27)) == 7
    assert full.is_attention_layer(26) and not full.is_attention_layer(24)
    assert full.qk_nope_head_dim + full.qk_rope_head_dim == 192


@pytest.mark.parametrize("field, value", [
    ("num_expert_group", 8), ("topk_group", 4), ("q_lora_rank", 1536),
    ("mla_use_nope", False), ("moe_router_activation_func", "softmax")])
def test_config_refuses_what_has_no_path_here(field, value):
    with pytest.raises(ValueError, match=field):
        tiny(**{field: value})


def test_config_refuses_a_layer_in_neither_list():
    with pytest.raises(ValueError, match="layer 28"):
        KimiLinearConfig(num_hidden_layers=28)
    with pytest.raises(ValueError, match="not among the router"):
        KimiLinearConfig(num_experts=8, first_expert=250)


@pytest.mark.parametrize("capacity_factor", [2.0, 0.25])
def test_loss_and_gradients_match_the_reference_float32(capacity_factor):
    cfg = tiny(dtype="float32", capacity_factor=capacity_factor)
    sz, w, tree = seeded(cfg)
    model, b = KimiLinear(cfg), batch()

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
    got = adapter.leaf_norms(jax.tree.map(
        lambda a, r: a - r, g_model, adapter.to_program_tree(g_ref, tree)))
    ref_norms = {k: float(jnp.linalg.norm(v)) for k, v in g_ref.items()}
    scale = float(np.median(list(ref_norms.values())))
    for name, gap in got.items():
        assert gap <= 2e-3 * max(ref_norms[name], scale), (name, gap)
    # every weight but the selection bias takes a gradient
    assert {k for k, v in ref_norms.items() if v == 0.0} == {
        f"l{i}.bias" for i in range(1, 5)}


def test_stages_block_by_block_equal_the_whole_sequence(monkeypatch):
    """KDA's per-token stages and the dense layer run in rematerialised
    blocks of `kda.SEGMENT` tokens (and the rule in chunks of `kda.CHUNK`,
    three here, one before): the same function as in one piece, values and
    gradients."""
    b = batch()
    cfg = tiny(dtype="float32")
    sz, w, tree = seeded(cfg)

    def loss_and_grads():
        fn = lambda p: chunked_head_loss_fn(  # noqa: E731
            KimiLinear(cfg), p, b, jax.random.key(0), None, True)[0]
        return jax.jit(jax.value_and_grad(fn))(tree)

    want, g_want = loss_and_grads()  # S = 48 under SEGMENT: one piece
    monkeypatch.setattr(kda, "CHUNK", 16)
    monkeypatch.setattr(kda, "SUB", 4)
    monkeypatch.setattr(kda, "SEGMENT", 16)
    got, g_got = loss_and_grads()
    assert float(got) == pytest.approx(float(want), abs=1e-5)
    for (path, a), c in zip(jax.tree_util.tree_flatten_with_path(g_got)[0],
                            jax.tree.leaves(g_want)):
        np.testing.assert_allclose(
            a, c, atol=1e-3 * max(float(jnp.max(jnp.abs(c))), 1e-3),
            err_msg=str(path))


# what the benchmark's `correct` compares, at this size: the program's
# bfloat16 stays inside, the reference computed in int8 does not
LIMITS = {"loss_gap": 5e-3, "grad_norm_gap": 1e-2}


def test_first_three_fit_steps_follow_the_reference_and_int8_does_not():
    cfg = tiny(dtype="bfloat16")
    # the family's own init. At this width a near-tied top-3 that flips
    # moves the gradient's norm by more than bfloat16 does, in the program
    # and in the control alike; these weights draw none in three steps
    sz, w, tree = seeded(cfg, seed=7, init_std=0.02)
    opt = OptimizerConfig(name="adamw", max_lr=3e-3, warmup_steps=2,
                          total_steps=10, b1=0.9, b2=0.95, weight_decay=0.1,
                          grad_clip=1.0)
    train = TrainConfig(steps=3, batch_size=B, log_every=1, eval_every=0,
                        ckpt_every=0, optimizer=opt, seed=0)
    trainer = Trainer(
        KimiLinear(cfg), train, loss_fn=chunked_head_loss_fn,
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
    adam = adapter.adam_of(opt)
    want = ref.follow_training(w, host, sz, adam)
    low = ref.follow_training(w, host, sz, adam, quant="int8")

    def gaps(loss, grad_norm):
        return {"loss_gap": max(abs(a - b) for a, b in zip(loss, want["loss"])),
                "grad_norm_gap": max(abs(a - b) / b for a, b in
                                     zip(grad_norm, want["grad_norm"]))}

    sound = gaps([r["train_loss"] for r in logged],
                 [r["grad_norm"] for r in logged])
    assert all(sound[k] <= LIMITS[k] for k in LIMITS), sound
    control = gaps(low["loss"], low["grad_norm"])
    assert control["grad_norm_gap"] > LIMITS["grad_norm_gap"], control
    # the weights moved as the reference's did, the selection bias by
    # AdamW's decay alone on both sides
    moved = adapter.leaf_norms(jax.tree.map(
        lambda a, b: a - b, state.params, jax.tree.map(jnp.array, tree)))
    scale = float(np.median(list(want["delta"].values())))
    worst = max(abs(moved[k] - v) / max(v, scale)
                for k, v in want["delta"].items())
    assert worst <= 0.05, worst


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """Four ranks hold four of sixteen experts each. Each routes over all
    sixteen and computes its own experts' part plus the shared expert;
    their routed parts, and the shared expert once, are the uncut layer of
    the reference."""
    ranks, held = 4, 4
    cfg0 = tiny(dtype="float32", first_expert=0, capacity_factor=16.0)
    sz = dataclasses.replace(adapter.sizes_of(cfg0), held=16, first=0,
                             capacity_factor=None, init_std=0.3, layers=2)
    w = ref.layer_weights(ref.make_weights(3, sz), 1)
    x = jax.random.normal(jax.random.key(0), (B, S, 32))
    whole = jax.jit(lambda w, x: ref.moe(w, x, sz, None)[0])(
        w, x.reshape(B * S, 32)).reshape(B, S, 32)

    def rank_params(r, zero_experts=False):
        sl = slice(r * held, (r + 1) * held)
        w3 = w["w3"][sl]
        return {"gate": {"kernel": w["gate"]}, "select_bias": w["bias"],
                "w1": w["w1"][sl], "w2": w["w2"][sl],
                "w3": jnp.zeros_like(w3) if zero_experts else w3,
                "shared_expert": {"gate": {"kernel": w["s_gate"]},
                                  "up": {"kernel": w["s_up"]},
                                  "down": {"kernel": w["s_down"]}}}

    def rank_out(r, **kw):
        cfg = dataclasses.replace(cfg0, first_expert=r * held)
        return jax.jit(held_moe(cfg).apply)(
            {"params": rank_params(r, **kw)}, x)

    shared = rank_out(0, zero_experts=True)  # what every rank computes alike
    routed = [rank_out(r) - shared for r in range(ranks)]
    assert all(float(jnp.max(jnp.abs(part))) > 1e-3 for part in routed)
    np.testing.assert_allclose(sum(routed) + shared, whole, atol=2e-5)


def test_sigmoid_pair_weights_choose_by_the_bias_and_weigh_by_the_score():
    logits = jnp.array([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 3.0, 0.1]])
    bias = jnp.array([0.0, 0.0, 0.0, 5.0])  # steers every token to expert 3
    w, idx, scores = ops.moe.topk_sigmoid_weights(logits, bias, 2, True, 2.5)
    np.testing.assert_allclose(scores, jax.nn.sigmoid(logits), atol=1e-7)
    assert sorted(idx[0].tolist()) == [0, 3] and sorted(idx[1].tolist()) == [2, 3]
    # the weights are the scores, not score + bias, renormalised, scaled
    s0 = jax.nn.sigmoid(jnp.array([2.0, -1.0]))
    want = 2.5 * s0 / jnp.sum(s0)
    got = w[0][jnp.argsort(idx[0])]
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(jnp.sum(w, -1), 2.5, atol=1e-6)
    raw, _, _ = ops.moe.topk_sigmoid_weights(logits, bias, 2, False, 1.0)
    np.testing.assert_allclose(raw[0][jnp.argsort(idx[0])], s0, atol=1e-6)
    # the bias takes no gradient, the logits do
    g_bias = jax.grad(lambda b: jnp.sum(
        ops.moe.topk_sigmoid_weights(logits, b, 2, False, 1.0)[0]))(bias)
    np.testing.assert_array_equal(g_bias, jnp.zeros(4))
    g_logits = jax.grad(lambda x: jnp.sum(
        ops.moe.topk_sigmoid_weights(x, bias, 2, False, 1.0)[0]))(logits)
    assert float(jnp.abs(g_logits[0, 0])) > 1e-3 and float(g_logits[0, 1]) == 0.0


def test_latent_attention_through_the_flash_kernels_equals_the_dense_one():
    """Keys 12 wide, values 8: the kernels (interpreted here) take a value
    width of their own."""
    x = jax.random.normal(jax.random.key(0), (1, 32, 32))
    norm_w = jnp.ones(32)
    dense = LatentAttention(tiny(dtype="float32"))
    flash = LatentAttention(tiny(dtype="float32", use_flash=True))
    params = dense.init(jax.random.key(1), x, norm_w)
    np.testing.assert_allclose(flash.apply(params, x, norm_w),
                               dense.apply(params, x, norm_w), atol=2e-6)


def test_registry_holds_the_published_sizes_and_the_factory_builds_it():
    cfg = get_config("kimi_linear_48b_a3b")
    m = cfg.model
    assert cfg.model_family == "kimi_linear"
    assert (m.num_hidden_layers, m.hidden_size, m.vocab_size) == (
        27, 2304, 163_840)
    assert (m.num_experts, m.router_experts, m.num_experts_per_token,
            m.moe_intermediate_size, m.routed_scaling_factor) == (
        256, 256, 8, 1024, 2.446)
    assert (m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim,
            m.v_head_dim, m.q_lora_rank) == (512, 128, 64, 128, None)
    assert (m.linear_num_heads, m.linear_head_dim,
            m.short_conv_kernel_size, m.intermediate_size) == (32, 128, 4, 9216)
    assert m.full_attn_layers == (4, 8, 12, 16, 20, 24, 27)
    small = dataclasses.replace(cfg, model=tiny())
    assert isinstance(build_model(small), KimiLinear)
    assert loss_fn_for(small) is chunked_head_loss_fn
    assert init_fn_for(small) is None


@pytest.mark.parametrize("use_flash", [False, True],
                         ids=["dense_attention", "flash"])
def test_train_step_names_the_new_layers(monkeypatch, use_flash):
    monkeypatch.setattr(kda, "CHUNK", 16)  # S = 48: three chunks
    monkeypatch.setattr(kda, "SUB", 4)
    monkeypatch.setattr(kda, "SEGMENT", 16)
    cfg = tiny(dtype="float32", remat=True, use_flash=use_flash)
    trainer = Trainer(
        KimiLinear(cfg), TrainConfig(steps=2, batch_size=B, log_every=1),
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
    assert {"L_kda_proj", "L_kda_conv", "L_kda_core", "L_dense_ffn",
            "L_attn_proj", "L_attn_core", "L_moe_gate", "L_moe_dispatch",
            "L_moe_experts", "L_moe_combine", "L_moe_shared", "L_moe_stats",
            "L_loss_head", "L_optimizer", "L_embed"} <= layers
    for layer in ("L_kda_proj", "L_kda_conv", "L_kda_core", "L_dense_ffn"):
        assert {s.pass_ for s in top if s.layer == layer} >= {"bwd", "remat"}
    assert not layers & {"L_gdn_proj", "L_gdn_conv", "L_gdn_core"}
    # the rule's kernels stay the rule's own scope's; the layers' remat
    # keeps the forward kernel's o and entering states (DELTA_RESIDUALS),
    # so it stands in the step alone, the backward kernel in the backward
    assert kernel_passes(text, scopes, "kda_(?:fwd|bwd)", "L_kda_core") == {
        "kda_fwd": {"fwd"}, "kda_bwd": {"bwd"}}
    assert "gated_delta_fwd" not in text and "gated_delta_bwd" not in text
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
    *DELTA_RESIDUALS)` against the same model under a plain `nn.remat(...,
    prevent_cse=True)`: one forward kernel a layer in the gradient, the
    flash one and the rule's, where the plain one holds two, the loss and
    every gradient leaf bit for bit."""
    monkeypatch.setattr(kda, "CHUNK", 16)
    monkeypatch.setattr(kda, "SUB", 4)
    monkeypatch.setattr(kda, "SEGMENT", 16)
    # published layers 1-4: three KDA layers, the fourth the attention layer
    cfg = tiny(dtype="float32", remat=True, use_flash=True,
               num_hidden_layers=4)
    _, _, tree = seeded(cfg)
    model, b = KimiLinear(cfg), batch()

    n_kept, n_plain = keep_against_plain_remat(
        monkeypatch, lambda: jax.value_and_grad(lambda p: chunked_head_loss_fn(
            model, p, b, jax.random.key(0), None, True)[0]),
        tree, ("flash_mla_fwd", "kda_fwd", "kda_bwd"))
    assert (n_kept, n_plain) == ((1, 3, 3), (2, 6, 3))


def test_cli_serve_refuses_the_family(capsys):
    from solvingpapers_tpu import cli

    rc = cli.main(["serve", "--config", "kimi_linear_48b_a3b", "--port", "0"])
    assert rc == 2
    assert "recurrent state" in capsys.readouterr().err


@pytest.mark.parametrize("rows", [64, 50])
def test_head_and_loss_in_chunks_equal_the_whole_logits(rows):
    """`ops.head_cross_entropy`: the untied head and the cross-entropy a
    chunk of rows at a time (16 here; 50 rows run as one chunk) against
    `ops.cross_entropy` of the whole logits, values and both gradients."""
    h = jax.random.normal(jax.random.key(0), (2, rows // 2, 24))
    k = 0.3 * jax.random.normal(jax.random.key(1), (24, 97))
    y = jax.random.randint(jax.random.key(2), (2, rows // 2), 0, 97)
    whole = lambda h, k: ops.cross_entropy(h @ k, y)  # noqa: E731
    chunks = lambda h, k: ops.head_cross_entropy(  # noqa: E731
        h, k, y, chunk_size=16)
    want, g_want = jax.value_and_grad(whole, (0, 1))(h, k)
    got, g_got = jax.jit(jax.value_and_grad(chunks, (0, 1)))(h, k)
    assert float(got) == pytest.approx(float(want), abs=1e-6)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=1e-6)
