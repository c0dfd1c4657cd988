"""The Qwen3-Next family at a tiny size on the CPU, seeded weights:

  * the layer pattern (Gated DeltaNet three to one with gated attention),
    rotary embedding on a quarter of a head, the zero-centred norm;
  * the model's loss and gradients against the plain reference
    (`benchmarks/reference/qwen3next_ref.py`, token-by-token DeltaNet,
    every held expert on every token) through the benchmark's adapter, in
    float32, where the two are the same function;
  * the first three `Trainer.fit` steps in bfloat16 against the reference's
    `follow_training`, within limits that the int8 control fails;
  * the share test: what each expert-parallel rank computes of an MoE
    layer, the shared expert counted once, adds up to the uncut layer;
  * the compiled train step names the new layers; `cli serve` refuses the
    family with a plain error.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.adapters import qwen3next as adapter
from benchmarks.drivers.train_job import Rows
from benchmarks.reference import qwen3next_ref as ref
from solvingpapers_tpu.configs import get_config
from solvingpapers_tpu.configs.factory import (
    build_model, init_fn_for, loss_fn_for,
)
from solvingpapers_tpu.metrics import hlo_cost
from solvingpapers_tpu.models.qwen3next import (
    Qwen3Next, Qwen3NextConfig, ZeroCenteredRMSNorm, held_moe,
)
from solvingpapers_tpu.ops import gated_delta
from solvingpapers_tpu.ops.rope import partial_rotary
from solvingpapers_tpu.sharding import MeshConfig, create_mesh
from solvingpapers_tpu.train import Trainer
from solvingpapers_tpu.train.engine import TrainConfig
from solvingpapers_tpu.train.objectives import qwen3next_loss_fn
from solvingpapers_tpu.train.optim import OptimizerConfig

pytestmark = pytest.mark.fast

TINY = dict(
    vocab_size=97, block_size=64, hidden_size=32, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, num_experts=4, router_experts=16, first_expert=4,
    num_experts_per_tok=3, moe_intermediate_size=24,
    shared_expert_intermediate_size=24, use_flash=False,
    capacity_factor=2.0, router_aux_loss_coef=0.01)
B, S = 2, 48


def tiny(**over):
    return Qwen3NextConfig(**{**TINY, **over})


def batch(seed=1):
    x = jax.random.randint(jax.random.key(seed), (B, S + 1), 0, 97)
    return {"x": x[:, :-1], "y": x[:, 1:]}


def seeded(cfg, seed=5, init_std=0.2):
    """(reference sizes, reference weights, the same as the program's
    tree). A wide init, so that at this width every layer matters."""
    sz = dataclasses.replace(adapter.sizes_of(cfg), init_std=init_std)
    w = ref.make_weights(seed, sz)
    shapes = jax.eval_shape(
        lambda: Qwen3Next(cfg).init(jax.random.key(0), batch()["x"]))
    return sz, w, adapter.to_program_tree(w, shapes["params"])


def test_layer_pattern_three_deltanet_to_one_attention():
    cfg = tiny(num_hidden_layers=8)
    assert [cfg.is_attention_layer(i) for i in range(8)] == [
        False, False, False, True] * 2
    params = jax.eval_shape(
        lambda: Qwen3Next(cfg).init(jax.random.key(0), batch()["x"]))["params"]
    for i in range(8):
        kind = "attn" if i % 4 == 3 else "gdn"
        assert set(params[f"layer_{i}"]["mixer"]) == {"input_norm", kind}
        assert set(params[f"layer_{i}"]["ffn"]) == {"post_norm", "moe"}
    # an untied head beside the embedding
    assert params["lm_head"]["kernel"].shape == (32, 97)
    assert params["tok_emb"]["embedding"].shape == (97, 32)
    # the published pattern: 48 layers, 12 of them attention
    full = Qwen3NextConfig()
    assert sum(full.is_attention_layer(i) for i in range(48)) == 12
    assert full.rotary_dim == 64 and full.head_dim == 256


def test_rotary_turns_a_quarter_of_the_head_and_leaves_the_rest():
    x = jax.random.normal(jax.random.key(0), (1, 6, 2, 256))
    y = partial_rotary(x, 64, 1e7)
    np.testing.assert_array_equal(y[..., 64:], x[..., 64:])
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)  # position 0
    assert float(jnp.max(jnp.abs(y[:, 1:, :, :64] - x[:, 1:, :, :64]))) > 0.1
    # rotate-half: feature i pairs with i + 32, angle pos * theta^(-i/32)
    pos, i = 5, 3
    ang = pos * 1e7 ** (-i / 32)
    a, b = x[0, pos, 1, i], x[0, pos, 1, i + 32]
    np.testing.assert_allclose(y[0, pos, 1, i], a * np.cos(ang) - b * np.sin(ang), atol=1e-5)
    np.testing.assert_allclose(y[0, pos, 1, i + 32], b * np.cos(ang) + a * np.sin(ang), atol=1e-5)
    # a rotation: lengths stay
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_zero_centred_norm_starts_as_the_plain_norm():
    x = 3.0 * jax.random.normal(jax.random.key(0), (4, 32))
    norm = ZeroCenteredRMSNorm(1e-6)
    params = norm.init(jax.random.key(1), x)
    np.testing.assert_array_equal(params["params"]["weight"], jnp.zeros(32))
    plain = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(norm.apply(params, x), plain, atol=1e-6)
    half = {"params": {"weight": jnp.full((32,), 0.5)}}
    np.testing.assert_allclose(norm.apply(half, x), 1.5 * plain, atol=1e-5)


@pytest.mark.parametrize("capacity_factor", [2.0, 0.25])
def test_loss_and_gradients_match_the_reference_float32(capacity_factor):
    cfg = tiny(dtype="float32", capacity_factor=capacity_factor)
    sz, w, tree = seeded(cfg)
    model, b = Qwen3Next(cfg), batch()

    @jax.jit
    def program(p):
        def loss_fn(p):
            loss, aux, _ = qwen3next_loss_fn(model, p, b, jax.random.key(0),
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


def test_stages_block_by_block_equal_the_whole_sequence(monkeypatch):
    """The DeltaNet's per-token stages and the rule's segments run in
    rematerialised blocks of `gated_delta.SEGMENT` tokens: the same function
    as in one piece, values and gradients."""
    b = batch()
    cfg = tiny(dtype="float32")
    sz, w, tree = seeded(cfg)

    def loss_and_grads():
        fn = lambda p: qwen3next_loss_fn(  # noqa: E731
            Qwen3Next(cfg), p, b, jax.random.key(0), None, True)[0]
        return jax.jit(jax.value_and_grad(fn))(tree)

    want, g_want = loss_and_grads()  # S = 48 under SEGMENT: one piece
    monkeypatch.setattr(gated_delta, "CHUNK", 16)
    monkeypatch.setattr(gated_delta, "SEGMENT", 16)
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
    sz, w, tree = seeded(cfg, init_std=0.02)  # the family's own
    opt = OptimizerConfig(name="adamw", max_lr=3e-3, warmup_steps=2,
                          total_steps=10, b1=0.9, b2=0.95, weight_decay=0.1,
                          grad_clip=1.0)
    train = TrainConfig(steps=3, batch_size=B, log_every=1, eval_every=0,
                        ckpt_every=0, optimizer=opt, seed=0)
    trainer = Trainer(
        Qwen3Next(cfg), train, loss_fn=qwen3next_loss_fn,
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
    # the weights moved as the reference's did
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
                             capacity_factor=None, init_std=0.3, layers=1)
    w = ref.layer_weights(ref.make_weights(3, sz), 0)
    x = jax.random.normal(jax.random.key(0), (B, S, 32))
    whole = jax.jit(lambda w, x: ref.moe(w, x, sz, None)[0])(
        w, x.reshape(B * S, 32)).reshape(B, S, 32)

    def rank_params(r, zero_experts=False):
        sl = slice(r * held, (r + 1) * held)
        w3 = w["w3"][sl]
        return {"gate": {"kernel": w["gate"]}, "w1": w["w1"][sl],
                "w2": w["w2"][sl],
                "w3": jnp.zeros_like(w3) if zero_experts else w3,
                "shared_expert": {"gate": {"kernel": w["s_gate"]},
                                  "up": {"kernel": w["s_up"]},
                                  "down": {"kernel": w["s_down"]}},
                "shared_gate": {"kernel": w["s_mix"]}}

    def rank_out(r, **kw):
        cfg = dataclasses.replace(cfg0, first_expert=r * held)
        return jax.jit(held_moe(cfg).apply)(
            {"params": rank_params(r, **kw)}, x)

    shared = rank_out(0, zero_experts=True)  # what every rank computes alike
    routed = [rank_out(r) - shared for r in range(ranks)]
    assert all(float(jnp.max(jnp.abs(part))) > 1e-3 for part in routed)
    np.testing.assert_allclose(sum(routed) + shared, whole, atol=2e-5)


def test_registry_holds_the_published_sizes_and_the_factory_builds_it():
    cfg = get_config("qwen3next_80b_a3b")
    m = cfg.model
    assert cfg.model_family == "qwen3next"
    assert (m.num_hidden_layers, m.hidden_size, m.vocab_size) == (
        48, 2048, 151_936)
    assert (m.num_experts, m.router_experts, m.num_experts_per_tok,
            m.moe_intermediate_size) == (512, 512, 10, 512)
    assert (m.linear_num_key_heads, m.linear_num_value_heads,
            m.linear_key_head_dim, m.linear_conv_kernel_dim) == (16, 32, 128, 4)
    small = dataclasses.replace(cfg, model=tiny())
    assert isinstance(build_model(small), Qwen3Next)
    assert loss_fn_for(small) is qwen3next_loss_fn
    assert init_fn_for(small) is None
    with pytest.raises(ValueError, match="not among the router"):
        Qwen3NextConfig(num_experts=32, first_expert=500)


def test_train_step_names_the_new_layers(monkeypatch):
    monkeypatch.setattr(gated_delta, "CHUNK", 16)  # S = 48: three chunks
    cfg = tiny(dtype="float32", remat=True)
    trainer = Trainer(
        Qwen3Next(cfg), TrainConfig(steps=2, batch_size=B, log_every=1),
        loss_fn=qwen3next_loss_fn,
        mesh=create_mesh(MeshConfig(), devices=jax.devices()[:1]))
    b = {k: np.asarray(v) for k, v in batch().items()}
    state = trainer.init_state(b)
    trainer._build_steps()
    with hlo_cost._persistent_cache_off():
        text = trainer._train_step.lower(state, b).compile().as_text()
    scopes = hlo_cost.device_scopes(text)
    top = [s for s in scopes.values() if s.top_level]
    layers = {s.layer for s in top}
    assert {"L_gdn_proj", "L_gdn_conv", "L_gdn_core", "L_attn_proj",
            "L_attn_core", "L_moe_gate", "L_moe_dispatch", "L_moe_experts",
            "L_moe_combine", "L_moe_shared", "L_moe_stats", "L_loss_head",
            "L_optimizer", "L_embed"} <= layers
    for layer in ("L_gdn_proj", "L_gdn_conv", "L_gdn_core"):
        assert {s.pass_ for s in top if s.layer == layer} >= {"bwd", "remat"}
    # the rule's kernels carry a `name=` that is no layer: their time stays
    # the rule's own scope's, the forward kernel's in the step and again
    # in the layer's remat, the backward kernel's in the backward pass
    seen = {}
    for m in re.finditer(
            r"%?([\w.\-]+) = [^\n]*op_name=\"[^\"]*(gated_delta_(?:fwd|bwd))", text):
        s = scopes[m.group(1)]
        assert s.layer == "L_gdn_core", m.group(0)
        if s.top_level:
            seen.setdefault(m.group(2), set()).add(s.pass_)
    assert seen == {"gated_delta_fwd": {"fwd", "remat"},
                    "gated_delta_bwd": {"bwd"}}
    covered = sum(s.layer is not None for s in top) / len(top)
    assert covered >= 0.9, f"{covered:.3f} of {len(top)} top-level instructions"


def test_cli_serve_refuses_the_family(capsys):
    from solvingpapers_tpu import cli

    rc = cli.main(["serve", "--config", "qwen3next_80b_a3b", "--port", "0"])
    assert rc == 2
    assert "recurrent state" in capsys.readouterr().err
