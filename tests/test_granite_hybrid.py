"""The Granite-hybrid family at a tiny size on the CPU, seeded weights:

  * the published parameter counts (the whole model and the benchmark's
    cut), the cut reading the first ten of the published 40 `layer_types`,
    what the config refuses (routed experts first);
  * the model's loss and gradients against the plain reference
    (`benchmarks/reference/granite_hybrid_ref.py`, token-by-token
    recurrence, dense scores) through the benchmark's adapter, in float32,
    where the two are the same function (the per-token stages in blocks,
    the rule in two grid steps of two blocks of heads);
  * the first three `Trainer.fit` steps in bfloat16 against the reference's
    `follow_training`;
  * the tied head: logits with the head are the hidden rows times E^T, and
    a vocabulary slice equals the whole model restricted to the slice's ids
    and logits;
  * the compiled train step stands under the layer scopes the benchmark
    reads; `cli train` runs the family, `cli serve` refuses it in words.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import keep_against_plain_remat, kernel_passes

from benchmarks.adapters import granite_hybrid as adapter
from benchmarks.drivers.train_job import Rows
from benchmarks.reference import granite_hybrid_ref as ref
from solvingpapers_tpu.configs import get_config
from solvingpapers_tpu.configs.factory import (
    build_model, init_fn_for, loss_fn_for,
)
from solvingpapers_tpu.metrics import hlo_cost
from solvingpapers_tpu.models.granite_hybrid import (
    GraniteHybrid, GraniteHybridConfig,
)
from solvingpapers_tpu.ops import ssd
from solvingpapers_tpu.sharding import MeshConfig, create_mesh
from solvingpapers_tpu.train import Trainer
from solvingpapers_tpu.train.engine import TrainConfig
from solvingpapers_tpu.train.objectives import chunked_head_loss_fn
from solvingpapers_tpu.train.optim import OptimizerConfig

pytestmark = pytest.mark.fast

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# two layers stand in for the ten, M *: sixteen heads of the mixer on ONE
# group (two blocks of eight heads in the kernels), four query heads on two
# key-value heads; the softmax's scale is 1 where head_dim^-0.5 is 0.354, so
# a program that took the latter is another function
TINY = dict(
    vocab_size=96, block_size=64, hidden_size=32, intermediate_size=48,
    shared_intermediate_size=48, num_hidden_layers=2,
    layer_types=("mamba", "attention"),
    num_attention_heads=4, num_key_value_heads=2, attention_multiplier=1.0,
    mamba_n_heads=16, mamba_d_head=4, mamba_n_groups=1, mamba_d_state=8,
    mamba_chunk_size=8, use_flash=False)
B, S = 2, 32


@pytest.fixture(autouse=True)
def short_segments(monkeypatch):
    """Blocks of 16 tokens: the per-token stages run two blocks, the rule
    two grid steps of two chunks each."""
    monkeypatch.setattr(ssd, "SEGMENT", 16)
    monkeypatch.setattr(ssd.kernel, "CHUNKS_A_STEP", 2)


def tiny(**over):
    return GraniteHybridConfig(**{**TINY, **over})


def batch(seed=1, vocab=96):
    x = jax.random.randint(jax.random.key(seed), (B, S + 1), 0, vocab)
    return {"x": x[:, :-1], "y": x[:, 1:]}


def shapes_of(cfg):
    return jax.eval_shape(lambda: GraniteHybrid(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]


def count(tree):
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))


def seeded(cfg, seed=5, init_std=0.2):
    """(reference sizes, reference weights, the same as the program's
    tree). A wide init, so that at this width every layer matters."""
    sz = dataclasses.replace(adapter.sizes_of(cfg), init_std=init_std)
    w = ref.make_weights(seed, sz)
    return sz, w, adapter.to_program_tree(w, shapes_of(cfg))


def loss_and_grads(cfg, tree, b):
    model = GraniteHybrid(cfg)
    fn = lambda p: chunked_head_loss_fn(  # noqa: E731
        model, p, b, jax.random.key(0), None, True)[0]
    return jax.jit(jax.value_and_grad(fn))(tree)


def test_published_parameter_counts_and_the_cut():
    """The whole model is the published "3B"; the benchmark's file changes
    two keys, and the ten layers it keeps are the first ten of the
    published 40 kinds: one whole period, attention at position 5."""
    whole = GraniteHybridConfig()
    assert count(shapes_of(whole)) == 3_191_396_096
    assert len(whole.layer_types) == 40
    assert [i for i, k in enumerate(whole.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    with open(os.path.join(
            ROOT, "benchmarks/configs/granite4_h_micro_pp4.json")) as f:
        file = json.load(f)
    assert tuple(file["layer_types"]) == whole.layer_types
    cut = dataclasses.replace(whole, **file["model"])
    assert sorted(k for k, v in file["model"].items()
                  if getattr(whole, k) != v) == sorted(file["reduced"])
    assert cut.layer_pattern == whole.layer_types[:10]
    assert cut.layer_pattern.count("mamba") == 9
    assert cut.layer_pattern[5] == "attention"
    assert count(shapes_of(cut)) == 772_160_448
    # every source key of the model dict is the file's own top-level value
    for key, value in file["model"].items():
        if key in file:
            assert file[key] == value, key
    assert file["chunk_size"] == file["mamba_chunk_size"] == 256
    sz = adapter.sizes_of(cut)
    assert (sz.pattern, sz.head_dim, sz.attn_scale) == (
        "MMMMM*MMMM", 64, 1 / 64)


@pytest.mark.parametrize("field, value", [
    ("num_local_experts", 8), ("num_experts_per_tok", 2)])
def test_config_refuses_routed_experts(field, value):
    with pytest.raises(ValueError, match="R-M19"):
        GraniteHybridConfig(**{field: value})


@pytest.mark.parametrize("field, value", [
    ("position_embedding_type", "rope"), ("tie_word_embeddings", False),
    ("hidden_act", "gelu"), ("mamba_proj_bias", True),
    ("shared_intermediate_size", 4096), ("mamba_expand", 3),
    ("layer_types", ("mamba",) * 39 + ("moe",))])
def test_config_refuses_what_has_no_path_here(field, value):
    with pytest.raises(ValueError, match=field):
        GraniteHybridConfig(**{field: value})


def test_model_refuses_a_cache_and_a_sequence_past_its_block():
    cfg = tiny(dtype="float32")
    model, tree = GraniteHybrid(cfg), shapes_of(cfg)
    with pytest.raises(NotImplementedError, match="R-M7"):
        jax.eval_shape(lambda p: model.apply(
            {"params": p}, batch()["x"], caches=[None]), tree)
    with pytest.raises(ValueError, match="block_size"):
        jax.eval_shape(lambda p: model.apply(
            {"params": p}, jnp.zeros((1, 65), jnp.int32)), tree)


def test_loss_and_gradients_match_the_reference_float32():
    """float32 on both sides, the flash kernels and the rule's interpreted:
    the same function, so the loss to 2e-5 and every gradient leaf to 2e-3
    of its own norm (or of the median leaf's): what is left is the chunked
    rule's and the kernels' order of sums. The embedding's gradient is the
    sum of the lookup's and the head's (the reference has one leaf too)."""
    cfg = tiny(dtype="float32", use_flash=True)
    sz, w, tree = seeded(cfg)
    b = batch()
    loss, g_model = loss_and_grads(cfg, tree, b)
    want, g_ref = jax.jit(jax.value_and_grad(
        lambda w: ref.loss_fn(w, b["x"], b["y"], sz)[0]))(w)
    assert float(loss) == pytest.approx(float(want), abs=2e-5)
    got = adapter.leaf_norms(jax.tree.map(
        lambda a, r: a - r, g_model, adapter.to_program_tree(g_ref, tree)))
    ref_norms = {k: float(jnp.linalg.norm(v)) for k, v in g_ref.items()}
    scale = float(np.median(list(ref_norms.values())))
    for name, gap in got.items():
        assert gap <= 2e-3 * max(ref_norms[name], scale), (name, gap)
    assert all(v > 0 for v in ref_norms.values())


def test_tied_head_and_a_vocabulary_slice():
    """With the head, the logits are the reference's Norm(x) E^T /
    logits_scaling. A model that holds the first 48 rows of E is the whole
    one restricted to those ids and logits: the slice's columns."""
    cfg = tiny(dtype="float32", num_hidden_layers=1)
    sz, w, tree = seeded(cfg)
    x = batch(vocab=48)["x"]
    logits, _ = GraniteHybrid(cfg).apply({"params": tree}, x)
    want = ref.logits_of(w, ref.hidden_states(w, x, sz), sz)
    np.testing.assert_allclose(logits, want, atol=2e-4)
    emb = tree["tok_emb"]["embedding"]
    sliced = dict(tree, tok_emb={"embedding": emb[:48]})
    part, _ = GraniteHybrid(dataclasses.replace(cfg, vocab_size=48)).apply(
        {"params": sliced}, x)
    np.testing.assert_allclose(part, logits[..., :48], atol=1e-6)


# what the benchmark's `correct` compares, at this size
LIMITS = {"loss_gap": 5e-3, "grad_norm_gap": 1e-2}


def test_first_three_fit_steps_follow_the_reference():
    """bfloat16 operands against the float32 reference: the loss within
    5e-3 and the gradient's norm within 1%, the tolerances of the other
    families' tiny sizes (bfloat16 carries 8 bits: 4e-3 a product), and the
    weights' change after three AdamW steps within 5% a leaf."""
    cfg = tiny(dtype="bfloat16")
    sz, w, tree = seeded(cfg, seed=7, init_std=0.02)
    opt = OptimizerConfig(name="adamw", max_lr=3e-3, warmup_steps=2,
                          total_steps=10, b1=0.9, b2=0.95, weight_decay=0.1,
                          grad_clip=1.0)
    train = TrainConfig(steps=3, batch_size=B, log_every=1, eval_every=0,
                        ckpt_every=0, optimizer=opt, seed=0)
    trainer = Trainer(
        GraniteHybrid(cfg), train, loss_fn=chunked_head_loss_fn,
        mesh=create_mesh(MeshConfig(), devices=jax.devices()[:1]))
    batches = [batch(seed) for seed in (1, 2, 3)]
    state = trainer.init_state(batches[0])
    # placed as the state's own, or the step compiles a second time
    state = state.replace(params=jax.device_put(
        jax.tree.map(jnp.array, tree), trainer._state_shardings.params))
    rows = Rows()
    state = trainer.fit(iter(batches), None, writer=rows, state=state)
    logged = [r for r in rows.rows if "train_loss" in r]
    assert [r["step"] for r in logged] == [1, 2, 3]
    host = [(np.asarray(b["x"]), np.asarray(b["y"])) for b in batches]
    want = ref.follow_training(w, host, sz, adapter.adam_of(opt))
    sound = {
        "loss_gap": max(abs(r["train_loss"] - b)
                        for r, b in zip(logged, want["loss"])),
        "grad_norm_gap": max(abs(r["grad_norm"] - b) / b
                             for r, b in zip(logged, want["grad_norm"]))}
    assert all(sound[k] <= LIMITS[k] for k in LIMITS), sound
    moved = adapter.leaf_norms(jax.tree.map(
        lambda a, b: a - b, state.params, jax.tree.map(jnp.array, tree)))
    scale = float(np.median(list(want["delta"].values())))
    worst = max(abs(moved[k] - v) / max(v, scale)
                for k, v in want["delta"].items())
    assert worst <= 0.05, worst


def test_registry_holds_the_published_sizes_and_the_factory_builds_it():
    cfg = get_config("granite4_h_micro")
    m = cfg.model
    assert cfg.model_family == "granite_hybrid"
    assert (m.num_hidden_layers, m.hidden_size, m.vocab_size,
            m.intermediate_size) == (40, 2048, 100_352, 8192)
    assert (m.mamba_n_heads, m.mamba_d_head, m.mamba_n_groups,
            m.mamba_d_state, m.mamba_d_conv, m.mamba_chunk_size) == (
        64, 64, 1, 128, 4, 256)
    assert (m.num_attention_heads, m.num_key_value_heads, m.head_dim,
            m.attention_scale) == (32, 8, 64, 0.015625)
    assert (m.embedding_multiplier, m.residual_multiplier,
            m.logits_scaling) == (12, 0.22, 8)
    assert cfg.train.optimizer.name == "adamw"
    assert cfg.train.tokens_per_step == cfg.data["block_size"] == 8192
    small = dataclasses.replace(cfg, model=tiny())
    assert isinstance(build_model(small), GraniteHybrid)
    assert loss_fn_for(small) is chunked_head_loss_fn
    assert init_fn_for(small) is None


def test_train_step_stands_under_the_layers_the_benchmark_reads():
    """The family adds no kind of layer to the trace: mixers, attention,
    the SwiGLU, both uses of the tied weight and the scaled adds are under
    scopes that `hlo_cost.LAYER_SCOPES` already names."""
    cfg = tiny(dtype="float32", remat=True, use_flash=True)
    trainer = Trainer(
        GraniteHybrid(cfg), TrainConfig(steps=2, batch_size=B, log_every=1),
        loss_fn=chunked_head_loss_fn,
        mesh=create_mesh(MeshConfig(), devices=jax.devices()[:1]))
    b = {k: np.asarray(v) for k, v in batch().items()}
    state = trainer.init_state(b)
    trainer._build_steps()
    with hlo_cost._persistent_cache_off():
        text = trainer._train_step.lower(state, b).compile().as_text()
    scopes = hlo_cost.device_scopes(text)
    top = [s for s in scopes.values() if s.top_level]
    layers = {s.layer for s in top} - {None}
    assert layers == {
        "L_embed", "L_ssm_proj", "L_ssm_conv", "L_ssm_core", "L_attn_proj",
        "L_attn_core", "L_dense_ffn", "L_loss_head", "L_optimizer",
        *hlo_cost.KERNEL_SCOPES}
    assert layers <= set(hlo_cost.LAYER_SCOPES) | set(
        hlo_cost.KERNEL_SCOPES)
    # the layers' remat keeps the forward kernels' results, the flash one's
    # (FLASH_RESIDUALS) and the recurrence's (SSD_RESIDUALS): each stands
    # in the step alone, while the projections around them run again
    assert {s.pass_ for s in top if s.layer == "flash_mla_fwd"} == {"fwd"}
    assert kernel_passes(text, scopes, "ssd_(?:fwd|bwd)", "L_ssm_core") == {
        "ssd_fwd": {"fwd"}, "ssd_bwd": {"bwd"}}
    assert {s.pass_ for s in top if s.layer == "L_ssm_proj"} == {
        "fwd", "remat", "bwd"}
    covered = sum(s.layer is not None for s in top) / len(top)
    assert covered >= 0.9, f"{covered:.3f} of {len(top)} top-level instructions"


def test_keeping_the_kernels_results_changes_no_bit_of_loss_or_gradient(
        monkeypatch):
    """The layers' remat with `save_only_these_names(*FLASH_RESIDUALS,
    *SSD_RESIDUALS)` against the same model under a plain `nn.remat(...,
    prevent_cse=True)`: one forward kernel a layer in the gradient, the
    flash one and the recurrence's (two blocks of eight heads a call),
    where the plain one holds two, the loss and every gradient leaf bit for
    bit."""
    cfg = tiny(dtype="float32", remat=True, use_flash=True,
               num_hidden_layers=3,
               layer_types=("mamba", "mamba", "attention"))
    _, _, tree = seeded(cfg)
    model, b = GraniteHybrid(cfg), batch()

    n_kept, n_plain = keep_against_plain_remat(
        monkeypatch, lambda: jax.value_and_grad(lambda p: chunked_head_loss_fn(
            model, p, b, jax.random.key(0), None, True)[0]),
        tree, ("flash_mla_fwd", "ssd_fwd", "ssd_bwd"))
    assert (n_kept, n_plain) == ((1, 2, 2), (2, 4, 2))


def test_cli_train_runs_the_family(monkeypatch, tmp_path):
    """`cli train --config granite4_h_micro` at a tiny size (one Mamba-2
    layer: the rule's kernels are interpreted on each of the eight CPU
    devices): the registry entry through `build_char_lm_run` and
    `Trainer.fit`; the loss falls."""
    from solvingpapers_tpu import cli
    from solvingpapers_tpu.configs import registry

    published = registry._REGISTRY["granite4_h_micro"]

    def small():
        cfg = published()
        opt = dataclasses.replace(cfg.train.optimizer, max_lr=1e-2,
                                  warmup_steps=1)
        return dataclasses.replace(
            cfg, model=tiny(num_hidden_layers=1),
            data={"kind": "char", "path": None, "block_size": 32},
            train=dataclasses.replace(
                cfg.train, steps=4, batch_size=8, log_every=1, eval_every=0,
                ckpt_every=0, optimizer=opt,
                tokens_per_step=8 * 32))  # eight CPU devices

    monkeypatch.setitem(registry._REGISTRY, "granite4_h_micro", small)
    out = tmp_path / "rows.jsonl"
    assert cli.main(["train", "--config", "granite4_h_micro", "--steps", "4",
                     "--jsonl", str(out)]) == 0
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    losses = [r["train_loss"] for r in rows if "train_loss" in r]
    assert len(losses) == 4 and losses[-1] < losses[0]


def test_cli_serve_refuses_the_family_and_list_names_it(capsys):
    from solvingpapers_tpu import cli

    rc = cli.main(["serve", "--config", "granite4_h_micro", "--port", "0"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "recurrent state" in err and "R-M7" in err
    assert cli.main(["list"]) == 0
    assert "granite4_h_micro" in capsys.readouterr().out
