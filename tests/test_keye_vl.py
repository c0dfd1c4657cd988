"""The Keye-VL-2.0 family (attention over a lightning indexer's keys, held
experts without a shared one) at a tiny size on the CPU, seeded weights,
`topk` smaller than the sequence:

  * the selection (`ops/dsa.py`) against the reference's, ties and all, and
    with `topk` >= the sequence plain causal 32-on-4 attention;
  * the model's three loss terms and every gradient leaf against the plain
    reference (`benchmarks/reference/keye_vl_ref.py`) through the
    benchmark's adapter, in float32, where the two are the same function;
  * the indexer's leaves take gradient from the KL alone, and no other
    leaf takes any from it;
  * the first three `Trainer.fit` steps against the reference's
    `follow_training`; the compiled step stands under the layers the
    benchmark reads;
  * the share tests: the eight ranks' routed parts add up to the uncut
    layer, the sliced-vocabulary model is the whole one on the slice, the
    whole model counts the published parameters;
  * `cli train` runs the family, `cli serve` refuses it in words.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.adapters import keye_vl as adapter
from benchmarks.reference import keye_vl_ref as ref
from solvingpapers_tpu import ops
from solvingpapers_tpu.configs import get_config
from solvingpapers_tpu.configs.factory import (
    build_model, init_fn_for, loss_fn_for,
)
from solvingpapers_tpu.metrics import hlo_cost
from solvingpapers_tpu.models import keye_vl
from solvingpapers_tpu.models.keye_vl import KeyeVL, KeyeVLConfig, held_moe
from solvingpapers_tpu.ops import dsa
from solvingpapers_tpu.sharding import MeshConfig, create_mesh
from solvingpapers_tpu.train import Trainer
from solvingpapers_tpu.train.engine import TrainConfig
from solvingpapers_tpu.train.objectives import keye_vl_loss_fn
from solvingpapers_tpu.train.optim import OptimizerConfig

pytestmark = pytest.mark.fast

TINY = dict(
    vocab_size=96, block_size=64, hidden_size=32, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, num_experts=4,
    num_local_experts=4, router_experts=16, first_expert=4,
    num_experts_per_tok=3, moe_intermediate_size=24, indexer_num_heads=2,
    indexer_head_dim=8, topk=32, capacity_factor=2.0,
    router_aux_loss_coef=0.01, dtype="float32")
B, S = 2, 64


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    # S = 64 in two spans of 32 keys, each two blocks of 16 queries: the
    # first span ends at `topk` and selects every causal key, the second
    # selects 32 of up to 64
    monkeypatch.setattr(dsa, "Q_BLOCK", 16)
    monkeypatch.setattr(dsa, "KEY_STEP", 32)
    monkeypatch.setattr(keye_vl, "SEGMENT", 16)


def tiny(**over):
    return KeyeVLConfig(**{**TINY, **over})


def batch(seed=1):
    x = jax.random.randint(jax.random.key(seed), (B, S + 1), 0, 96)
    return {"x": x[:, :-1], "y": x[:, 1:]}


def seeded(cfg, seed=5, init_std=0.2):
    """(reference sizes, reference weights, the same as the program's
    tree). A wide init, so that at this width every layer matters and the
    index scores spread."""
    sz = dataclasses.replace(adapter.sizes_of(cfg), init_std=init_std)
    w = ref.make_weights(seed, sz)
    shapes = jax.eval_shape(
        lambda: KeyeVL(cfg).init(jax.random.key(0), batch()["x"]))
    return sz, w, adapter.to_program_tree(w, shapes["params"])


def program_masks(qi, w, ki, topk):
    """`dsa.selection_mask`, (B, S, S), as booleans."""
    return dsa.selection_mask(qi, w, ki, topk) > 0


@pytest.mark.parametrize("ties", [False, True])
def test_selected_sets_are_the_references(ties):
    """Each row's set is its `topk` largest causal index scores, all of
    them while there are no more; with scores that tie by the hundred (small
    whole numbers) the lower key wins, in the program (k-th value and k-th
    index of `lax.top_k`) as in the reference (a count along the row)."""
    j, d, topk = 2, 8, 32
    keys = jax.random.split(jax.random.key(7), 3)
    qi = jax.random.normal(keys[0], (B, S, j, d))
    ki = jax.random.normal(keys[1], (B, S, d))
    w = jax.random.normal(keys[2], (B, S, j))
    if ties:
        qi, ki, w = (jnp.round(a) for a in (qi, ki, w))
    got = jax.jit(program_masks, static_argnums=3)(qi, w, ki, topk)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = ref.index_scores(qi, w, ki, None)
    want = ref.select(scores, causal, topk)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        jnp.sum(got, -1), jnp.broadcast_to(
            jnp.minimum(jnp.arange(S) + 1, topk), (B, S)))
    if ties:  # the test means something: a row's k-th value is shared
        masked = jnp.where(causal, scores, -jnp.inf)
        kth = jax.lax.top_k(masked, topk)[0][..., -1:]
        assert int(jnp.sum((masked == kth) & causal & ~got)) > 50
    # the scores themselves: the program's one product and the reference's
    # head by head
    np.testing.assert_allclose(dsa.index_scores(qi, w, ki), scores,
                               rtol=1e-5, atol=1e-5)


def test_topk_past_the_sequence_is_plain_causal_attention():
    n, g, wd, j, d = 4, 2, 8, 2, 8
    keys = jax.random.split(jax.random.key(3), 6)
    q = jax.random.normal(keys[0], (B, S, n, wd))
    k = jax.random.normal(keys[1], (B, S, g, wd))
    v = jax.random.normal(keys[2], (B, S, g, wd))
    qi = jax.random.normal(keys[3], (B, S, j, d))
    ki = jax.random.normal(keys[4], (B, S, d))
    w = jax.random.normal(keys[5], (B, S, j))
    out, kl, count, live = jax.jit(lambda *a: dsa.selected_attention(
        *a, topk=S, scale=wd ** -0.5))(q, k, v, qi, ki, w)
    want = ops.dot_product_attention(q, k, v, causal=True, scale=wd ** -0.5)
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert float(count) == B * S * (S + 1) / 2
    assert float(kl) > 0.0  # the indexer still has its target


def test_live_tile_fraction_counts_the_causal_tiles_that_hold_a_pair(
        both_sides):
    """64 queries by 64 keys in tiles of 16 x 32: six causal tiles (the
    second key tile begins at key 32, past the first two query tiles). Every
    query selecting key 0 alone leaves four of them live, one pair more in
    the second key tile five, the causal mask all six; a batch row counts
    for itself. The objective logs the layers' mean in the attention's
    backward tiling (one tile at this length: 1.0)."""
    only_first = jnp.zeros((B, S, S), jnp.int8).at[:, :, 0].set(1)
    live = jax.jit(dsa.live_tile_fraction, static_argnums=(1, 2))
    assert float(jnp.mean(live(only_first, 16, 32))) == pytest.approx(4 / 6)
    one_more = only_first.at[0, 40, 35].set(1)
    np.testing.assert_allclose(live(one_more, 16, 32), [5 / 6, 4 / 6])
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), jnp.int8)), (B, S, S))
    assert live(causal, 16, 32).tolist() == [1.0] * B
    assert float(both_sides["aux"]["dsa_live_tile_fraction"]) == 1.0


@pytest.fixture(scope="module")
def both_sides():
    """Program and reference on the same weights and batch, float32: (loss,
    aux, gradients by reference name) of each; the program's two groups of
    terms differentiated apart as well."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dsa, "Q_BLOCK", 16)
        mp.setattr(dsa, "KEY_STEP", 32)
        mp.setattr(keye_vl, "SEGMENT", 16)
        cfg = tiny()
        sz, w, tree = seeded(cfg)
        b = batch()
        model = KeyeVL(cfg)

        def terms(p):
            loss, aux, _ = keye_vl_loss_fn(model, p, b, None, None, True)
            return (loss - aux["dsa_index_kl"], aux["dsa_index_kl"]), aux

        def program(p):
            (rest, kl), pull, aux = jax.vjp(terms, p, has_aux=True)
            one, zero = jnp.ones(()), jnp.zeros(())
            return rest + kl, aux, pull((one, zero))[0], pull((zero, one))[0]

        loss, aux, g_rest, g_kl = jax.jit(program)(tree)
        (want, want_aux), want_g = jax.jit(jax.value_and_grad(
            lambda w: ref.loss_fn(w, b["x"], b["y"], sz, q_block=32),
            has_aux=True))(w)
    return dict(loss=loss, aux=aux, g_rest=adapter.leaf_norms(g_rest),
                g_kl=adapter.leaf_norms(g_kl),
                g=jax.tree.map(jnp.add, g_rest, g_kl), tree=tree,
                want=want, want_aux=want_aux, want_g=want_g)


def test_loss_terms_and_gradients_match_the_reference_float32(both_sides):
    """Same function, other order of float32 sums: the three terms to 1e-5,
    every gradient leaf to 1e-4 of the largest entry of its reference (a
    key that changes sides of the 32nd place would move a leaf by far more:
    the selected sets agree)."""
    s = both_sides
    ce, balance, index_kl, _, selected = s["want_aux"]
    assert float(s["loss"]) == pytest.approx(float(s["want"]), abs=1e-5)
    aux = s["aux"]
    assert float(jnp.log(aux["perplexity"])) == pytest.approx(
        float(ce), abs=1e-5)
    assert float(aux["balance_loss"]) == pytest.approx(float(balance),
                                                       abs=1e-5)
    assert float(aux["dsa_index_kl"]) == pytest.approx(float(index_kl),
                                                       abs=1e-6)
    assert float(index_kl) > 1e-3
    assert float(aux["dsa_selected_fraction"]) == pytest.approx(
        float(selected)) == pytest.approx(
            (32 * 33 / 2 + (S - 32) * 32) / (S * (S + 1) / 2))
    got = adapter.to_program_tree(s["want_g"], s["tree"])
    flat = jax.tree_util.tree_leaves_with_path(s["g"])
    assert len(flat) == len(s["want_g"]) == 2 * 15 + 3
    for (path, leaf), want in zip(flat, jax.tree.leaves(got)):
        np.testing.assert_allclose(
            leaf, want, atol=1e-4 * float(jnp.max(jnp.abs(want))),
            err_msg=jax.tree_util.keystr(path))


def test_each_loss_moves_its_own_parameters(both_sides):
    """The KL's gradient is zero, to the bit, outside the indexer's three
    matrices a layer, and non-zero inside; cross-entropy and balance term
    the other way round."""
    g_rest, g_kl = both_sides["g_rest"], both_sides["g_kl"]
    indexer = {k for k in g_kl if k.split(".")[-1].startswith("idx_")}
    assert len(indexer) == 2 * 3
    for name in g_kl:
        if name in indexer:
            assert g_kl[name] > 1e-6 and g_rest[name] == 0.0, name
        else:
            assert g_kl[name] == 0.0 and g_rest[name] > 1e-6, name


def test_first_three_steps_follow_the_reference_and_the_step_is_scoped():
    """Three AdamW steps of the `Trainer`'s own compiled step in float32
    against the reference's `follow_training`: losses and gradient norms to
    1e-4 (float32 sums in another order, three steps deep), every leaf's
    change within 2% of the reference's (the median leaf's where that is
    larger). The same compiled step stands under the layers the benchmark
    reads. (`Trainer.fit` itself: the `cli train` test below.)"""
    cfg = tiny(num_hidden_layers=1)
    sz, w, tree = seeded(cfg, init_std=0.05)
    opt = OptimizerConfig(name="adamw", max_lr=3e-3, warmup_steps=2,
                          total_steps=10, b1=0.9, b2=0.95, weight_decay=0.1,
                          grad_clip=1.0)
    train = TrainConfig(steps=3, batch_size=B, log_every=1, eval_every=0,
                        ckpt_every=0, optimizer=opt, seed=0)
    trainer = Trainer(
        KeyeVL(cfg), train, loss_fn=keye_vl_loss_fn,
        mesh=create_mesh(MeshConfig(), devices=jax.devices()[:1]))
    batches = [{k: np.asarray(v) for k, v in batch(seed).items()}
               for seed in (1, 2, 3)]
    state = trainer.init_state(batches[0])
    state = state.replace(params=jax.device_put(
        jax.tree.map(jnp.array, tree), trainer._state_shardings.params))
    trainer._build_steps()
    step = trainer._train_step.lower(state, batches[0]).compile()
    logged = []
    for b in batches:
        state, row = step(state, b)
        logged.append(jax.device_get(row))
    assert all("train_dsa_index_kl" in r and "train_dsa_selected_fraction" in r
               and "train_moe_drop_fraction" in r for r in logged)
    want = ref.follow_training(w, [(b["x"], b["y"]) for b in batches], sz,
                               adapter.adam_of(opt), q_block=32)
    np.testing.assert_allclose([r["train_loss"] for r in logged],
                               want["loss"], atol=1e-4)
    np.testing.assert_allclose([r["grad_norm"] for r in logged],
                               want["grad_norm"], rtol=1e-4)
    np.testing.assert_allclose([r["train_dsa_index_kl"] for r in logged],
                               [t[2] for t in want["terms"]], atol=1e-5)
    moved = adapter.leaf_norms(jax.tree.map(
        lambda a, b: a - b, state.params, jax.tree.map(jnp.array, tree)))
    scale = float(np.median(list(want["delta"].values())))
    worst = max(abs(moved[k] - v) / max(v, scale)
                for k, v in want["delta"].items())
    assert worst <= 0.02, worst

    scopes = hlo_cost.device_scopes(step.as_text())
    top = [s for s in scopes.values() if s.top_level]
    layers = {s.layer for s in top}
    assert {"L_dsa_index", "L_dsa_select", "L_dsa_attend", "L_dsa_loss",
            "L_attn_proj", "L_moe_gate", "L_moe_dispatch", "L_moe_experts",
            "L_moe_combine", "L_moe_stats", "L_loss_head", "L_optimizer",
            "L_embed"} <= layers
    assert "L_moe_shared" not in layers and "L_attn_core" not in layers
    # the selection is forward only, and the layer's remat keeps its masks:
    # no sort runs again
    assert {s.pass_ for s in top if s.layer == "L_dsa_select"} == {"fwd"}
    assert {s.pass_ for s in top if s.layer == "L_dsa_attend"} >= {"fwd",
                                                                   "bwd"}
    covered = sum(s.layer is not None for s in top) / len(top)
    assert covered >= 0.97, f"{covered:.3f} of {len(top)} instructions"


def test_the_eight_ranks_shares_add_up_to_the_uncut_layer():
    """Eight ranks hold two of sixteen experts each. Each routes over all
    sixteen and computes its own experts' part; with no shared expert their
    parts alone are the uncut layer of the reference, and the layer has no
    leaf but the router's and the experts'."""
    ranks, held = 8, 2
    cfg0 = tiny(num_experts=held, num_local_experts=held, first_expert=0,
                capacity_factor=16.0)
    sz = dataclasses.replace(adapter.sizes_of(cfg0), held=16, first=0,
                             capacity_factor=None, init_std=0.3, layers=1)
    w = ref.layer_weights(ref.make_weights(3, sz), 0)
    x = jax.random.normal(jax.random.key(0), (B, S, 32))
    whole = jax.jit(lambda w, x: ref.moe(w, x, sz, None)[0])(
        w, x.reshape(B * S, 32)).reshape(B, S, 32)
    layer = held_moe(cfg0)
    shapes = jax.eval_shape(lambda: layer.init(jax.random.key(0), x))
    assert sorted(shapes["params"]) == ["gate", "w1", "w2", "w3"]

    @jax.jit
    def parts_of(w):
        out = []
        for r in range(ranks):
            sl = slice(r * held, (r + 1) * held)
            cfg = dataclasses.replace(cfg0, first_expert=r * held)
            out.append(held_moe(cfg).apply(
                {"params": {"gate": {"kernel": w["gate"]}, "w1": w["w1"][sl],
                            "w2": w["w2"][sl], "w3": w["w3"][sl]}}, x))
        return jnp.stack(out)

    parts = parts_of(w)
    assert float(jnp.min(jnp.max(jnp.abs(parts), (1, 2, 3)))) > 1e-3
    np.testing.assert_allclose(jnp.sum(parts, 0), whole, atol=2e-5)


def test_a_vocabulary_slice_is_the_whole_model_on_the_slice():
    """Embedding rows and head columns [0, V/8) of the whole model, tokens
    from the slice: the sliced model's logits are the whole model's over
    the slice."""
    whole_cfg = tiny(num_hidden_layers=1)
    cut_cfg = tiny(num_hidden_layers=1, vocab_size=12)
    _, _, tree = seeded(whole_cfg)
    cut = dict(tree, tok_emb={"embedding": tree["tok_emb"]["embedding"][:12]},
               lm_head={"kernel": tree["lm_head"]["kernel"][:, :12]})
    x = jax.random.randint(jax.random.key(2), (B, S), 0, 12)
    whole, _ = jax.jit(KeyeVL(whole_cfg).apply)({"params": tree}, x)
    sliced, _ = jax.jit(KeyeVL(cut_cfg).apply)({"params": cut}, x)
    np.testing.assert_allclose(sliced, whole[..., :12], atol=1e-5)


def count(cfg):
    shapes = jax.eval_shape(
        lambda: KeyeVL(cfg).init(jax.random.key(0),
                                 jnp.zeros((1, 8), jnp.int32)))
    return sum(int(np.prod(p.shape))
               for p in jax.tree.leaves(shapes["params"]))


def test_published_parameter_counts_and_the_cut():
    cfg = get_config("keye_vl2_30b_a3b")
    m = cfg.model
    assert cfg.model_family == "keye_vl"
    assert (m.num_hidden_layers, m.hidden_size, m.vocab_size) == (
        48, 2048, 151_936)
    assert (m.num_attention_heads, m.num_key_value_heads, m.head_dim) == (
        32, 4, 128)
    assert (m.num_experts, m.router_experts, m.num_experts_per_tok,
            m.moe_intermediate_size) == (128, 128, 8, 768)
    assert (m.indexer_num_heads, m.indexer_head_dim, m.indexer_num_kv_heads,
            m.topk) == (16, 64, 1, 2048)
    layer = count(dataclasses.replace(m, num_hidden_layers=1)) - count(
        dataclasses.replace(m, num_hidden_layers=0))
    assert layer == 625_381_632
    # the published "30B": 48 layers, embedding, head and final norm
    assert 48 * layer + count(
        dataclasses.replace(m, num_hidden_layers=0)) == 30_640_650_240
    cell = json.load(open("benchmarks/configs/keye_vl2_ep8.json"))
    cut = dataclasses.replace(m, **cell["model"])
    assert count(cut) == 465_390_592
    assert count(dataclasses.replace(cut, num_hidden_layers=1)) - count(
        dataclasses.replace(cut, num_hidden_layers=0)) == 96_899_328
    small = dataclasses.replace(cfg, model=tiny())
    assert isinstance(build_model(small), KeyeVL)
    assert loss_fn_for(small) is keye_vl_loss_fn
    assert init_fn_for(small) is None
    with pytest.raises(ValueError, match="not among the router"):
        KeyeVLConfig(num_experts=16, num_local_experts=16, first_expert=120)
    with pytest.raises(ValueError, match="one count under two names"):
        KeyeVLConfig(num_experts=16)


def test_model_refuses_a_cache_and_a_sequence_past_its_block():
    model = KeyeVL(tiny())
    x = jnp.zeros((1, 8), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0), x))
    with pytest.raises(NotImplementedError, match="indexer's keys"):
        jax.eval_shape(lambda p: model.apply(p, x, caches=[None]), params)
    with pytest.raises(ValueError, match="exceeds block_size"):
        jax.eval_shape(lambda p: model.apply(
            p, jnp.zeros((1, 65), jnp.int32)), params)


def test_cli_train_runs_the_family_and_serve_refuses_it(
        monkeypatch, tmp_path, capsys):
    """`cli train --config keye_vl2_30b_a3b` at a tiny size: the registry
    entry through `build_char_lm_run` and `Trainer.fit`; the loss falls.
    `cli serve` says why it cannot."""
    from solvingpapers_tpu import cli
    from solvingpapers_tpu.configs import registry

    published = registry._REGISTRY["keye_vl2_30b_a3b"]

    def small():
        cfg = published()
        opt = dataclasses.replace(cfg.train.optimizer, max_lr=1e-2,
                                  warmup_steps=1)
        return dataclasses.replace(
            cfg, model=tiny(num_hidden_layers=1, block_size=32, topk=12),
            data={"kind": "char", "path": None, "block_size": 32},
            train=dataclasses.replace(
                cfg.train, steps=4, batch_size=8, log_every=1, eval_every=0,
                ckpt_every=0, optimizer=opt,
                tokens_per_step=8 * 32))  # eight CPU devices

    monkeypatch.setitem(registry._REGISTRY, "keye_vl2_30b_a3b", small)
    out = tmp_path / "rows.jsonl"
    assert cli.main(["train", "--config", "keye_vl2_30b_a3b", "--steps", "4",
                     "--jsonl", str(out)]) == 0
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    losses = [r["train_loss"] for r in rows if "train_loss" in r]
    assert len(losses) == 4 and losses[-1] < losses[0]
    assert all(0.0 < r["train_dsa_selected_fraction"] < 1.0
               for r in rows if "train_loss" in r)
    capsys.readouterr()
    rc = cli.main(["serve", "--config", "keye_vl2_30b_a3b", "--port", "0"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "indexer" in err and "R-M13" in err
