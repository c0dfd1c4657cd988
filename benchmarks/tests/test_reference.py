"""The plain reference against `models/deepseekv3.py` computing in float32,
at a tiny size: same weights, same tokens, same logits and loss."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.adapters import deepseekv3 as adapter
from benchmarks.reference import deepseekv3_ref as ref


def _program(**kw):
    from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3, DeepSeekV3Config

    cfg = DeepSeekV3Config(
        vocab_size=97, block_size=32, dim=64, n_layers=2, n_heads=2,
        latent_dim=16, n_experts=4, top_experts=2, dropout=0.0,
        attn_dropout=0.0, dtype="float32", pe_scale=0.02, **kw)
    return DeepSeekV3(cfg)


@pytest.mark.parametrize("kw", [
    dict(capacity_factor=4.0, balance_loss_weight=1e-2),
    dict(capacity_factor=1.0),  # capacity binds: tokens are dropped
    dict(capacity_factor=2.0, rope_dim=8),
], ids=["tinystories_like", "capacity_binds", "long_like_rope"])
def test_logits_and_loss_match_program_in_float32(kw):
    model = _program(**kw)
    sz = adapter.sizes_of(model.cfg)
    w = ref.make_weights(7, sz)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 97, size=(3, 32)).astype(np.int32)
    y = rng.integers(0, 97, size=(3, 32)).astype(np.int32)
    variables = model.init({"params": jax.random.key(0)}, jnp.asarray(x))
    params = adapter.to_program_tree(w, variables["params"])
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply(
            {"params": params, "moe_state": variables["moe_state"]},
            jnp.asarray(x), deterministic=True)
    biases = jnp.zeros((sz.layers, sz.experts))
    hid, _, _, dropped = ref.hidden_states(w, jnp.asarray(x), biases, sz,
                                           q_block=16)
    mine = ref.logits_of(w, hid)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(logits),
                               atol=2e-5, rtol=1e-4)
    if kw["capacity_factor"] == 1.0:
        assert float(dropped.mean()) > 0.0
    ce = ref.cross_entropy(w, hid, jnp.asarray(y), row_block=32)
    lp = jax.nn.log_softmax(logits, -1)
    want = -jnp.take_along_axis(lp, jnp.asarray(y)[..., None], -1).mean()
    assert abs(float(ce) - float(want)) < 1e-5


def test_blocked_attention_equals_whole():
    sz = ref.Sizes(vocab=50, block=64, dim=32, layers=1, heads=2, latent=8,
                   experts=2, top_k=1, rope_dim=4)
    w = ref.make_weights(3, sz)
    x = jnp.asarray(np.random.default_rng(1).integers(0, 50, (2, 64)))
    b = jnp.zeros((1, 2))
    whole, *_ = ref.hidden_states(w, x, b, sz, q_block=64)
    blocked, *_ = ref.hidden_states(w, x, b, sz, q_block=16)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(blocked),
                               atol=1e-5)


def test_weights_follow_the_seed_and_take_large_seeds():
    sz = ref.Sizes(vocab=50, block=8, dim=16, layers=1, heads=2, latent=4,
                   experts=2, top_k=1)
    a = ref.make_weights(2**31 + 5, sz)
    b = ref.make_weights(2**31 + 5, sz)
    c = ref.make_weights(5, sz)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["tok_emb"], c["tok_emb"])
    assert a["l0.norm1"].tolist() == [1.0] * 16


def test_int8_control_moves_the_numbers():
    sz = ref.Sizes(vocab=50, block=16, dim=32, layers=1, heads=2, latent=8,
                   experts=2, top_k=1)
    w = ref.make_weights(1, sz)
    rng = np.random.default_rng(2)
    batches = [(rng.integers(0, 50, (2, 16)), rng.integers(0, 50, (2, 16)))]
    opt = ref.Adam(max_lr=1e-3, warmup_steps=10, total_steps=100)
    a = ref.follow_training(w, batches, sz, opt)
    b = ref.follow_training(w, batches, sz, opt, quant="int8")
    assert a["loss"] != b["loss"]
    assert abs(a["loss"][0] - b["loss"][0]) < 0.05
