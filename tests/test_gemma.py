"""Gemma model tests: forward/grouped-head structure, cached decode
equivalence, loss-goes-down smoke, and the shared-RoPE speedup premise
(decode is jitted with a cache — the reference's cell 21 latency complaint
stemmed from rebuilding rotation matrices per token per layer).
"""

import jax
import jax.numpy as jnp
import numpy as np

from solvingpapers_tpu.data import load_char_corpus
from solvingpapers_tpu.data.batches import lm_batch_iterator
from solvingpapers_tpu.infer import generate
from solvingpapers_tpu.models.gemma import Gemma, GemmaConfig
from solvingpapers_tpu.train import OptimizerConfig, TrainConfig, Trainer

TINY = GemmaConfig(
    vocab_size=64, max_seq_len=32, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    dropout=0.0,
)


def test_forward_shape_and_geglu_hidden():
    model = Gemma(TINY)
    toks = jnp.zeros((2, 16), jnp.int32)
    params = model.init({"params": jax.random.key(0)}, toks)["params"]
    logits, caches = model.apply({"params": params}, toks)
    assert logits.shape == (2, 16, TINY.vocab_size)
    assert caches is None
    # GeGLU hidden = 4*dim (gemma.ipynb cell 9)
    ffn = params["block_0"]["ffn"]
    assert ffn["gate"]["kernel"].shape == (TINY.dim, 4 * TINY.dim)
    assert "bias" not in ffn["gate"]


def test_cached_decode_equals_full_forward():
    model = Gemma(TINY)
    rng = jax.random.key(1)
    prompt = jax.random.randint(rng, (2, 5), 0, TINY.vocab_size)
    params = model.init({"params": rng}, prompt)["params"]

    out = generate(model, params, prompt, rng, max_new_tokens=8)
    # one compiled forward a prefix length, not one program an operation
    forward = jax.jit(lambda toks: model.apply(
        {"params": params}, toks, deterministic=True))
    toks = prompt
    for _ in range(8):
        logits, _ = forward(toks)
        toks = jnp.concatenate([toks, jnp.argmax(logits[:, -1], -1)[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(toks))


def test_loss_decreases():
    _, train_toks, _ = load_char_corpus(synthetic_chars=20_000)
    from solvingpapers_tpu.sharding import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(data=1, fsdp=1, model=1), jax.devices()[:1])
    cfg = TrainConfig(
        steps=40, batch_size=8, log_every=100, eval_every=0,
        optimizer=OptimizerConfig(max_lr=3e-3, warmup_steps=5, total_steps=40),
    )
    trainer = Trainer(Gemma(TINY), cfg, mesh=mesh)
    it = lm_batch_iterator(train_toks, 8, TINY.max_seq_len, seed=0)
    b0 = next(it)
    state = trainer.init_state(b0)
    trainer._build_steps()
    state, m0 = trainer._train_step(state, b0)
    first = float(m0["train_loss"])
    for _ in range(cfg.steps):
        state, m = trainer._train_step(state, next(it))
    assert float(m["train_loss"]) < first - 0.3
