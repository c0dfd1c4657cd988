"""LLaMA3 model tests: forward shape, GQA head accounting, cached decode
equivalence (which the reference's generate fails — LLaMA-jax.ipynb cell 14
never passes the cache), loss-goes-down smoke training, sgd parity option.
"""

import jax
import jax.numpy as jnp
import numpy as np

from solvingpapers_tpu.data import load_char_corpus
from solvingpapers_tpu.data.batches import lm_batch_iterator
from solvingpapers_tpu.infer import generate
from solvingpapers_tpu.models.llama3 import Llama, LlamaConfig
from solvingpapers_tpu.train import OptimizerConfig, TrainConfig, Trainer

TINY = LlamaConfig(
    vocab_size=64, max_seq_len=32, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    dropout=0.0,
)


def test_forward_shape_and_param_structure():
    model = Llama(TINY)
    toks = jnp.zeros((2, 16), jnp.int32)
    params = model.init({"params": jax.random.key(0)}, toks)["params"]
    logits, caches = model.apply({"params": params}, toks)
    assert logits.shape == (2, 16, TINY.vocab_size)
    assert caches is None
    # GQA: kv projection is n_kv_heads wide, q is n_heads wide
    attn = params["block_0"]["attn"]
    head_dim = TINY.dim // TINY.n_heads
    assert attn["q"]["kernel"].shape == (TINY.dim, TINY.n_heads * head_dim)
    assert attn["kv"]["kernel"].shape == (TINY.dim, 2 * TINY.n_kv_heads * head_dim)


def test_cached_decode_equals_full_forward():
    model = Llama(TINY)
    rng = jax.random.key(1)
    prompt = jax.random.randint(rng, (2, 6), 0, TINY.vocab_size)
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


def test_cp_decode_matches_dense_generate(devices):
    """KV-cache decode under context parallelism (CPKVCache, ring prefill,
    distributed-softmax steps) must emit the dense generate's exact greedy
    tokens."""
    import dataclasses

    from solvingpapers_tpu.infer import generate_cp
    from solvingpapers_tpu.sharding import MeshConfig, create_mesh

    cfg = dataclasses.replace(TINY, max_seq_len=64)
    model = Llama(cfg)
    prompt = jax.random.randint(jax.random.key(2), (2, 32), 0, cfg.vocab_size)
    params = model.init({"params": jax.random.key(0)}, prompt)["params"]
    ref = generate(model, params, prompt, jax.random.key(1), max_new_tokens=12)

    cp_model = Llama(dataclasses.replace(cfg, context_parallel=True))
    mesh = create_mesh(MeshConfig(data=1, context=4), jax.devices()[:4])
    out = generate_cp(cp_model, params, prompt, jax.random.key(1), mesh,
                      max_new_tokens=12)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_loss_decreases_with_sgd():
    """Reference parity: llama3 trains with hand-rolled SGD (cell 29)."""
    _, train_toks, _ = load_char_corpus(synthetic_chars=20_000)
    cfg = TrainConfig(
        steps=40, batch_size=8, log_every=100, eval_every=0,
        optimizer=OptimizerConfig(name="sgd", max_lr=0.5, warmup_steps=0,
                                  total_steps=40, grad_clip=1.0,
                                  weight_decay=0.0),
    )
    trainer = Trainer(Llama(TINY), cfg)
    it = lm_batch_iterator(train_toks, 8, TINY.max_seq_len, seed=0)
    b0 = next(it)
    state = trainer.init_state(b0)
    trainer._build_steps()
    state, m0 = trainer._train_step(state, b0)
    first = float(m0["train_loss"])
    for _ in range(cfg.steps):
        state, m = trainer._train_step(state, next(it))
    assert float(m["train_loss"]) < first - 0.3


def test_sharded_train_matches_single_device(devices):
    from solvingpapers_tpu.sharding import MeshConfig, batch_sharding, create_mesh

    _, train_toks, _ = load_char_corpus(synthetic_chars=10_000)
    opt = OptimizerConfig(max_lr=1e-3, warmup_steps=0, total_steps=10)

    def run(mesh_config, devs):
        mesh = create_mesh(mesh_config, devs)
        cfg = TrainConfig(steps=2, batch_size=8, log_every=100, eval_every=0,
                          optimizer=opt)
        trainer = Trainer(Llama(TINY), cfg, mesh=mesh)
        it = lm_batch_iterator(train_toks, 8, TINY.max_seq_len, seed=3,
                               sharding=batch_sharding(mesh))
        b0 = next(it)
        state = trainer.init_state(b0)
        trainer._build_steps()
        losses = []
        for batch in [b0, next(it)]:
            state, m = trainer._train_step(state, batch)
            losses.append(float(m["train_loss"]))
        return losses

    single = run(MeshConfig(data=1, fsdp=1, model=1), devices[:1])
    sharded = run(MeshConfig(data=2, fsdp=2, model=2), devices)
    np.testing.assert_allclose(sharded, single, rtol=2e-4, atol=2e-5)


def test_remat_matches_noremat():
    """remat=True must be numerically identical (it only trades recompute
    for memory) for both forward and gradients."""
    import dataclasses

    base = dataclasses.replace(TINY, remat=False)
    rmt = dataclasses.replace(TINY, remat=True)
    toks = jax.random.randint(jax.random.key(5), (2, 16), 0, TINY.vocab_size)
    params = Llama(base).init({"params": jax.random.key(0)}, toks)["params"]

    def loss(cfg, params):
        logits, _ = Llama(cfg).apply({"params": params}, toks)
        return jnp.sum(logits.astype(jnp.float32) ** 2)

    l0, g0 = jax.value_and_grad(lambda p: loss(base, p))(params)
    l1, g1 = jax.value_and_grad(lambda p: loss(rmt, p))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
