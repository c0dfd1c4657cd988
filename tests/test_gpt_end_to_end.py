"""End-to-end slice tests: GPT char-LM training, cached decode, sharding.

The SURVEY.md §4 contract: loss-goes-down smoke training, cache-equivalence
(decode with cache == full-prefix forward — which the reference fails),
and sharded-vs-single-device numerical equality on the virtual 8-device mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from solvingpapers_tpu import ops
from solvingpapers_tpu.data import load_char_corpus
from solvingpapers_tpu.data.batches import lm_batch_iterator
from solvingpapers_tpu.infer import generate
from solvingpapers_tpu.models.gpt import GPT, GPTConfig
from solvingpapers_tpu.sharding import MeshConfig, create_mesh, batch_sharding
from solvingpapers_tpu.train import Trainer, TrainConfig, OptimizerConfig

TINY = GPTConfig(vocab_size=64, block_size=32, dim=32, n_layers=2, n_heads=2, dropout=0.0)


def tiny_corpus():
    tok, train, val = load_char_corpus(synthetic_chars=20_000)
    assert tok.vocab_size <= TINY.vocab_size
    return tok, train, val


def test_gpt_loss_decreases():
    _, train_toks, _ = tiny_corpus()
    cfg = TrainConfig(
        steps=30,
        batch_size=8,
        log_every=100,
        eval_every=0,
        optimizer=OptimizerConfig(max_lr=1e-2, warmup_steps=5, total_steps=30),
    )
    trainer = Trainer(GPT(TINY), cfg)
    it = lm_batch_iterator(train_toks, 8, TINY.block_size, seed=0)
    first_batch = next(it)
    state = trainer.init_state(first_batch)
    trainer._build_steps()
    state, m0 = trainer._train_step(state, first_batch)
    losses = [float(m0["train_loss"])]
    for _ in range(cfg.steps):
        state, m = trainer._train_step(state, next(it))
        losses.append(float(m["train_loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[:3] + losses[-3:]


def test_scan_steps_window_equals_sequential_steps():
    """TrainConfig.scan_steps runs K steps per dispatch via lax.scan; the
    result must be bit-identical to K sequential _train_step calls (same
    per-step rng fold on state.step)."""
    _, train_toks, _ = tiny_corpus()
    K = 4

    def make(scan_steps):
        cfg = TrainConfig(
            steps=0, batch_size=8, log_every=100, eval_every=0,
            scan_steps=scan_steps,
            optimizer=OptimizerConfig(max_lr=1e-2, warmup_steps=5, total_steps=30),
        )
        t = Trainer(GPT(TINY), cfg)
        it = lm_batch_iterator(train_toks, 8, TINY.block_size, seed=0)
        s = t.init_state(next(it))
        t._build_steps()
        return t, s

    it = lm_batch_iterator(train_toks, 8, TINY.block_size, seed=0)
    next(it)  # consumed by init in both trainers
    batches = [next(it) for _ in range(K)]

    t_seq, s_seq = make(1)
    for b in batches:
        s_seq, m_seq = t_seq._train_step(s_seq, b)

    t_scan, s_scan = make(K)
    window = jax.tree.map(lambda *xs: np.stack(xs), *batches)
    s_scan, m_scan = t_scan._train_step_scan(s_scan, window)

    assert int(s_scan.step) == int(s_seq.step)
    np.testing.assert_allclose(
        float(m_scan["train_loss"]), float(m_seq["train_loss"]), rtol=1e-6
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6),
        jax.device_get(s_scan.params), jax.device_get(s_seq.params),
    )


def test_fit_with_scan_steps_smoke():
    """fit() drives scan windows (incl. a ragged single-step tail) and the
    loss still goes down."""
    _, train_toks, _ = tiny_corpus()
    cfg = TrainConfig(
        steps=22, batch_size=8, log_every=4, eval_every=0, scan_steps=4,
        optimizer=OptimizerConfig(max_lr=1e-2, warmup_steps=5, total_steps=30),
    )
    trainer = Trainer(GPT(TINY), cfg)
    it = lm_batch_iterator(train_toks, 8, TINY.block_size, seed=0)
    rows = []

    class Cap:
        def write(self, step, metrics):
            rows.append((step, metrics))

    state = trainer.fit(it, writer=Cap())
    assert int(state.step) == 22
    losses = [m["train_loss"] for _, m in rows if "train_loss" in m]
    assert losses[-1] < losses[0], rows


def test_fit_rejects_misaligned_scan_cadence():
    cfg = TrainConfig(steps=8, batch_size=8, log_every=3, eval_every=0,
                      scan_steps=4)
    trainer = Trainer(GPT(TINY), cfg)
    it = lm_batch_iterator(tiny_corpus()[1], 8, TINY.block_size, seed=0)
    with pytest.raises(ValueError, match="multiple of scan_steps"):
        trainer.fit(it)


def test_cached_decode_equals_full_forward():
    """Greedy decode through the KV cache must match recompute-from-scratch."""
    model = GPT(TINY)
    rng = jax.random.key(0)
    prompt = jax.random.randint(rng, (2, 5), 0, TINY.vocab_size)
    params = model.init({"params": rng}, prompt)["params"]

    out = generate(model, params, prompt, rng, max_new_tokens=8)
    assert out.shape == (2, 13)
    np.testing.assert_array_equal(np.asarray(out[:, :5]), np.asarray(prompt))

    # reference: greedy loop recomputing the full prefix each step (no cache)
    # (one compiled forward a prefix length, not one program an operation)
    forward = jax.jit(lambda toks: model.apply(
        {"params": params}, toks, deterministic=True))
    toks = prompt
    for _ in range(8):
        logits, _ = forward(toks)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(toks))


@pytest.mark.parametrize(
    "mesh_cfg",
    [
        MeshConfig(data=8, fsdp=1, model=1),
        MeshConfig(data=1, fsdp=8, model=1),
        MeshConfig(data=2, fsdp=2, model=2),
    ],
    ids=["dp8", "fsdp8", "dp2_fsdp2_tp2"],
)
def test_sharded_train_matches_single_device(mesh_cfg, devices):
    """3 train steps on a sharded mesh == 3 steps on a 1-device mesh."""
    _, train_toks, _ = tiny_corpus()
    opt = OptimizerConfig(max_lr=1e-3, warmup_steps=0, total_steps=10)

    def run(mesh_config, devs):
        mesh = create_mesh(mesh_config, devs)
        cfg = TrainConfig(steps=3, batch_size=8, log_every=100, eval_every=0,
                          optimizer=opt)
        trainer = Trainer(GPT(TINY), cfg, mesh=mesh)
        it = lm_batch_iterator(
            train_toks, 8, TINY.block_size, seed=7, sharding=batch_sharding(mesh)
        )
        b0 = next(it)
        state = trainer.init_state(b0)
        trainer._build_steps()
        losses = []
        state, m = trainer._train_step(state, b0)
        losses.append(float(m["train_loss"]))
        for _ in range(2):
            state, m = trainer._train_step(state, next(it))
            losses.append(float(m["train_loss"]))
        return losses

    single = run(MeshConfig(data=1, fsdp=1, model=1), devices[:1])
    sharded = run(mesh_cfg, devices)
    np.testing.assert_allclose(sharded, single, rtol=2e-4, atol=2e-5)


def test_generate_with_sampler_topk_runs():
    model = GPT(TINY)
    rng = jax.random.key(1)
    prompt = jnp.zeros((1, 3), jnp.int32)
    params = model.init({"params": rng}, prompt)["params"]
    import functools

    out = generate(
        model, params, prompt, rng, max_new_tokens=5,
        sampler=functools.partial(ops.sample_top_k, k=5, temperature=0.8),
    )
    assert out.shape == (1, 8)
    assert int(jnp.max(out)) < TINY.vocab_size


def test_generate_rejects_past_block_size():
    model = GPT(TINY)
    rng = jax.random.key(2)
    prompt = jnp.zeros((1, 30), jnp.int32)
    params = model.init({"params": rng}, prompt)["params"]
    with pytest.raises(ValueError, match="max positions"):
        generate(model, params, prompt, rng, max_new_tokens=10)  # 40 > block 32


def test_sliding_window_includes_last_start():
    from solvingpapers_tpu.data.batches import sliding_window_split

    toks = np.arange(100)
    x, y = sliding_window_split(toks, block_size=10, stride=1)
    assert x[-1][0] == 89 and y[-1][-1] == 99
    np.testing.assert_array_equal(y, x + 1)


def test_generate_eos_early_stop():
    """deepseekv3 cell 40's stop-on-EOS, static-shape form: after a
    sequence samples EOS every later position is EOS."""
    model = GPT(TINY)
    rng = jax.random.key(3)
    prompt = jnp.zeros((2, 3), jnp.int32)
    params = model.init({"params": rng}, prompt)["params"]

    # immediate EOS: every generated position must be the EOS id
    always_eos = lambda logits, key: jnp.full(  # noqa: E731
        (logits.shape[0],), 7, jnp.int32
    )
    out = generate(model, params, prompt, rng, max_new_tokens=6,
                   sampler=always_eos, eos_id=7)
    np.testing.assert_array_equal(np.asarray(out[:, 3:]), 7)

    # stochastic mid-sequence EOS (seeded -> deterministic): each step emits
    # EOS with p=0.4, so rows hit EOS mid-sequence; after the first hit the
    # done-propagation must pin every later position to EOS
    def sometimes_eos(logits, key):
        hit = jax.random.bernoulli(key, 0.4, (logits.shape[0],))
        return jnp.where(hit, 7, jnp.argmax(logits, -1)).astype(jnp.int32)

    out2 = generate(model, params, prompt, rng, max_new_tokens=10,
                    sampler=sometimes_eos, eos_id=7)
    gen = np.asarray(out2[:, 3:])
    mid_hits = 0
    for row in gen:
        hits = np.where(row == 7)[0]
        if hits.size and hits[0] < len(row) - 1:
            mid_hits += 1
            assert np.all(row[hits[0]:] == 7), row
    assert mid_hits > 0, gen  # the property must actually be exercised
