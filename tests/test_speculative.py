"""MTP self-speculative decoding (infer/speculative.py): greedy output
must be IDENTICAL to plain generate — speculation changes only how many
forwards it takes. Verified on untrained params (drafts mostly reject:
the all-reject path must still be exact) and the acceptance bookkeeping.
"""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from solvingpapers_tpu import ops
from solvingpapers_tpu.infer import generate, generate_speculative
from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3, DeepSeekV3Config

TINY = DeepSeekV3Config(
    vocab_size=64, block_size=128, dim=32, n_layers=2, n_heads=2,
    latent_dim=8, rope_dim=8, pe_scale=0.02, n_experts=4, top_experts=2,
    dropout=0.0, attn_dropout=0.0, mtp_heads=1,
)


def _setup(seed=0, prompt_len=9):
    model = DeepSeekV3(TINY)
    prompt = jax.random.randint(
        jax.random.key(seed), (1, prompt_len), 0, TINY.vocab_size
    )
    variables = model.init({"params": jax.random.key(seed + 1)}, prompt,
                           return_mtp=True)
    extra = {"moe_state": variables["moe_state"]}
    return model, variables["params"], prompt, extra


@pytest.mark.parametrize("new", [5, 16])
def test_speculative_equals_plain_greedy(new):
    model, params, prompt, extra = _setup(prompt_len=9)
    plain = generate(model, params, prompt, jax.random.key(9),
                     max_new_tokens=new, sampler=ops.sample_greedy,
                     extra_variables=extra, max_len=prompt.shape[1] + new + 2)
    spec, stats = generate_speculative(
        model, params, prompt, max_new_tokens=new, extra_variables=extra,
    )
    np.testing.assert_array_equal(np.asarray(spec[:, : prompt.shape[1] + new]),
                                  np.asarray(plain))
    f = int(stats["forwards"])
    a = int(stats["accepted"])
    # bookkeeping: each forward commits 1 + accepted tokens, first token
    # comes from prefill; the loop may overshoot by one accepted token
    assert f + a + 1 in (new, new + 1), (f, a)
    assert 0 <= a <= f


@pytest.mark.slow
def test_speculative_accepts_on_predictable_stream():
    """A prompt the model continues deterministically after a short
    training burst should accept drafts (>0) — the speedup mechanism is
    live, not just the fallback path. Marked slow (training-fit-backed):
    tier-1 keeps draft-verify token equality at every level (the
    equality tests here, the CLI regression, the serving matrix in
    tests/test_spec.py)."""
    from solvingpapers_tpu.data.batches import lm_batch_iterator
    from solvingpapers_tpu.train import OptimizerConfig, TrainConfig, Trainer
    from solvingpapers_tpu.train.objectives import dsv3_init_fn, dsv3_loss_fn

    model = DeepSeekV3(TINY)
    # a trivially periodic corpus: the model memorizes it fast, so the MTP
    # head's 2-ahead predictions line up with the main model's argmax
    toks = np.tile(np.arange(8), 4000)
    tcfg = TrainConfig(
        steps=150, batch_size=8, log_every=1000, eval_every=0,
        optimizer=OptimizerConfig(max_lr=3e-3, warmup_steps=10,
                                  total_steps=150),
    )
    trainer = Trainer(model, tcfg, loss_fn=dsv3_loss_fn, init_fn=dsv3_init_fn)
    state = trainer.fit(lm_batch_iterator(toks, 8, 32, seed=0))
    params = jax.device_get(state.params)
    extra = {"moe_state": jax.device_get(state.model_state)["moe_state"]}

    prompt = jnp.asarray(np.tile(np.arange(8), 2)[None, :], jnp.int32)
    new = 24
    plain = generate(model, params, prompt, jax.random.key(0),
                     max_new_tokens=new, sampler=ops.sample_greedy,
                     extra_variables=extra, max_len=prompt.shape[1] + new + 2)
    spec, stats = generate_speculative(
        model, params, prompt, max_new_tokens=new, extra_variables=extra,
    )
    np.testing.assert_array_equal(np.asarray(spec[:, : prompt.shape[1] + new]),
                                  np.asarray(plain))
    assert int(stats["accepted"]) > 0, dict(stats)
    assert int(stats["forwards"]) < new  # strictly fewer forwards


TINY2 = dc.replace(TINY, mtp_heads=2)


@pytest.mark.parametrize("new", [5, 16])
def test_speculative_2draft_equals_plain_greedy(new):
    """Chained 2-head drafts: greedy output identical to plain generate,
    even when untrained drafts mostly reject."""
    model = DeepSeekV3(TINY2)
    prompt = jax.random.randint(jax.random.key(0), (1, 9), 0, TINY2.vocab_size)
    variables = model.init({"params": jax.random.key(1)}, prompt,
                           return_mtp=True)
    extra = {"moe_state": variables["moe_state"]}
    params = variables["params"]
    plain = generate(model, params, prompt, jax.random.key(9),
                     max_new_tokens=new, sampler=ops.sample_greedy,
                     extra_variables=extra, max_len=prompt.shape[1] + new + 3)
    spec, stats = generate_speculative(
        model, params, prompt, max_new_tokens=new, extra_variables=extra,
        n_drafts=2,
    )
    np.testing.assert_array_equal(np.asarray(spec[:, : prompt.shape[1] + new]),
                                  np.asarray(plain))
    f, a = int(stats["forwards"]), int(stats["accepted"])
    # each forward commits 1 + (accepted this iter); overshoot <= 2
    assert new <= f + a + 1 <= new + 2, (f, a)
    assert 0 <= a <= 2 * f


@pytest.mark.slow
def test_speculative_2draft_beats_single_on_predictable_stream():
    """On a memorized periodic stream the chained drafts must push
    tokens/forward ABOVE the single-draft cap of 2. Marked slow (a
    training fit feeds a PERFORMANCE acceptance): 2-draft token
    equality stays tier-1 (`test_speculative_2draft_equals_plain_greedy`
    + the full-context edge)."""
    from solvingpapers_tpu.data.batches import lm_batch_iterator
    from solvingpapers_tpu.train import OptimizerConfig, TrainConfig, Trainer
    from solvingpapers_tpu.train.objectives import dsv3_init_fn, dsv3_loss_fn

    model = DeepSeekV3(TINY2)
    toks = np.tile(np.arange(8), 4000)
    tcfg = TrainConfig(
        steps=150, batch_size=8, log_every=1000, eval_every=0,
        optimizer=OptimizerConfig(max_lr=3e-3, warmup_steps=10,
                                  total_steps=150),
    )
    trainer = Trainer(model, tcfg, loss_fn=dsv3_loss_fn, init_fn=dsv3_init_fn)
    state = trainer.fit(lm_batch_iterator(toks, 8, 32, seed=0))
    params = jax.device_get(state.params)
    extra = {"moe_state": jax.device_get(state.model_state)["moe_state"]}

    prompt = jnp.asarray(np.tile(np.arange(8), 2)[None, :], jnp.int32)
    new = 24
    plain = generate(model, params, prompt, jax.random.key(0),
                     max_new_tokens=new, sampler=ops.sample_greedy,
                     extra_variables=extra, max_len=prompt.shape[1] + new + 3)
    spec, stats = generate_speculative(
        model, params, prompt, max_new_tokens=new, extra_variables=extra,
        n_drafts=2,
    )
    np.testing.assert_array_equal(np.asarray(spec[:, : prompt.shape[1] + new]),
                                  np.asarray(plain))
    f, a = int(stats["forwards"]), int(stats["accepted"])
    tpf = 1 + a / f
    assert tpf > 2.0, dict(stats)  # beyond the single-draft cap


def test_speculative_2draft_full_context_edge():
    """Full-context decode (s0 + new + n_drafts - 1 == block_size): the
    cache must NOT clamp the final 3-token chunk's write (a clamped
    dynamic_update_slice would shift the write one slot left and corrupt a
    committed token's latent — code-review r5 finding)."""
    cfg = dc.replace(TINY2, block_size=48)
    model = DeepSeekV3(cfg)
    s0 = 16
    new = cfg.block_size - s0 - 1  # 31: exactly at the position limit
    prompt = jax.random.randint(jax.random.key(2), (1, s0), 0, cfg.vocab_size)
    variables = model.init({"params": jax.random.key(1)}, prompt,
                           return_mtp=True)
    extra = {"moe_state": variables["moe_state"]}
    params = variables["params"]
    plain = generate(model, params, prompt, jax.random.key(9),
                     max_new_tokens=new, sampler=ops.sample_greedy,
                     extra_variables=extra)
    spec, _ = generate_speculative(model, params, prompt, max_new_tokens=new,
                                   extra_variables=extra, n_drafts=2)
    np.testing.assert_array_equal(np.asarray(spec[:, : s0 + new]),
                                  np.asarray(plain[:, : s0 + new]))
    # one past the limit must raise, not silently clamp
    with pytest.raises(ValueError, match="max positions"):
        generate_speculative(model, params, prompt, max_new_tokens=new + 1,
                             extra_variables=extra, n_drafts=2)


def test_cli_sample_speculative_matches_plain_greedy(tmp_path, capsys):
    """`cli sample --speculative` (the user-facing wiring of
    infer/speculative.py) prints EXACTLY the text of `--greedy` — the
    CLI-level token-equality regression for the MTP path, pinned end to
    end through config registry + tokenizer + restore plumbing."""
    from solvingpapers_tpu.cli import main as cli_main
    from solvingpapers_tpu.configs import register
    from solvingpapers_tpu.configs.registry import (
        OptimizerConfig,
        RunConfig,
        TrainConfig,
    )

    @register("dsv3_mtp_clitest")
    def _cfg() -> RunConfig:
        return RunConfig(
            name="dsv3_mtp_clitest",
            model_family="deepseekv3",
            model=TINY,  # the f32 tiny config the equality tests use
            train=TrainConfig(
                steps=1, batch_size=2, log_every=1, eval_every=0,
                optimizer=OptimizerConfig(max_lr=1e-3, total_steps=1),
            ),
            data={"kind": "char", "path": None, "block_size": 32},
            notes="test-only tiny MTP config",
        )

    corpus = tmp_path / "corpus.txt"
    corpus.write_text("abcdefgh " * 400)
    common = ["sample", "--config", "dsv3_mtp_clitest",
              "--data-path", str(corpus), "--prompt", "abcab",
              "--max-new-tokens", "16", "--seed", "3"]
    base = common + ["--greedy"]
    assert cli_main(base) == 0
    plain = capsys.readouterr().out
    assert cli_main(base + ["--speculative"]) == 0
    cap = capsys.readouterr()
    assert cap.out == plain, "--speculative changed the greedy text"
    # bad invocations exit with a message, never a traceback
    assert cli_main(common + ["--speculative"]) == 1  # demands --greedy
    assert cli_main(base + ["--speculative", "--spec-drafts", "2"]) == 1


def test_speculative_rejects_bad_inputs():
    model, params, prompt, extra = _setup()
    with pytest.raises(ValueError, match="batch 1"):
        generate_speculative(model, params, jnp.tile(prompt, (2, 1)),
                             max_new_tokens=4, extra_variables=extra)
    no_mtp = DeepSeekV3(dc.replace(TINY, mtp_heads=0))
    with pytest.raises(ValueError, match="mtp_heads"):
        generate_speculative(no_mtp, params, prompt, max_new_tokens=4,
                             extra_variables=extra)
