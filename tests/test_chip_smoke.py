"""chip_smoke.py's phase functions at a tiny size on the CPU.

The script has no CPU mode; these tests call its functions with a tiny
DeepSeekV3 (Pallas kernels interpreted) to keep its paths, arguments and
checks working, and pin what the CPU must NOT be able to pass: the
`device` phase, the Mosaic-kernel check and the hardware-PRNG check.
"""

import dataclasses
import importlib.util
import pathlib

import pytest

from solvingpapers_tpu.train.optim import OptimizerConfig

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", pathlib.Path(__file__).parent.parent / "chip_smoke.py"
)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

VOCAB = 512
TINY = dict(vocab_size=VOCAB, dim=64, n_layers=2, n_heads=4, latent_dim=16,
            n_experts=4, dtype="bfloat16")


@pytest.fixture(scope="module")
def token_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("smoke") / "tokens.npy"
    return chip_smoke.write_token_file(str(path), 60_000, VOCAB, seed=0)


def tiny_config(name, token_file, *, steps, model_overrides, **train):
    cfg = chip_smoke.tokens_config(
        name, token_file, steps=steps, log_every=1, eval_every=0, **train
    )
    model = dataclasses.replace(cfg.model, **TINY, **model_overrides)
    return dataclasses.replace(
        cfg, model=model,
        data={**cfg.data, "block_size": model.block_size},
        train=dataclasses.replace(
            cfg.train, batch_size=8,
            optimizer=OptimizerConfig(max_lr=3e-3, total_steps=steps),
        ),
    )


def test_device_phase_refuses_the_cpu():
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.device_phase(1)


def test_token_file_covers_the_vocabulary_and_is_seeded(token_file, tmp_path):
    import numpy as np

    a = np.load(token_file)
    b = np.load(chip_smoke.write_token_file(
        str(tmp_path / "again.npy"), 60_000, VOCAB, seed=0))
    c = np.load(chip_smoke.write_token_file(
        str(tmp_path / "other.npy"), 60_000, VOCAB, seed=1))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.max() < VOCAB and len(np.unique(a)) > VOCAB // 2


def test_train_phase_tiny(token_file, tmp_path):
    cfg = tiny_config(chip_smoke.FLAGSHIP, token_file, steps=12,
                      model_overrides=dict(block_size=64), xla_obs=True)
    out = chip_smoke.train_phase(cfg, str(tmp_path / "train.jsonl"))
    assert out["steps"] == 12 and out["last_loss"] < out["first_loss"]
    assert out["compiles"]["train_step"]["compilations"] == 1
    assert out["drop_fraction"] is not None


def test_flash_phase_runs_but_cannot_pass_interpreted(token_file, tmp_path):
    """On the CPU the kernel is interpreted: the steps run and are finite,
    and the phase then fails at exactly the Mosaic check."""
    cfg = tiny_config(chip_smoke.LONG, token_file, steps=2,
                      model_overrides=dict(block_size=256, rope_dim=16),
                      xla_obs=True)
    assert cfg.model.use_flash and cfg.model.remat
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.flash_train_phase(cfg, str(tmp_path / "flash.jsonl"))


def test_profile_phase_runs_but_finds_no_device_plane(token_file, tmp_path):
    """On the CPU the profiled steps run and the trainer leaves the trace
    and its layer map; the phase then fails at the look for a TPU plane."""
    prof = tmp_path / "profile"
    cfg = tiny_config(chip_smoke.FLAGSHIP, token_file, steps=6,
                      model_overrides=dict(block_size=64),
                      profile_dir=str(prof), profile_steps=(2, 4))
    with pytest.raises(chip_smoke.SmokeFailure, match="/device:TPU:0"):
        chip_smoke.profile_phase(cfg, str(tmp_path))
    assert (prof / "device_scopes.json").exists()
    assert list(prof.glob("plugins/profile/*/*.xplane.pb"))


def test_flash_dropout_check_needs_the_hardware_prng():
    with pytest.raises(ValueError, match="hardware PRNG"):
        chip_smoke.flash_dropout_check(0)


def test_serve_phase_tiny(token_file):
    cfg = tiny_config(chip_smoke.FLAGSHIP, token_file, steps=1,
                      model_overrides=dict(block_size=128))
    out = chip_smoke.serve_phase(cfg, seed=0)
    for pool in ("lane", "paged"):
        assert out[pool]["finish_reasons"] == ["length"]
        assert out[pool]["tokens"] == (len(chip_smoke.PROMPT_LENGTHS)
                                       * chip_smoke.MAX_NEW_TOKENS)
        assert out[pool]["health"] == "healthy"
        assert out[pool]["max_logit_gap"] <= chip_smoke.SERVE_LOGIT_MARGIN


def test_serve_reference_catches_a_wrong_stream(token_file):
    """The float32 full-prefix reference is what decides `serve`: ids that
    are not the model's greedy continuation trail the maximum by far more
    than the margin."""
    cfg = tiny_config(chip_smoke.FLAGSHIP, token_file, steps=1,
                      model_overrides=dict(block_size=128))
    model, params, extra, *_ = chip_smoke.build_serving(cfg, seed=0)
    bogus = [{"prompt": [1, 2, 3, 4], "ids": [5, 6, 7, 8, 9, 10]}]
    gaps = chip_smoke.reference_gaps(model, params, extra, bogus)
    assert gaps.shape == (6,) and gaps.max() > chip_smoke.SERVE_LOGIT_MARGIN


def test_sharded_phase_on_four_virtual_devices(token_file, tmp_path, devices):
    cfg = tiny_config(chip_smoke.FLAGSHIP, token_file, steps=3,
                      model_overrides=dict(block_size=64))
    out = chip_smoke.sharded_phase(cfg, str(tmp_path))
    assert out["mesh"]["data"] == 2 and out["mesh"]["fsdp"] == 2
    assert len(out["param_devices"]) == 4
    assert out["collectives"]["all-gather"] > 0
    assert out["max_loss_diff"] <= chip_smoke.SHARDED_LOSS_TOL
    assert out["registered_prng"] == "rbg"
    assert len(out["registered_prng_losses"]) == 3
