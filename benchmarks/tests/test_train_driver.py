"""The train driver end to end at a tiny size on the CPU: a sound run is
`correct`, a step that returns its state unchanged is not, the control (the
reference in int8 in the program's place) reads several times a sound run's
numbers, and the real entry point refuses to run without a TPU."""

import os
import subprocess
import sys

import pytest

from benchmarks import harness
from benchmarks.tests.tiny import tiny_files, tiny_run

CELLS = ["dsv3_tinystories.train_64x256", "dsv3_long.train_16k"]
# a tiny model's numbers, not the chip's: only the order of magnitude of the
# limits in the configurations' files carries over
TINY_LIMITS = {"train": {"loss_gap": 5e-3, "grad_norm_gap": 2e-2,
                         "first_grad_leaf_gap": 3e-2, "delta_leaf_gap": 1e-2}}


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    monkeypatch.setattr(harness, "peak_bytes", lambda n: (1, 1))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_prints_the_contract_line(cell):
    run = tiny_run(cell, limits=TINY_LIMITS)
    line = run.result()
    assert line["correct"] is True, run.checks
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["attempted"] == run.obs["steps"] >= 2 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(c["value"] <= c["limit"] for c in run.checks)
    assert {c["check"] for c in run.checks} == {
        "loss_gap", "grad_norm_gap", "first_grad_leaf_gap", "delta_leaf_gap",
        "window_loss_rise"}


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from solvingpapers_tpu.train.state import TrainState

    def frozen(self, grads, new_model_state=None):
        return self.replace(step=self.step + 1)

    monkeypatch.setattr(TrainState, "apply_gradients", frozen)
    run = tiny_run(CELLS[0], limits=TINY_LIMITS)
    assert run.result()["correct"] is False
    failed = {c["check"] for c in run.checks if not c["ok"]}
    assert "delta_leaf_gap" in failed


def test_part_of_the_batch_left_out_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from solvingpapers_tpu.train import objectives

    real = objectives.dsv3_loss_fn

    def half(model, params, batch, rng, model_state, train):
        n = batch["x"].shape[0] // 2
        cut = {k: jnp.concatenate([v[:n], v[:n]]) for k, v in batch.items()}
        return real(model, params, cut, rng, model_state, train)

    import solvingpapers_tpu.configs.factory as factory

    monkeypatch.setattr(factory, "loss_fn_for", lambda cfg: half)
    run = tiny_run(CELLS[0], limits=TINY_LIMITS)
    assert run.result()["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_int8_control_fails_where_a_sound_run_passes(cell):
    _, _, config, traffic = tiny_files(cell)
    driver = harness.load_module("drivers", traffic["driver"])
    got = driver.control_readings(config, traffic, seed=5)
    lim = TINY_LIMITS["train"]
    over = [k for k in lim if got[k] > lim[k]]
    assert over, got


def test_entry_point_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, cwd=harness.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout
