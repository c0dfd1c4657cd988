"""Driver `train_job`: the registry's training job through
`build_char_lm_run` and `Trainer.fit`, as `cli train` runs it, for a window
of `--seconds`.

One `Trainer` with one compiled step and one state serves everything.
Set-up gives it weights made from `--seed` (the reference's own
`make_weights`), drives it through its first `check_steps` steps with every
loss logged, times a few more to learn the step's length, and then hands
the same object to the window: `fit` for as many steps as fill `--seconds`,
logging at the registry's cadence, nothing fenced between steps by the
benchmark, ended by `block_until_ready` on the last state. After the window
the state is freed and the plain reference follows the same first steps on
the same batches; `correct` holds the two together.

Traffic file: `batch_size`, `corpus_tokens`, `corpus_seed`,
`zipf_exponent`, `check_steps`, `calibration_steps`, `trace_seconds`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import os
import statistics
import time

import numpy as np

from benchmarks import harness


def token_file(vocab: int, n_tokens: int, corpus_seed: int,
               exponent: float) -> str:
    """Token ids over the whole vocabulary, Zipf-distributed under a seeded
    permutation (uniform ids would leave the loss at ln V whatever the model
    did). Written once per checkout; `--seed` draws the crops from it."""
    os.makedirs(harness.WORK_DIR, exist_ok=True)
    path = os.path.join(
        harness.WORK_DIR,
        f"tokens_v{vocab}_n{n_tokens}_s{corpus_seed}_z{exponent}.npy")
    if not os.path.exists(path):
        rng = np.random.default_rng(corpus_seed)
        p = 1.0 / np.arange(1, vocab + 1) ** exponent
        ranks = rng.choice(vocab, size=n_tokens, p=p / p.sum())
        ids = rng.permutation(vocab)[ranks]
        dtype = np.uint16 if vocab <= 1 << 16 else np.uint32
        tmp = f"{path}.{os.getpid()}.tmp.npy"
        np.save(tmp, ids.astype(dtype))
        os.replace(tmp, path)
    return path


def run_config(config: dict, traffic: dict, seed: int):
    """The registry's RunConfig with the configuration file's model
    settings in force, fed from the token file, for this cell's batch."""
    from solvingpapers_tpu.configs import get_config

    cfg = get_config(config["registry"])
    model = dataclasses.replace(cfg.model, **config["model"])
    changed = sorted(k for k, v in config["model"].items()
                     if getattr(cfg.model, k) != v)
    if changed != sorted(config["reduced"]):
        raise harness.BenchFailure(
            f"{config['registry']}: the file changes {changed} from the "
            f"registry, `reduced` says {sorted(config['reduced'])}")
    block = model.block_size
    batch = traffic["batch_size"]
    train = dataclasses.replace(
        cfg.train, batch_size=batch, seed=seed, eval_every=0, ckpt_every=0,
        tokens_per_step=batch * block)
    path = token_file(model.vocab_size, traffic["corpus_tokens"],
                      traffic["corpus_seed"], traffic["zipf_exponent"])
    return dataclasses.replace(
        cfg, model=model, train=train,
        data={"kind": "tokens", "path": path, "block_size": block})


class Feed:
    """The iterator handed to `Trainer.fit`: times every `next` (the
    trainer's wait for data), marks it in the profiler's trace, and keeps
    host copies of the batches the reference has to follow."""

    def __init__(self, it, annotate: bool):
        self.it, self.annotate = it, annotate
        self.waits: list[float] = []
        self.keep = 0
        self.kept: list[tuple[np.ndarray, np.ndarray]] = []

    def __iter__(self):
        return self

    def __next__(self):
        import jax

        span = (jax.profiler.TraceAnnotation("data_wait") if self.annotate
                else contextlib.nullcontext())
        t0 = time.perf_counter()
        with span:
            batch = next(self.it)
        self.waits.append(time.perf_counter() - t0)
        if self.keep > 0:
            self.keep -= 1
            self.kept.append((np.asarray(batch["x"]), np.asarray(batch["y"])))
        return batch


class Rows:
    """The metrics writer handed to `fit`: keeps the logged rows."""

    def __init__(self):
        self.rows: list[dict] = []

    def write(self, step, row):
        self.rows.append({"step": int(step), **row})

    def close(self):
        pass


def first_moment(opt_state):
    """Adam's first moment inside the optimizer's state."""
    import jax

    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise harness.BenchFailure(
            f"expected one Adam state in the optimizer, found {len(found)}")
    return found[0].mu


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """The largest gap between the program's norm of a weight and the
    reference's, against the reference's norm of that weight or of the
    median weight, whichever is larger."""
    median = statistics.median(reference.values())
    worst, where = 0.0, ""
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, median)
        if not gap <= worst:  # NaN wins
            worst, where = gap, name
    return worst, where


def readings(losses, grad_norms, first_grad: dict, delta: dict,
             ref: dict) -> dict:
    """The numbers `correct` compares, of one side (the program, or the
    control put in its place) against the reference's `follow_training`."""
    grad_gap, grad_at = worst_leaf_gap(first_grad, ref["first_grad"])
    delta_gap, delta_at = worst_leaf_gap(delta, ref["delta"])
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(losses, ref["loss"])),
        "grad_norm_gap": max(abs(a - b) / b
                             for a, b in zip(grad_norms, ref["grad_norm"])),
        "first_grad_leaf_gap": grad_gap, "first_grad_leaf_at": grad_at,
        "delta_leaf_gap": delta_gap, "delta_leaf_at": delta_at,
    }


def control_readings(config: dict, traffic: dict, seed: int,
                     quant: str = "int8") -> dict:
    """The control: the reference computed in `quant`, put in the
    program's place and held against the float32 reference on the same
    batches (crops drawn by `seed` from the cell's token file). Needs no
    measured window."""
    adapter = harness.load_module("adapters", config["adapter"])
    reference = harness.load_module("reference", config["reference"])
    cfg = run_config(config, traffic, seed)
    sizes = adapter.sizes_of(cfg.model)
    adam = adapter.adam_of(cfg.train.optimizer)
    tokens = np.load(cfg.data["path"], mmap_mode="r")
    rng = np.random.default_rng(seed)
    block, batch = cfg.model.block_size, cfg.train.batch_size
    batches = []
    for _ in range(traffic["check_steps"]):
        starts = rng.integers(0, len(tokens) - block - 1, size=batch)
        batches.append((
            np.stack([tokens[s:s + block] for s in starts]).astype(np.int32),
            np.stack([tokens[s + 1:s + block + 1]
                      for s in starts]).astype(np.int32)))
    q_block = traffic.get("reference_q_block", 2048)
    ref = reference.follow_training(
        reference.make_weights(seed, sizes), batches, sizes, adam,
        q_block=q_block)
    low = reference.follow_training(
        reference.make_weights(seed, sizes), batches, sizes, adam,
        quant=quant, q_block=q_block)
    return readings(low["loss"], low["grad_norm"], low["first_grad"],
                    low["delta"], ref)


def run(run: harness.Run) -> None:
    import jax

    from solvingpapers_tpu.configs.factory import (
        build_char_lm_run, init_fn_for, loss_fn_for, rules_for,
    )
    from solvingpapers_tpu.sharding import batch_sharding, create_mesh
    from solvingpapers_tpu.train import Trainer

    traffic, config = run.traffic, run.config
    adapter = harness.load_module("adapters", config["adapter"])
    reference = harness.load_module("reference", config["reference"])
    cfg = run_config(config, traffic, run.seed)
    mesh = create_mesh(cfg.train.mesh,
                       devices=jax.devices()[:run.cell["chips"]])
    cfg, model, _, train_iter, _ = build_char_lm_run(
        cfg, sharding=batch_sharding(mesh))
    base = cfg.train
    trainer = Trainer(model, base, loss_fn=loss_fn_for(cfg),
                      init_fn=init_fn_for(cfg), mesh=mesh,
                      rules=rules_for(cfg))
    sizes = adapter.sizes_of(model.cfg)
    adam = adapter.adam_of(base.optimizer)
    batch, block = base.batch_size, model.cfg.block_size
    feed, rows = Feed(train_iter, run.trace), Rows()
    run.phase("build_trainer")

    # weights from the seed, under the program's names; copied, because the
    # step donates its state
    example = {k: np.zeros((batch, block), np.int32) for k in ("x", "y")}
    state = trainer.init_state(example)
    run.phase("trainer_init_state")
    weights = reference.make_weights(run.seed, sizes)
    tree = adapter.to_program_tree(weights, state.params)
    state = state.replace(params=jax.device_put(
        jax.tree.map(lambda a: a.copy(), tree),
        trainer._state_shardings.params))
    del tree
    run.phase("weights_from_seed")

    def fit_to(step: int, log_every: int):
        nonlocal state
        trainer.config = dataclasses.replace(base, steps=step,
                                             log_every=log_every)
        state = trainer.fit(feed, None, writer=rows, state=state)

    # the first steps, each loss logged, through the window's own call
    n_check = traffic["check_steps"]
    feed.keep = n_check
    fit_to(1, 1)
    program_grad = adapter.leaf_norms(first_moment(state.opt_state))
    fit_to(n_check, 1)
    delta = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x - y, a, b))(
        state.params, adapter.to_program_tree(weights, state.params))
    program_delta = adapter.leaf_norms(delta)
    del delta, weights
    checked = [r for r in rows.rows if "train_loss" in r]
    if [r["step"] for r in checked] != list(range(1, n_check + 1)):
        raise harness.BenchFailure(f"expected a logged row for each of the "
                                   f"first {n_check} steps, got {checked}")

    run.phase("first_steps")

    # learn the step's length, then fill the window
    n_cal = traffic["calibration_steps"]
    t0 = time.perf_counter()
    fit_to(n_check + n_cal, base.log_every)
    jax.block_until_ready(state.params)
    step_s = (time.perf_counter() - t0) / n_cal
    n_steps = max(2, int(run.window_seconds / step_s))
    done, n_rows, n_waits = n_check + n_cal, len(rows.rows), len(feed.waits)
    run.phase("calibration")
    run.note(phase="setup", step_s_calibrated=step_s, window_steps=n_steps,
             params=sum(int(np.prod(p.shape))
                        for p in jax.tree.leaves(state.params)))
    with run.window():
        fit_to(done + n_steps, base.log_every)
        jax.block_until_ready(state)
    window_rows = [r for r in rows.rows[n_rows:] if "train_loss" in r]
    losses = [r["train_loss"] for r in window_rows]
    run.attempted = n_steps
    run.failed = 0 if all(math.isfinite(x) for x in losses) else n_steps
    run.obs.update(
        steps=n_steps, tokens_per_step=batch * block, rows=window_rows,
        data_waits=feed.waits[n_waits:], sizes=sizes, seq_len=block,
        batch_size=batch, train_step_module="jit_train_step")
    run.note(phase="window", window_s=run.obs["window_s"], steps=n_steps,
             last_row=window_rows[-1] if window_rows else None)
    if run.trace:
        run.reduce_trace(("data_wait", "PjitFunction(train_step)"),
                         "host_between_steps")

    # free the program's state, then let the reference follow the same steps
    grad_factor = 1.0 / (1.0 - adam.b1)  # mu after one step = (1-b1) * g
    program_grad = {k: v * grad_factor for k, v in program_grad.items()}
    run.phase("window_and_reduction")
    feed.it.close()  # ends the prefetch thread
    del state, trainer, model, train_iter
    gc.collect()
    ref = reference.follow_training(
        reference.make_weights(run.seed, sizes), feed.kept, sizes, adam,
        q_block=traffic.get("reference_q_block", 2048))
    run.phase("reference")
    limits = config["limits"]["train"]
    got = readings([r["train_loss"] for r in checked],
                   [r["grad_norm"] for r in checked],
                   program_grad, program_delta, ref)
    run.note(phase="reference_numbers",
             program_loss=[r["train_loss"] for r in checked],
             reference_loss=ref["loss"],
             program_grad_norm=[r["grad_norm"] for r in checked],
             reference_grad_norm=ref["grad_norm"],
             reference_dropped=ref["dropped"],
             worst_grad_leaf=got["first_grad_leaf_at"],
             worst_delta_leaf=got["delta_leaf_at"])
    for name in ("loss_gap", "grad_norm_gap", "first_grad_leaf_gap",
                 "delta_leaf_gap"):
        run.compare(name, got[name], limits[name])
    rise = (losses[-1] - checked[0]["train_loss"]) if losses and \
        run.failed == 0 else float("nan")
    run.compare("window_loss_rise", rise, limits["window_loss_rise"])
