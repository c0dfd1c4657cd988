#!/usr/bin/env python3
"""The planted faults of a train cell's correctness check, run by hand on
the chip at the cell's own size:

    python benchmarks/faults.py --workload <name> --fault frozen|half --seed <n>

The cell runs as `run.py` runs it, with one fault planted in the program:
`frozen`, a step that returns its state unchanged (the weights never move),
or `half`, a loss over half of the step's tokens counted twice (half of the
sequences, or of one sequence's tokens where a step has one). It prints the
numbers `correct` compares and has to end with `correct` false: a limit in
the configuration's file lies under what the fault it is there for reads.
The benchmark's own runs never run it; `benchmarks/tests/` plants the same
faults at a tiny size.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FAULTS = ("frozen", "half")


def plant(fault: str, set_attr=setattr) -> None:
    """Plant `fault` in the program; a test hands in `monkeypatch.setattr`."""
    if fault == "frozen":
        from solvingpapers_tpu.train.state import TrainState

        def frozen(self, grads, new_model_state=None):
            return self.replace(step=self.step + 1)

        set_attr(TrainState, "apply_gradients", frozen)
    elif fault == "half":
        import jax.numpy as jnp

        from solvingpapers_tpu.configs import factory

        real_for = factory.loss_fn_for

        def loss_fn_for(cfg):
            real = real_for(cfg)

            def half(model, params, batch, rng, model_state, train):
                axis = 0 if batch["x"].shape[0] > 1 else 1
                n = batch["x"].shape[axis] // 2
                cut = {k: jnp.concatenate([jnp.take(v, jnp.arange(n), axis)] * 2,
                                          axis)
                       for k, v in batch.items()}
                return real(model, params, cut, rng, model_state, train)

            return half

        set_attr(factory, "loss_fn_for", loss_fn_for)
    else:
        raise ValueError(f"fault {fault!r} is not one of {FAULTS}")


def main(argv=None) -> int:
    import argparse

    from benchmarks import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=FAULTS, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    plant(args.fault)
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "0"])


if __name__ == "__main__":
    from benchmarks import harness

    try:
        sys.exit(main())
    except harness.BenchFailure as e:
        print(f"benchmarks/faults.py: {e}", file=sys.stderr)
        sys.exit(1)
