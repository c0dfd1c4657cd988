"""Seconds of set-up inside `Trainer.init_state` (`init_eval_shape`,
`init_jit`), less JAX's trace, lower and compile events inside it (program
span, through `trace/startup.py`)."""
from benchmarks.trace import startup


def read(obs):
    return startup.part(obs, "init_state_s")
