"""Seconds of set-up inside `build_run` (data, model), `create_mesh`,
`trainer_init` and `build_steps`, less JAX's trace, lower and compile events
inside them (program span, through `trace/startup.py`)."""
from benchmarks.trace import startup


def read(obs):
    return startup.part(obs, "build_s")
