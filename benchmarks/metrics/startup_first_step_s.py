"""Seconds of the process's first `fit_first_step` (its first dispatch of
the train step to the fetch that fences it), less JAX's trace, lower and
compile events inside it (program span, through `trace/startup.py`)."""
from benchmarks.trace import startup


def read(obs):
    return startup.part(obs, "first_step_s")
