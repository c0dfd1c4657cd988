"""Seconds of set-up in which JAX traced a function to a jaxpr (`trace:*`)
or lowered one to StableHLO (`lower:*`), by its own `jax.monitoring` events
(program span, through `trace/startup.py`)."""
from benchmarks.trace import startup


def read(obs):
    return startup.part(obs, "trace_s", "lower_s")
