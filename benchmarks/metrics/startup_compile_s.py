"""Seconds of set-up in JAX's `backend_compile_duration` events
(`compile:*`): the compile, or on a hit of the persistent cache the read and
the executable's load (program span, through `trace/startup.py`)."""
from benchmarks.trace import startup


def read(obs):
    return startup.part(obs, "compile_s")
