"""Programs that the persistent compile cache did not hold before the
window: JAX's `/jax/compilation_cache/cache_misses`, counted by the
program's listener (program counter, through `trace/startup.py`)."""
from benchmarks.trace import startup


def read(obs):
    return startup.part(obs, "cache_misses")
