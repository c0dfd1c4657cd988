"""Median device duration of the decode program's executions (one decode
block for every slot), from the `XLA Modules` line of the profiler trace."""
from benchmarks.trace.xplane import median_module_ms


def read(obs):
    return median_module_ms(obs.get("trace"), obs.get("decode_module"))
