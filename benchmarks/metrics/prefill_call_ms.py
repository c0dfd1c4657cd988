"""Median device duration of the prefill program's executions (`XLA
Modules` line of the profiler trace), over all its bucket shapes."""
from benchmarks.trace.xplane import median_module_ms


def read(obs):
    return median_module_ms(obs.get("trace"), obs.get("prefill_module"))
