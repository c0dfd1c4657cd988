"""Median device duration of the train step's program (`XLA Modules` line
of the profiler trace)."""
from benchmarks.trace.xplane import median_module_ms


def read(obs):
    return median_module_ms(obs.get("trace"), obs.get("train_step_module"))
