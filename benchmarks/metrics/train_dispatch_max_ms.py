"""The longest single dispatch of the train step inside the window: the
largest `dispatch_max_ms` of the window's logged rows (program counter)."""
from benchmarks.trace import startup


def read(obs):
    return startup.largest_of_rows(obs, "dispatch_max_ms")
