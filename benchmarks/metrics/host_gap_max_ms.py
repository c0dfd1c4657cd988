"""The longest stretch inside the window between the return of one
dispatch and the start of the next, less what the loop spent blocked on the
device in it: the largest `host_gap_max_ms` of the window's logged rows
(program counter)."""
from benchmarks.trace import startup


def read(obs):
    return startup.largest_of_rows(obs, "host_gap_max_ms")
