"""Share of device 0's idle seconds inside the window that lie, by overlap,
in one of the train loop's own sections (`data_wait`, `train_dispatch`,
`log_fetch`, `log_write`, `eval`, `callback`, `checkpoint`; the step's
annotation alone is no account), from the device trace through
`trace/host.py`."""
from benchmarks.trace import host


def read(obs):
    return host.read(obs)
