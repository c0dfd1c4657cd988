"""Seconds of set-up that the program's own spans account for: the union
of every span the other `startup_*` metrics read. `setup_s` less this is the
benchmark's own and the interpreter's (program span, through
`trace/startup.py`)."""
from benchmarks.trace import startup


def read(obs):
    return startup.part(obs, "program_s")
