"""Seconds of set-up inside the program's `import:<package>` spans (their
union: `solvingpapers_tpu` and its sub-packages, with what they import;
program span, through `trace/startup.py`)."""
from benchmarks.trace import startup


def read(obs):
    return startup.part(obs, "import_s")
