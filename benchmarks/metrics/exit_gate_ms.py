"""Device time a step of a looped decoder's exit gate (scope `L_exit_gate`:
the gate's product after every pass, the exit distribution, the loss's
weighted sum and its entropy), forward and backward (device trace through
`trace/layers.py`). None against a program that has no such scope."""
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, ("L_exit_gate",))
