"""Device time a step of the Mamba-2 layers: norm, input projection, step,
gated norm, output projection and residual add (`L_ssm_proj`), the causal
convolution with its bias and SiLU (`L_ssm_conv`) and the chunked
recurrence (`L_ssm_core`), forward, backward and recomputed (device trace
through `trace/layers.py`). None against a program that has no such
scopes."""
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, ("L_ssm_proj", "L_ssm_conv", "L_ssm_core"))
