"""Device time a step of the Gated DeltaNet layers: norm, input
projections, output norm, gate and `out_proj` (`L_gdn_proj`), the causal
convolution (`L_gdn_conv`) and the chunked gated delta rule (`L_gdn_core`),
forward, backward and recomputed (device trace through `trace/layers.py`).
None against a program that has no such scopes."""
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, ("L_gdn_proj", "L_gdn_conv", "L_gdn_core"))
