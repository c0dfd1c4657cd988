"""Roofline share of the flash attention backward kernels (dq and dkv together): the
least time the chip could take for the calls in the traced window
(operations and bytes from `kernels/flash_mla.py`, peaks from `peaks.json`)
over the time the trace gives them. At 16,384 tokens every call is bound by
compute, not by memory (`flash_mla.least_seconds` says which)."""
from benchmarks.kernels import flash_mla


def read(obs):
    return flash_mla.roofline_share(obs, ("bwd_dq", "bwd_dkv"))
