"""Roofline share of the flash attention forward kernel at a grouped-query
shape: the least time the chip could take for a step's calls (operations and
bytes from `kernels/flash_gqa.py`, peaks from `peaks.json`) over the device
time a step of the kernel `flash_mla_fwd`."""
from benchmarks.kernels import flash_gqa


def read(obs):
    return flash_gqa.roofline_share(obs, ("flash_mla_fwd",))
