"""Roofline share of the flash attention forward kernel at 16 query heads
on 16 key-value heads of width 128, two sequences of 4,096: the least time
the chip could take for a step's calls, one a layer application and
sequence (operations and bytes from `kernels/flash_gqa.py` through
`kernels/flash_mha_16on16.py`, peaks from `peaks.json`) over the device
time a step of the kernel `flash_mla_fwd`, which under remat runs twice a
layer application."""
from benchmarks.kernels import flash_mha_16on16


def read(obs):
    return flash_mha_16on16.roofline_share(obs, ("flash_mla_fwd",))
