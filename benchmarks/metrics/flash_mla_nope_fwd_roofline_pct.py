"""Roofline share of the flash attention forward kernel at the shape of
latent attention trained decompressed (keys wider than values): the least
time the chip could take for a step's calls, one a layer (operations and
bytes from `kernels/flash_mla_nope.py`, peaks from `peaks.json`) over the
device time a step of the kernel `flash_mla_fwd`, which under remat runs
twice a layer."""
from benchmarks.kernels import flash_mla_nope


def read(obs):
    return flash_mla_nope.roofline_share(obs, ("flash_mla_fwd",))
