"""Roofline share of the flash attention backward kernels (dq and dkv
together) at the shape of latent attention trained decompressed: the least
time the chip could take for a step's calls (operations and bytes from
`kernels/flash_mla_nope.py`, peaks from `peaks.json`) over the device time a
step of the kernels `flash_mla_bwd_dq` and `flash_mla_bwd_dkv`."""
from benchmarks.kernels import flash_mla_nope


def read(obs):
    return flash_mla_nope.roofline_share(
        obs, ("flash_mla_bwd_dq", "flash_mla_bwd_dkv"))
