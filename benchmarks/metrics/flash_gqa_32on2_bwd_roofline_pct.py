"""Roofline share of the flash attention backward kernels (dq and dkv
together) at 32 query heads on 2 key-value heads of width 128: the least
time the chip could take for a step's calls (operations and bytes from
`kernels/flash_gqa.py` through `kernels/flash_gqa_32on2.py`, peaks from
`peaks.json`) over the device time a step of the kernels `flash_mla_bwd_dq`
and `flash_mla_bwd_dkv`."""
from benchmarks.kernels import flash_gqa_32on2


def read(obs):
    return flash_gqa_32on2.roofline_share(
        obs, ("flash_mla_bwd_dq", "flash_mla_bwd_dkv"))
