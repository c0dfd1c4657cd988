"""Roofline share of the flash attention backward kernels (dq and dkv
together) at 32 query heads on 8 key-value heads of width 64: the least
time the chip could take for a step's calls, one of each an attention layer
(operations and bytes from `kernels/flash_gqa.py` through
`kernels/flash_gqa_32on8_w64.py`, peaks from `peaks.json`) over the device
time a step of the kernels `flash_mla_bwd_dq` and `flash_mla_bwd_dkv`."""
from benchmarks.kernels import flash_gqa_32on8_w64


def read(obs):
    return flash_gqa_32on8_w64.roofline_share(
        obs, ("flash_mla_bwd_dq", "flash_mla_bwd_dkv"))
