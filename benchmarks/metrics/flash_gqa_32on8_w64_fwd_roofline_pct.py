"""Roofline share of the flash attention forward kernel at 32 query heads
on 8 key-value heads of width 64: the least time the chip could take for a
step's calls, one an attention layer (operations and bytes from
`kernels/flash_gqa.py` through `kernels/flash_gqa_32on8_w64.py`, peaks from
`peaks.json`) over the device time a step of the kernel `flash_mla_fwd`,
which runs once a layer where the layer's remat keeps its results."""
from benchmarks.kernels import flash_gqa_32on8_w64


def read(obs):
    return flash_gqa_32on8_w64.roofline_share(obs, ("flash_mla_fwd",))
