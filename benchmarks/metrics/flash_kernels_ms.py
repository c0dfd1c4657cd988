"""Device time a step of the three flash attention kernels, found by the
`name=` of their `pallas_call`s (`flash_mla_fwd`, `flash_mla_bwd_dq`,
`flash_mla_bwd_dkv`). It has to equal the Mosaic operations' time a step
that `flash_share_pct` is made from, which finds them by their
`custom_call_target`: the check that names and shapes agree."""
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, layers.KERNELS)
