"""Device time of the Mosaic kernels (the flash attention calls) over the
device's busy time in the traced window."""
from benchmarks.kernels import flash_mla


def read(obs):
    tr = obs.get("trace")
    if tr is None:
        return None
    t = sum(sum(d) for name, d in tr.ops.items() if flash_mla.is_mosaic(name))
    return 100.0 * t / tr.busy_s if t > 0 else None
