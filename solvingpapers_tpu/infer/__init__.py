"""Jitted inference: preallocated KV/latent caches + prefill/decode loops."""

from solvingpapers_tpu.metrics.trace import begin as _begin

_imported = _begin("import:infer")
from solvingpapers_tpu.infer.cache import (
    CPKVCache,
    CPLatentCache,
    KVCache,
    LatentCache,
    update_kv_cache,
    update_latent_cache,
)
from solvingpapers_tpu.infer.decode import generate, generate_cp
from solvingpapers_tpu.infer.speculative import generate_speculative  # noqa: E402,F401

_imported()
