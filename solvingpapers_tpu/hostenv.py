"""Virtual CPU devices, decided from the environment alone.

A process that has initialised a JAX backend holds the accelerator, and a
child it then starts cannot have it. So the scripts that need an N-device
CPU mesh (`__graft_entry__.dryrun_multichip`)
decide whether to re-exec from `JAX_PLATFORMS` and `XLA_FLAGS` — which is
all JAX itself will look at — and never ask `jax.devices()` first.
Nothing here imports jax.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = re.compile(r"--xla_force_host_platform_device_count=(\d+)")


def virtual_cpu_devices(env=None) -> int:
    """How many CPU devices a JAX process started under `env` will see:
    the forced host device count when the platform is pinned to cpu
    (1 without the flag), 0 when the platform is not pinned to cpu."""
    env = os.environ if env is None else env
    if env.get("JAX_PLATFORMS", "") != "cpu":
        return 0
    m = _COUNT_FLAG.search(env.get("XLA_FLAGS", ""))
    return int(m.group(1)) if m else 1


def virtual_cpu_env(n_devices: int, env=None) -> dict:
    """A copy of `env` under which a new JAX process sees `n_devices`
    virtual CPU devices and no accelerator."""
    env = dict(os.environ if env is None else env)
    flags = _COUNT_FLAG.sub("", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    return env
