"""Multi-host initialization + host-local data utilities.

SURVEY.md §2.3 "Multi-host / elastic" row: the reference has nothing; the
TPU-native path is `jax.distributed.initialize()` over DCN with slice-local
data loading. All meshes in this repo are built from `jax.devices()`
(global across hosts once initialized), so the existing pjit/GSPMD train
steps run multi-host unchanged; the pieces a multi-host launch needs are:

  * initialize() — idempotent wrapper over jax.distributed.initialize,
    reading the standard env (Cloud TPU autodetects; explicit args for
    other clusters);
  * host_batch_slice / host_seed — deterministic per-host data sharding
    (SURVEY.md hard part #6: seed-stable per host).
"""

from __future__ import annotations

import os

import jax

_initialized = False


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Idempotent jax.distributed.initialize. Returns True if a multi-host
    runtime was (or already is) initialized, False for single-process runs.

    On Cloud TPU pods all arguments autodetect; elsewhere pass them or set
    JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID.
    """
    global _initialized
    if _initialized:
        return True
    # NOTE: do not touch jax.process_count()/jax.devices() here — any such
    # call initializes the local XLA backend and forecloses distributed init
    if jax.distributed.is_initialized():
        _initialized = True
        return True
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    explicit = coordinator_address is not None
    autodetectable = (
        "TPU_WORKER_HOSTNAMES" in os.environ
        or "MEGASCALE_COORDINATOR_ADDRESS" in os.environ
    )
    if not explicit and not autodetectable:
        return False  # single-process
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=(
                num_processes
                if num_processes is not None
                else _int_env("JAX_NUM_PROCESSES")
            ),
            # `or` would discard the coordinator's legitimate process_id=0
            process_id=(
                process_id if process_id is not None else _int_env("JAX_PROCESS_ID")
            ),
        )
    except (RuntimeError, ValueError) as e:
        # backend already initialized, or autodetection came up empty (e.g.
        # a single-host dev env that still sets TPU_* vars): stay
        # single-process rather than crash — but an explicit request is a
        # real configuration error
        if explicit:
            raise
        import warnings

        warnings.warn(f"skipping jax.distributed.initialize: {e}", stacklevel=2)
        return False
    _initialized = True
    return True


def _int_env(name: str) -> int | None:
    v = os.environ.get(name)
    return int(v) if v is not None else None


def host_seed(base_seed: int) -> int:
    """Deterministic per-host seed (hard part #6): every host draws a
    disjoint, reproducible batch stream."""
    return base_seed * 1_000_003 + jax.process_index()


def host_batch_slice(global_batch_size: int) -> tuple[int, int]:
    """(host_batch_size, offset) for loading only this host's rows of a
    globally-batched array. Requires divisibility by process_count."""
    n = jax.process_count()
    if global_batch_size % n:
        raise ValueError(
            f"global batch {global_batch_size} not divisible by {n} hosts"
        )
    per = global_batch_size // n
    return per, per * jax.process_index()
