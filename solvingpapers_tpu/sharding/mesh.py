"""Device-mesh construction with standardized axis names.

Axes (SURVEY.md §2.3/§5):
  data   — pure data parallelism (batch split, params replicated)
  fsdp   — fully-sharded data parallelism (batch AND params split; XLA
           all-gathers params on use, reduce-scatters grads)
  model  — tensor parallelism (attention heads / FFN hidden)
  expert — expert parallelism for MoE all_to_all dispatch

Batches are sharded over (data, fsdp) jointly; parameters over
(fsdp, model); MoE experts over expert; the sequence axis over context
(ring attention / Ulysses — both in sharding/ring_attention.py). On a
single chip every axis has size 1 and all of this compiles to a no-op.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from solvingpapers_tpu.metrics.trace import run_span

MESH_AXES = ("data", "fsdp", "model", "expert", "context", "pipe")

# The mesh a GSPMD-partitioned model is currently tracing under (set by the
# Trainer around its non-shard_map step/init bodies). pallas_call is opaque
# to GSPMD — without this, a use_flash model under a >1-device mesh would
# silently all-gather its attention operands; with it, apply_flash_attention
# routes through the shard_map-wrapped kernels.sharded_flash_attention.
# Inside CP/PP shard_map bodies this stays None: operands there are already
# local, so the direct kernel call is correct.
_AMBIENT_MESH: contextvars.ContextVar[Mesh | None] = contextvars.ContextVar(
    "ambient_gspmd_mesh", default=None
)


@contextlib.contextmanager
def ambient_mesh(mesh: Mesh | None):
    """Mark `mesh` as the GSPMD mesh for code traced within this scope."""
    token = _AMBIENT_MESH.set(mesh)
    try:
        yield
    finally:
        _AMBIENT_MESH.reset(token)


def get_ambient_mesh() -> Mesh | None:
    return _AMBIENT_MESH.get()


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Axis sizes; -1 means 'absorb all remaining devices' (exactly one allowed)."""

    data: int = -1
    fsdp: int = 1
    model: int = 1
    expert: int = 1
    context: int = 1
    pipe: int = 1

    def resolve(self, n_devices: int) -> tuple[int, ...]:
        sizes = [self.data, self.fsdp, self.model, self.expert, self.context,
                 self.pipe]
        wild = [i for i, s in enumerate(sizes) if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one -1 axis allowed, got {sizes}")
        fixed = math.prod(s for s in sizes if s != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(f"{n_devices} devices not divisible by {fixed}")
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(f"mesh {sizes} needs {fixed} devices, have {n_devices}")
        return tuple(sizes)


@run_span("create_mesh")  # `jax.devices()` may be the backend's start
def create_mesh(
    config: MeshConfig | None = None, devices: list | None = None
) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    config = config or MeshConfig()
    sizes = config.resolve(len(devices))
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, MESH_AXES)


def mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    """{axis_name: size} for a mesh — the lookup the engines and the
    mesh observatory repeat (pipe depth, data-shard count, ...)."""
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_spec(extra_dims: int = 1, context: bool = False) -> P:
    """PartitionSpec for a batch-leading array: batch over (data, fsdp);
    with `context`, the next (sequence) dim over the 'context' axis — the
    layout context-parallel training steps shard_map over."""
    if context and extra_dims >= 1:
        return P(("data", "fsdp"), "context", *([None] * (extra_dims - 1)))
    return P(("data", "fsdp"), *([None] * extra_dims))


def batch_sharding(mesh: Mesh, extra_dims: int = 1, context: bool = False) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(extra_dims, context=context))
