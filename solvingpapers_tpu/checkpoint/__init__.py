"""Checkpointing (L7): Orbax manager + params-only export."""

from solvingpapers_tpu.metrics.trace import begin as _begin

_imported = _begin("import:checkpoint")
from solvingpapers_tpu.checkpoint.manager import CheckpointManager, export_params, load_params

_imported()
