"""Typed run configs (SURVEY.md §5 'config/flag system' rebuild).

One registry of named workloads replacing the reference's four ad-hoc
config styles; every notebook's train() cell is a named entry here,
launchable via `python -m solvingpapers_tpu.cli train --config=<name>`.
"""

from solvingpapers_tpu.metrics.trace import begin as _begin

_imported = _begin("import:configs")
from solvingpapers_tpu.configs.registry import RunConfig, get_config, list_configs, register

_imported()
