"""Pallas TPU kernels (the framework's native-code surface).

The reference has zero custom kernels (SURVEY.md §0: no C++/CUDA at all);
these are new TPU-first implementations of the hot ops: blockwise flash
attention (causal + bidirectional, GQA; `flash_attention.py`, its mesh
wrapper `sharded_flash.py`), the chunked gated delta rule
(`gated_delta.py`) and the routed experts' unit, gated or not, over the rows
each expert really holds (`moe_grouped.py`; MoE dispatch and combine
themselves are row gathers in `ops/moe.py`, no kernel). Each kernel has a plain jnp reference
(in ops/, or the model's einsums) and interpret-mode equality tests.
"""

from solvingpapers_tpu.metrics.trace import begin as _begin

_imported = _begin("import:kernels")
from solvingpapers_tpu.kernels.flash_attention import flash_attention
from solvingpapers_tpu.kernels.sharded_flash import sharded_flash_attention

_imported()
