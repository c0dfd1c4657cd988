"""Mesh construction and sharding rules (L8).

The reference's parallelism ceiling is one `nn.DataParallel` wrap over two
GPUs (deepseekv3/deepseekv3.ipynb cells 37, 54). Here parallelism is
expressed the TPU-native way: a `jax.sharding.Mesh` with standardized axes
('data', 'fsdp', 'model', 'expert', 'context'), PartitionSpec rules over
parameter pytrees, XLA/GSPMD inserting the collectives over ICI/DCN, and
shard_map + explicit collectives for ring attention / Ulysses context
parallelism.
"""

from solvingpapers_tpu.metrics.trace import begin as _begin

_imported = _begin("import:sharding")
from solvingpapers_tpu.sharding.mesh import (
    MESH_AXES,
    MeshConfig,
    ambient_mesh,
    create_mesh,
    batch_spec,
    batch_sharding,
    get_ambient_mesh,
    mesh_axis_sizes,
)
from solvingpapers_tpu.sharding.rules import (
    GPT_RULES,
    LM_RULES,
    PP_RULES,
    param_specs,
    param_shardings,
)
from solvingpapers_tpu.sharding.ring_attention import (
    cp_halo_right,
    cp_shift_left,
    ring_attention,
    ring_attention_local,
    ulysses_attention,
    ulysses_attention_local,
)
from solvingpapers_tpu.sharding.pipeline import (
    analytic_bubble_fraction,
    pipeline_apply,
    schedule_ticks,
    shard_map_compat,
    stack_stage_params,
    tick_unit,
    vma_axes,
)
from solvingpapers_tpu.sharding.distributed import (
    initialize as initialize_distributed,
    host_batch_slice,
    host_seed,
)

_imported()
