"""Shared primitive ops (L1).

One implementation of each primitive the reference re-implements per
notebook: norms, RoPE (both formulations), activations, attention cores,
losses, and samplers.
"""

from solvingpapers_tpu.metrics.trace import begin as _begin

_imported = _begin("import:ops")
from solvingpapers_tpu.ops.norms import rms_norm, layer_norm, local_response_norm
from solvingpapers_tpu.ops.rope import (
    precompute_rope,
    precompute_freqs_cis,
    apply_rope,
    apply_rotary_emb_complex,
    partial_rotary,
    rope_rotation_matrix,
    sinusoidal_position_encoding,
)
from solvingpapers_tpu.ops import moe
from solvingpapers_tpu.ops.activations import (
    relu,
    relu2,
    leaky_relu,
    prelu,
    elu,
    gelu_tanh,
    silu,
    swish,
)
from solvingpapers_tpu.ops.attention import (
    repeat_kv,
    causal_mask,
    dot_product_attention,
    luong_attention,
)
from solvingpapers_tpu.ops.losses import (
    cross_entropy,
    head_cross_entropy,
    head_nll_rows,
    distillation_loss,
    vae_loss,
    mtp_loss,
)
from solvingpapers_tpu.ops.quant import (
    quantize,
    dequantize,
    quantize_tree,
    dequantize_tree,
    scale_shape,
)
from solvingpapers_tpu.ops.sampling import (
    sample_greedy,
    sample_categorical,
    sample_top_k,
    sample_top_p,
    sample_min_p,
    top_k_mask,
    top_p_mask,
    min_p_mask,
    allowed_logits,
)

_imported()
