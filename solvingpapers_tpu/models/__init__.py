"""Flax model zoo (L2/L3).

Every model family from the reference, rebuilt on the shared ops/layers:
gpt, llama3 (GQA+RoPE+SwiGLU), gemma (MQA+GeGLU), deepseekv3 (MLA+MoE+MTP),
vit, alexnet, autoencoder/vae, kd teacher/student; and five published
architectures at their published widths, for training: qwen3next (Gated
DeltaNet + gated attention + held experts), kimi_linear (Kimi Delta
Attention + latent attention + held experts), nemotron_h (Mamba-2 + NoPE
attention + held squared-ReLU experts), ouro (one looped stack with exit
gates), granite_hybrid (Mamba-2 or NoPE attention, and a SwiGLU, a layer).
One file a family, which imports the shared `layers.py`, `mixers.py` and
`staged.py` and no other family (`tests/test_layering.py`).
"""

from solvingpapers_tpu.metrics.trace import begin as _begin

_imported = _begin("import:models")
from solvingpapers_tpu.models.layers import Attention, MLP, GLUFFN, RMSNorm, LayerNorm
from solvingpapers_tpu.models.gpt import GPT, GPTConfig
from solvingpapers_tpu.models.llama3 import Llama, LlamaConfig
from solvingpapers_tpu.models.gemma import Gemma, GemmaConfig
from solvingpapers_tpu.models.vit import ViT, ViTConfig
from solvingpapers_tpu.models.alexnet import AlexNet, AlexNetConfig
from solvingpapers_tpu.models.autoencoder import (
    AutoEncoder,
    AutoEncoderConfig,
    VAE,
    VAEConfig,
)
from solvingpapers_tpu.models.kd import (
    MLPClassifier,
    MLPClassifierConfig,
    teacher_config,
    student_config,
)

_imported()
