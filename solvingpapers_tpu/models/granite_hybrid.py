"""Granite-4.0-H-style hybrid decoder: every layer a mixer (Mamba-2, or
now and then grouped-query attention that carries no positions) AND a dense
SwiGLU, each behind its own norm and a scaled residual add, between a
scaled embedding and a tied, scaled head, for training.

Capability target: the published `granitemoehybrid` architecture at its
dense size (huggingface.co/ibm-granite/granite-4.0-h-micro, config.json; the
fields of `GraniteHybridConfig` that the source states carry the source's
names). With E the embedding (V, D), also the head:

  * x = `embedding_multiplier` * E[tokens]
  * layer i, of the kind `layer_types[i]` says:
      h = Norm(x; input_layernorm)
      "mamba": m = the Mamba-2 mixer of `models/mixers.py`
        (`Mamba2Mixer`: `in_proj` to [z | xBC | dt], the causal depthwise
        convolution with bias and SiLU, the recurrence of `ops/ssd.py`
        through the Pallas kernels, y * SiLU(z) normed, `out_proj`), here
        with `mamba_n_heads` heads of `mamba_d_head` that ALL read the
        `mamba_n_groups` = 1 group's B and C, the gated norm over all
        d_inner channels at once, chunks of `mamba_chunk_size`
      "attention": m = `NoPEAttention` of the same file: q to
        `num_attention_heads` heads, k and v to `num_key_value_heads`, of
        width hidden_size / num_attention_heads; causal softmax of
        `attention_multiplier` q k^T (1/64 at width 64, not 64^-0.5)
        through the flash kernels; no bias, no rotation
      x = x + `residual_multiplier` * m
      x = x + `residual_multiplier` * W_down (SiLU(W_gate u) * (W_up u)),
        u = Norm(x; post_attention_layernorm)   [`layers.blocked_swiglu`]
  * logits = Norm(x; norm_f) E^T / `logits_scaling`: the head is the
    embedding, tied; 1 / `logits_scaling` (a power of two: exact) is folded
    into the normed rows, so that the chunked head-with-loss
    (`ops.head_cross_entropy`, `train/objectives.py`
    `chunked_head_loss_fn`) takes E^T as its kernel and the logits are
    never whole. E's gradient is the float32 sum of its two uses.
  * norm: x * rsqrt(mean(x^2) + eps) * w, float32, w one at the start

The residual stream is float32; products take bfloat16 operands over
float32 weights (`dtype`); steps, decays and the state are float32. The
source keeps [W_gate | W_up] as one matrix `input_linear`; here they are two
leaves, as in the other families.

Not here, by mechanism: routed experts added to the dense MLP inside such a
layer (`num_local_experts` > 0, the family's larger sizes: refused, ROADMAP
R-M19); a decode cache (ROADMAP R-M7: a Mamba-2 layer's state and the
convolution's last rows, which no cache manager here holds); packed
documents that reset the state (R-M10); rotary positions
(`position_embedding_type` other than "nope": refused).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from solvingpapers_tpu import ops
from solvingpapers_tpu.kernels.flash_attention import FLASH_RESIDUALS
from solvingpapers_tpu.kernels.ssd import SSD_RESIDUALS
from solvingpapers_tpu.models.layers import (
    blocked_swiglu, remat_keeping, training_only,
)
from solvingpapers_tpu.models.mixers import Mamba2Mixer, NoPEAttention
from solvingpapers_tpu.ops import ssd

# every matrix starts normal(0, 0.02) (assumed: the source's config.json, as
# the catalog carries it, has no `initializer_range`)
_INIT = nn.initializers.normal(0.02)
KINDS = ("mamba", "attention")
# the published pattern: an attention layer at 5, 15, 25, 35 of 40
_LAYER_TYPES = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    # --- the source's config.json, under its names
    vocab_size: int = 100_352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    # whole, as published: a cut in depth reads its first `num_hidden_layers`
    layer_types: tuple[str, ...] = _LAYER_TYPES
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    hidden_act: str = "silu"
    normalization_function: str = "rmsnorm"
    position_embedding_type: str = "nope"
    rms_norm_eps: float = 1e-5
    num_local_experts: int = 0
    num_experts_per_tok: int = 0
    tie_word_embeddings: bool = True
    # --- this repo's
    # Mamba-2's range for the step at the start (the source states none)
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    block_size: int = 8192
    remat: bool = True
    use_flash: bool = True
    dtype: str = "bfloat16"

    def __post_init__(self):
        # a JSON file hands a list over
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.num_local_experts or self.num_experts_per_tok:
            raise ValueError(
                "granite_hybrid: routed experts beside the dense MLP of a "
                "mixer-plus-MLP layer (num_local_experts "
                f"{self.num_local_experts}, num_experts_per_tok "
                f"{self.num_experts_per_tok}) have no path here (ROADMAP "
                "R-M19)")
        unsupported = {
            "layer_types": bool(set(self.layer_pattern) - set(KINDS)),
            "hidden_act": self.hidden_act != "silu",
            "normalization_function": self.normalization_function
            != "rmsnorm",
            "position_embedding_type": self.position_embedding_type
            != "nope",
            "mamba_conv_bias": not self.mamba_conv_bias,
            "mamba_proj_bias": self.mamba_proj_bias,
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": not self.tie_word_embeddings,
            "shared_intermediate_size": self.shared_intermediate_size
            != self.intermediate_size,
            "mamba_expand": self.mamba_expand * self.hidden_size
            != self.d_inner,
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                f"granite_hybrid: no path here for this value of {bad}")
        if len(self.layer_pattern) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers asks for {self.num_hidden_layers}")
        if (self.mamba_n_heads % self.mamba_n_groups
                or self.num_attention_heads % self.num_key_value_heads
                or self.hidden_size % self.num_attention_heads):
            raise ValueError("heads must be a multiple of their groups, "
                             "and divide the hidden size")

    @property
    def compute_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)

    @property
    def layer_pattern(self) -> tuple[str, ...]:
        """One kind a layer that runs here."""
        return self.layer_types[:self.num_hidden_layers]

    # --- what `Mamba2Mixer` and `NoPEAttention` read (`models/mixers.py`
    # `Mamba2Config`, `NoPEAttentionConfig`), under the names they read it by

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def attention_scale(self) -> float:
        return self.attention_multiplier

    @property
    def layer_norm_epsilon(self) -> float:
        return self.rms_norm_eps

    @property
    def mamba_num_heads(self) -> int:
        return self.mamba_n_heads

    @property
    def mamba_head_dim(self) -> int:
        return self.mamba_d_head

    @property
    def n_groups(self) -> int:
        return self.mamba_n_groups

    @property
    def ssm_state_size(self) -> int:
        return self.mamba_d_state

    @property
    def conv_kernel(self) -> int:
        return self.mamba_d_conv

    @property
    def chunk_size(self) -> int:
        return self.mamba_chunk_size

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state


class GraniteHybridLayer(nn.Module):
    """x + r * Mixer(Norm(x)), then x + r * SwiGLU(Norm(x)), r the
    `residual_multiplier`: `kind` is the layer's entry of `layer_types`.
    Each scaled add stands under its sub-block's scope."""

    cfg: GraniteHybridConfig
    kind: str = "mamba"

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d, dt = cfg.hidden_size, cfg.compute_dtype
        ones = nn.initializers.ones
        norm_in = self.param("input_layernorm", ones, (d,))
        norm_post = self.param("post_attention_layernorm", ones, (d,))
        ffn = (d, cfg.intermediate_size)
        with jax.named_scope("L_dense_ffn"):
            w_gate = self.param("gate_proj", _INIT, ffn).astype(dt)
            w_up = self.param("up_proj", _INIT, ffn).astype(dt)
            w_down = self.param("down_proj", _INIT, ffn[::-1]).astype(dt)
        if self.kind == "attention":
            m = NoPEAttention(cfg, name="attn")(x, norm_in)
        else:
            m = Mamba2Mixer(cfg, name="mixer")(x, norm_in)
        with jax.named_scope("L_attn_proj" if self.kind == "attention"
                             else "L_ssm_proj"):
            x = x + cfg.residual_multiplier * m.astype(jnp.float32)
        return blocked_swiglu(
            x, norm_post, w_gate, w_up, w_down, eps=cfg.rms_norm_eps,
            block=ssd.SEGMENT, scale=cfg.residual_multiplier)


class GraniteHybrid(nn.Module):
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, tokens, *, caches=None, head: bool = True):
        """(B, S) tokens -> ((B, S, V) logits, None), as the other families
        return (logits, caches); with `head` False the normed hidden states
        times 1 / `logits_scaling`, (B, S, D) in the compute dtype, for a
        loss that applies the tied head itself a chunk of rows at a time
        (`chunked_head_loss_fn`, which takes E^T from `head_kernel`).
        Training and scoring only: the family has no decode cache yet, and
        no dropout."""
        cfg = self.cfg
        training_only(
            "granite_hybrid", cfg, tokens, caches,
            "a Mamba-2 layer keeps recurrent state, which no cache manager "
            "here holds yet (ROADMAP R-M7)")
        emb = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=jnp.float32,
            embedding_init=_INIT, name="tok_emb")
        with jax.named_scope("L_embed"):
            x = emb(tokens) * cfg.embedding_multiplier
        # as `nemotron_h`: the kernels' forward runs are kept, not run
        # again: the attention layer's flash o and lse (33 MiB at 32 heads
        # of 64 over 8,192 tokens), a Mamba-2 layer's y and the float32
        # state entering each of its 16 grid steps (64 + 32 MiB at 64
        # heads of 64 x 128); the mixer's projections and the SwiGLU
        # behind it are made again
        layer_cls = remat_keeping(
            GraniteHybridLayer, cfg.remat, *FLASH_RESIDUALS, *SSD_RESIDUALS)
        for i, kind in enumerate(cfg.layer_pattern):
            x = layer_cls(cfg, kind, name=f"layer_{i}")(x)
        with jax.named_scope("L_loss_head"):
            norm_f = self.param("norm_f", nn.initializers.ones,
                                (cfg.hidden_size,))
            x = (ops.rms_norm(x, norm_f, cfg.rms_norm_eps)
                 * (1.0 / cfg.logits_scaling)).astype(cfg.compute_dtype)
            if not head and not self.is_initializing():
                return x, None
            return x @ emb.embedding.astype(cfg.compute_dtype).T, None

    def head_kernel(self, params) -> jax.Array:  # (D, V): E^T, the tied head
        with jax.named_scope("L_loss_head"):
            return params["tok_emb"]["embedding"].T
