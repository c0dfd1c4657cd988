"""Nemotron-H-style hybrid decoder: Mamba-2 state-space layers, a few
grouped-query attention layers that carry no positions, and a wide sigmoid
router over many small ungated squared-ReLU experts with a shared one, each
layer ONE of them alone, for training.

Capability target: the published `nemotron_h` architecture
(huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, config.json;
the fields of `NemotronHConfig` that the source states carry the source's
names).

  * norm: x * rsqrt(mean(x^2) + eps) * w, float32, w one at the start
  * layer i is ONE sub-block with its own norm and residual, x + Mixer_i(
    Norm_i(x)), of the kind `hybrid_override_pattern[i]` says: `M` Mamba-2,
    `E` MoE, `*` attention (`-`, a dense MLP, is refused: this model has
    none); final norm, then an untied head; no position encoding anywhere
    (order comes from the Mamba layers)
  * Mamba-2 (H heads of P, `d_inner` = H * P; G groups, state N wide):
    `in_proj` to [z (d_inner) | xBC (d_inner + 2 G N) | dt (H)]; xBC <-
    SiLU(causal depthwise convolution of width `conv_kernel` + bias) = [x |
    B | C]; dt = softplus(dt + dt_bias), a = -exp(A_log) a head; the
    recurrence of `ops/ssd.py` (S <- exp(dt a) S + dt x B^T, y = S C + D x,
    head h reading group h // (H / G)) in chunks of `chunk_size`, through
    the Pallas kernels of `kernels/ssd.py`; u = y *
    SiLU(z), then RMS-normalised over each group's d_inner / G channels,
    times a weight; `out_proj`
  * attention: q, k, v projections to `num_attention_heads` and
    `num_key_value_heads` heads of `head_dim`, causal softmax at
    head_dim^-0.5 through the flash kernels, `o_proj`; no bias, no rotation
  * MoE (`HeldExpertsMoE` of models/mixers.py, sigmoid scoring, ungated):
    s = sigmoid(x W_r) over ALL `router_experts` in float32; the chosen are
    the top-k of s + a selection bias that takes no gradient; weights s /
    sum of the chosen s * `routed_scaling_factor`; an expert is W_down
    relu(W_up x)^2; this device computes the experts it holds,
    [first_expert, first_expert + n_routed_experts), through capacity slots
    with no exchange; plus the shared expert of the same form. With
    `n_group` = `topk_group` = 1 the source's group-limited choice is plain
    top-k; wider groups are refused (ROADMAP R-M2).

The residual stream is float32; products take bfloat16 operands over
float32 weights (`dtype`); the router's product, the steps, the decays and
the state are float32. Not here: dense `-` layers, decode caches (ROADMAP
R-M7: a cache manager would hold a layer's P x N state a head and the
convolution's last rows, with `ops.ssd.ssd_step` as the step and the
kernels' `state=` for a prefill), a balance
loss or an update rule for the selection bias (the source's config states
neither).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from solvingpapers_tpu import ops
from solvingpapers_tpu.kernels.flash_attention import FLASH_RESIDUALS
from solvingpapers_tpu.kernels.ssd import SSD_RESIDUALS
from solvingpapers_tpu.models.layers import remat_keeping, training_only
from solvingpapers_tpu.models.mixers import (
    HeldExpertsMoE, Mamba2Mixer, NoPEAttention,
)

# every matrix starts normal(0, 0.02), the family's initializer_range
_INIT = nn.initializers.normal(0.02)
# the kinds of layer that run here: Mamba-2, MoE, attention
KINDS = "ME*"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    # --- the source's config.json, under its names
    vocab_size: int = 131_072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    # whole, as published: a cut in depth reads its first `num_hidden_layers`
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    mamba_hidden_act: str = "silu"
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # experts HELD by this device (the source's count when it holds all)
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_shared_experts: int = 1
    mlp_hidden_act: str = "relu2"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    n_group: int = 1
    topk_group: int = 1
    layer_norm_epsilon: float = 1e-5
    # --- this repo's
    # the router's width: every expert of the layer, here or elsewhere
    router_experts: int = 128
    first_expert: int = 0  # global index of the first expert held
    block_size: int = 16_384
    capacity_factor: float = 8.0
    remat: bool = True
    use_flash: bool = True
    dtype: str = "bfloat16"

    def __post_init__(self):
        if not (0 <= self.first_expert
                <= self.router_experts - self.n_routed_experts):
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert} + "
                f"{self.n_routed_experts}) are not among the router's "
                f"{self.router_experts}"
            )
        unsupported = {
            "n_group": self.n_group != 1,
            "topk_group": self.topk_group != 1,
            "n_shared_experts": self.n_shared_experts != 1,
            "use_conv_bias": not self.use_conv_bias,
            "mamba_hidden_act": self.mamba_hidden_act != "silu",
            "mlp_hidden_act": self.mlp_hidden_act != "relu2",
            "hybrid_override_pattern": bool(
                set(self.layer_pattern) - set(KINDS)),
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                f"nemotron_h: no path here for this value of {bad} "
                "(ROADMAP R-M2, R-M11)")
        if len(self.layer_pattern) != self.num_hidden_layers:
            raise ValueError(
                f"hybrid_override_pattern names "
                f"{len(self.hybrid_override_pattern)} layers, "
                f"num_hidden_layers asks for {self.num_hidden_layers}")
        if (self.mamba_num_heads % self.n_groups
                or self.num_attention_heads % self.num_key_value_heads):
            raise ValueError("heads must be a multiple of their groups")

    @property
    def compute_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)

    @property
    def layer_pattern(self) -> str:
        """One character a layer that runs here."""
        return self.hybrid_override_pattern[:self.num_hidden_layers]

    @property
    def d_inner(self) -> int:
        """Heads x head width, what the published code takes (`expand` is
        stated by the source and unused)."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def attention_scale(self) -> float:
        """What multiplies q k^T before the softmax."""
        return self.head_dim ** -0.5


def held_moe(cfg: NemotronHConfig, name: str | None = None) -> HeldExpertsMoE:
    """The held-experts layer as this family's config words it."""
    return HeldExpertsMoE(
        router_experts=cfg.router_experts, held=cfg.n_routed_experts,
        first_expert=cfg.first_expert, top_k=cfg.num_experts_per_tok,
        expert_hidden=cfg.moe_intermediate_size,
        shared_hidden=(cfg.moe_shared_expert_intermediate_size
                       * cfg.n_shared_experts),
        capacity_factor=cfg.capacity_factor, dtype=cfg.compute_dtype,
        scoring="sigmoid", renorm=cfg.norm_topk_prob,
        scale=cfg.routed_scaling_factor, gated=False, activation=ops.relu2,
        name=name,
    )


class NemotronHLayer(nn.Module):
    """x + Mixer(Norm(x)), one sub-block: `kind` is the layer's character of
    the pattern."""

    cfg: NemotronHConfig
    kind: str = "M"

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm_w = self.param("norm", nn.initializers.ones, (cfg.hidden_size,))
        if self.kind == "E":
            with jax.named_scope("L_moe_gate"):
                h = ops.rms_norm(x, norm_w, cfg.layer_norm_epsilon)
            h = held_moe(cfg, name="moe")(h)
            with jax.named_scope("L_moe_combine"):
                return x + h
        if self.kind == "*":
            h = NoPEAttention(cfg, name="attn")(x, norm_w)
        else:
            h = Mamba2Mixer(cfg, name="mixer")(x, norm_w)
        with jax.named_scope("L_attn_proj" if self.kind == "*"
                             else "L_ssm_proj"):
            return x + h.astype(jnp.float32)


class NemotronH(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, tokens, *, caches=None, head: bool = True):
        """(B, S) tokens -> ((B, S, V) logits, None), as the other families
        return (logits, caches); with `head` False the normed hidden states
        (B, S, D) in the compute dtype instead, for a loss that applies
        `lm_head` itself a chunk of rows at a time (`chunked_head_loss_fn`,
        which takes the kernel from `head_kernel`). Training and scoring
        only: the family has no decode cache yet, and no dropout."""
        cfg = self.cfg
        training_only(
            "nemotron_h", cfg, tokens, caches,
            "a Mamba-2 layer keeps recurrent state, which no cache manager "
            "here holds yet (ROADMAP R-M7)")
        with jax.named_scope("L_embed"):
            x = nn.Embed(
                cfg.vocab_size, cfg.hidden_size, dtype=jnp.float32,
                embedding_init=_INIT, name="tok_emb",
            )(tokens)
        # the kernels' forward runs are kept, not run again: the attention
        # layer's flash o and lse (130 MiB at 32 heads of 16,384 tokens),
        # a Mamba-2 layer's y and the float32 state entering each of its 32
        # grid steps (128 + 64 MiB at 64 heads of 64 x 128); everything
        # else of a layer is made again, and an expert layer, with nothing
        # named, remats whole
        layer_cls = remat_keeping(
            NemotronHLayer, cfg.remat, *FLASH_RESIDUALS, *SSD_RESIDUALS)
        for i, kind in enumerate(cfg.layer_pattern):
            x = layer_cls(cfg, kind, name=f"layer_{i}")(x)
        with jax.named_scope("L_loss_head"):
            norm_f = self.param("norm_f", nn.initializers.ones,
                                (cfg.hidden_size,))
            x = ops.rms_norm(x, norm_f, cfg.layer_norm_epsilon).astype(
                cfg.compute_dtype)
            lm_head = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=cfg.compute_dtype,
                kernel_init=_INIT, name="lm_head")
            if not head and not self.is_initializing():
                return x, None
            return lm_head(x), None

    def head_kernel(self, params) -> jax.Array:  # (D, V), the loss's to apply
        return params["lm_head"]["kernel"]
