"""Qwen3-Next-style hybrid decoder: Gated DeltaNet layers three to one with
gated softmax attention, a wide softmax router over many small experts with
a gated shared expert, for training.

Capability target: the published `qwen3_next` architecture
(huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, config.json; the fields
of `Qwen3NextConfig` that the source states carry the source's names).

  * norm everywhere but inside the DeltaNet output: x * rsqrt(mean(x^2) +
    eps) * (1 + w), float32, w zero at the start (`ZeroCenteredRMSNorm`)
  * layer l: h = x + Mixer_l(Norm(x)); out = h + MoE(Norm(h)); the mixer is
    gated attention where (l + 1) % full_attention_interval == 0, else
    Gated DeltaNet; final norm, then an untied head
  * gated attention: q_proj gives query and gate a head ([q | gate] inside
    a head's 2*head_dim columns), q and k normed a head, rotate-half rotary
    on the first partial_rotary_factor * head_dim features, causal GQA
    attention through the flash kernels, heads' output * sigmoid(gate)
  * Gated DeltaNet: one projection to [q | k | v | z] (contiguous blocks:
    Hk*dk, Hk*dk, Hv*dv, Hv*dv columns; value head h reads key head
    h // (Hv / Hk)), one to [b | a]; causal depthwise convolution of
    [q | k | v] then SiLU; beta = sigmoid(b), g = -exp(A_log) *
    softplus(a + dt_bias); the gated delta rule (ops/gated_delta.py);
    o * rsqrt(mean(o^2) + eps) * w_n a head, times SiLU(z); out_proj
  * MoE (`HeldExpertsMoE` of models/mixers.py, softmax scoring): softmax
    over ALL `router_experts` in float32, top-k renormalised;
    this device computes the experts it holds, [first_expert, first_expert
    + num_experts), through capacity slots with no exchange (what the other
    experts would add is left out: one expert-parallel rank's part); plus
    sigmoid(x w_s) * SharedExpert(x); the family's load-balancing loss

The residual stream is float32; products take bfloat16 operands over
float32 weights (`dtype`), and the router's product, the DeltaNet's decay
and state are float32. Not here: the multi-token-prediction head (not in
the source's config), decode caches (serving this family waits for a cache
manager that holds recurrent state, ROADMAP R-M7).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from solvingpapers_tpu import ops
from solvingpapers_tpu.models.layers import (
    _by_blocks, causal_attention, training_only,
)
from solvingpapers_tpu.models.mixers import HeldExpertsMoE, delta_a_log_init
from solvingpapers_tpu.ops import gated_delta
from solvingpapers_tpu.ops.conv import causal_depthwise_conv

# every matrix starts as the family does: normal, initializer_range 0.02
_INIT = nn.initializers.normal(0.02)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    # --- the source's config.json, under its names
    vocab_size: int = 151_936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10_000_000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # experts HELD by this device (the source's count when it holds all)
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    # --- this repo's
    # the router's width: every expert of the layer, here or elsewhere
    router_experts: int = 512
    first_expert: int = 0  # global index of the first expert held
    block_size: int = 16_384
    router_aux_loss_coef: float = 0.001
    capacity_factor: float = 2.0
    remat: bool = True
    use_flash: bool = True
    dtype: str = "bfloat16"

    def __post_init__(self):
        if not 0 <= self.first_expert <= self.router_experts - self.num_experts:
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert} + "
                f"{self.num_experts}) are not among the router's "
                f"{self.router_experts}"
            )
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("value heads must be a multiple of key heads")

    @property
    def compute_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def is_attention_layer(self, index: int) -> bool:
        return (index + 1) % self.full_attention_interval == 0


class ZeroCenteredRMSNorm(nn.Module):
    """x * rsqrt(mean(x^2) + eps) * (1 + w), float32, w zero at the start."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        w = self.param("weight", nn.initializers.zeros, (x.shape[-1],))
        return ops.rms_norm(x.astype(jnp.float32), 1.0 + w, self.eps)


class GatedAttention(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        n, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        dt = cfg.compute_dtype
        dense = lambda f, name: nn.Dense(  # noqa: E731
            f, use_bias=False, dtype=dt, kernel_init=_INIT, name=name)
        with jax.named_scope("L_attn_proj"):
            x = x.astype(dt)
            qg = dense(n * hd * 2, "q_proj")(x).reshape(b, s, n, 2 * hd)
            q, gate = qg[..., :hd], qg[..., hd:]
            k = dense(kv * hd, "k_proj")(x).reshape(b, s, kv, hd)
            v = dense(kv * hd, "v_proj")(x).reshape(b, s, kv, hd)
            q = ZeroCenteredRMSNorm(cfg.rms_norm_eps, name="q_norm")(q)
            k = ZeroCenteredRMSNorm(cfg.rms_norm_eps, name="k_norm")(k)
            q = ops.partial_rotary(q, cfg.rotary_dim, cfg.rope_theta).astype(dt)
            k = ops.partial_rotary(k, cfg.rotary_dim, cfg.rope_theta).astype(dt)
        ctx = causal_attention(self, q, k, v, scale=hd ** -0.5,
                               use_flash=cfg.use_flash)
        with jax.named_scope("L_attn_proj"):
            ctx = ctx.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))
            return dense(cfg.hidden_size, "o_proj")(
                ctx.reshape(b, s, n * hd).astype(dt))


class GatedDeltaNet(nn.Module):
    """Norm(x) -> the Gated DeltaNet mixer. The input norm is applied here
    (`norm_w` is its weight), inside the first of the two per-token stages
    that run block by block: projections before the rule, gated norm and
    `out_proj` after it. Each stage is one loop under its own scope, so a
    device trace still tells projections, convolution and rule apart."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, norm_w):
        cfg = self.cfg
        b, s, _ = x.shape
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        dt = cfg.compute_dtype
        n_qk, n_v = hk * dk, hv * dv
        # one weight, [q | k | v | z] by columns; z leaves by its own
        # product, so that no slice of the output is copied
        w_qkvz = self.param("in_proj_qkvz", _INIT,
                            (cfg.hidden_size, 2 * n_qk + 2 * n_v)).astype(dt)
        w_ba = self.param("in_proj_ba", _INIT,
                          (cfg.hidden_size, 2 * hv)).astype(dt)
        a_log = self.param("A_log", delta_a_log_init, (hv,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,))
        k_conv = cfg.linear_conv_kernel_dim
        conv_w = self.param(
            "conv_w", nn.initializers.normal((3.0 * k_conv) ** -0.5),
            (k_conv, 2 * n_qk + n_v))
        w_n = self.param("norm_weight", nn.initializers.ones, (dv,))
        w_out = self.param("out_proj", _INIT, (n_v, cfg.hidden_size)).astype(dt)

        def before(x):
            h = ops.rms_norm(x.astype(jnp.float32), 1.0 + norm_w,
                             cfg.rms_norm_eps).astype(dt)
            ba = (h @ w_ba).astype(jnp.float32)
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
            return (h @ w_qkvz[:, :2 * n_qk + n_v],
                    h @ w_qkvz[:, 2 * n_qk + n_v:], g, beta)

        def after(o, z):
            shape = o.shape[:2] + (hv, dv)
            o = gated_delta.gated_rms_norm(
                o.reshape(shape), z.reshape(shape), w_n, cfg.rms_norm_eps)
            return o.reshape(o.shape[:2] + (n_v,)) @ w_out

        with jax.named_scope("L_gdn_proj"):
            qkv, z, g, beta = _by_blocks(before, gated_delta.SEGMENT, x)
        with jax.named_scope("L_gdn_conv"):
            qkv = causal_depthwise_conv(qkv, conv_w, True)
            q = qkv[..., :n_qk].reshape(b, s, hk, dk)
            k = qkv[..., n_qk:2 * n_qk].reshape(b, s, hk, dk)
            v = qkv[..., 2 * n_qk:].reshape(b, s, hv, dv)
        with jax.named_scope("L_gdn_core"):
            o = gated_delta.gated_delta_rule(q, k, v, g, beta)
        with jax.named_scope("L_gdn_proj"):
            return _by_blocks(
                after, gated_delta.SEGMENT, o.reshape(b, s, n_v), z)


def held_moe(cfg: Qwen3NextConfig, name: str | None = None) -> HeldExpertsMoE:
    """The layer as this family's config words it."""
    return HeldExpertsMoE(
        router_experts=cfg.router_experts, held=cfg.num_experts,
        first_expert=cfg.first_expert, top_k=cfg.num_experts_per_tok,
        expert_hidden=cfg.moe_intermediate_size,
        shared_hidden=cfg.shared_expert_intermediate_size,
        capacity_factor=cfg.capacity_factor, dtype=cfg.compute_dtype,
        renorm=cfg.norm_topk_prob, name=name,
    )


class MixerBlock(nn.Module):
    """x + Mixer(Norm(x)): gated attention or Gated DeltaNet."""

    cfg: Qwen3NextConfig
    attention: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm_w = self.param("input_norm", nn.initializers.zeros,
                            (cfg.hidden_size,))
        if self.attention:
            with jax.named_scope("L_attn_proj"):
                h = ops.rms_norm(x, 1.0 + norm_w, cfg.rms_norm_eps)
            h = GatedAttention(cfg, name="attn")(h)
        else:
            h = GatedDeltaNet(cfg, name="gdn")(x, norm_w)
        with jax.named_scope("L_attn_proj" if self.attention
                             else "L_gdn_proj"):
            return x + h.astype(jnp.float32)


class MoEBlock(nn.Module):
    """x + MoE(Norm(x)), this rank's part."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("L_moe_gate"):
            h = ZeroCenteredRMSNorm(self.cfg.rms_norm_eps, name="post_norm")(x)
        h = held_moe(self.cfg, name="moe")(h)
        with jax.named_scope("L_moe_combine"):
            return x + h


class Qwen3NextLayer(nn.Module):
    cfg: Qwen3NextConfig
    attention: bool = False

    @nn.compact
    def __call__(self, x):
        x = MixerBlock(self.cfg, self.attention, name="mixer")(x)
        return MoEBlock(self.cfg, name="ffn")(x)


class Qwen3Next(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, tokens, *, caches=None):
        """(B, S) tokens -> ((B, S, V) logits, None), as the other families
        return (logits, caches). Training and scoring only: the family has
        no decode cache yet, and no dropout."""
        cfg = self.cfg
        training_only(
            "qwen3next", cfg, tokens, caches,
            "a Gated DeltaNet layer keeps recurrent state, which no cache "
            "manager here holds yet (ROADMAP R-M7)")
        with jax.named_scope("L_embed"):
            x = nn.Embed(
                cfg.vocab_size, cfg.hidden_size, dtype=jnp.float32,
                embedding_init=nn.initializers.normal(0.02), name="tok_emb",
            )(tokens)
        layer_cls = (nn.remat(Qwen3NextLayer, prevent_cse=False)
                     if cfg.remat else Qwen3NextLayer)
        for i in range(cfg.num_hidden_layers):
            x = layer_cls(
                cfg, cfg.is_attention_layer(i), name=f"layer_{i}"
            )(x)
        with jax.named_scope("L_loss_head"):
            x = ZeroCenteredRMSNorm(cfg.rms_norm_eps, name="norm_f")(x)
            logits = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=cfg.compute_dtype,
                kernel_init=nn.initializers.normal(0.02), name="lm_head",
            )(x.astype(cfg.compute_dtype))
        return logits, None
