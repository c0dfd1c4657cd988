"""Kimi-Linear-style hybrid decoder: Kimi Delta Attention (a delta rule
with a decay per key channel) three to one with latent attention that
carries no positions, a wide sigmoid router over many small experts with a
shared expert behind one dense layer, for training.

Capability target: the published `kimi_linear` architecture
(huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct, config.json; the
fields of `KimiLinearConfig` that the source states carry the source's
names, those of its `linear_attn_config` group prefixed `linear_`).

  * norm: x * rsqrt(mean(x^2) + eps) * w, float32, w one at the start
  * layer l (published numbering from 1): h = x + Mixer_l(Norm(x)); out =
    h + FFN_l(Norm(h)); the mixer is latent attention where l is in
    `full_attn_layers`, else KDA; FFN_l is a dense SwiGLU for l <=
    `first_k_dense_replace`, else the MoE; final norm, then an untied head;
    no position encoding anywhere
  * KDA: one projection to [q | k | v] (H*dk columns each), a causal
    depthwise convolution of width `short_conv_kernel_size` over them, then
    SiLU; one projection to [f | o | b]: f and o the low-rank (width dk)
    inputs of the decay and of the output gate, b one a head; g = -exp(A_log
    a head) * softplus(f W_f + dt_bias) a key channel, beta = sigmoid(b);
    the rule (`ops/kda.py`: S <- (I - beta k k^T) Diag(e^g) S + beta k v^T,
    o = S^T q, q and k unit length a head, q scaled by dk^-0.5); o *
    rsqrt(mean(o^2) + eps) * w_n a head, times sigmoid(o_low W_g); o_proj
  * latent attention, trained decompressed: q = W_q x, a head [q_n | q_r];
    [c | k_r] = W_kva x, c normed; [k_n | v] = W_kvb c a head; a head's key
    is [k_n | k_r], k_r shared by all heads, NO rotation on either side
    (`mla_use_nope`); causal softmax at (d_nope + d_rope)^-0.5 through the
    flash kernels with keys 192 and values 128 wide; o_proj
  * MoE (`HeldExpertsMoE` of models/mixers.py, sigmoid scoring): s =
    sigmoid(x W_r) over ALL `router_experts` in float32; the chosen are the
    top-k of s + a selection bias that takes no gradient; weights s / sum of
    the chosen s * `routed_scaling_factor`; this device computes the
    experts it holds, [first_expert, first_expert + num_experts), through
    capacity slots with no exchange; plus the shared expert, ungated. With
    `num_expert_group` = `topk_group` = 1 the source's group-limited choice
    is plain top-k; wider groups are refused (ROADMAP R-M3).

The residual stream is float32; products take bfloat16 operands over
float32 weights (`dtype`); the router's product, the decays and the rule's
state are float32. Not here: a compressed query (`q_lora_rank`, null in the
source), the absorbed form of the latent attention that decoding wants and
decode caches (ROADMAP R-M1, R-M7), a balance loss or an update rule for
the selection bias (the source's config states neither).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from solvingpapers_tpu import ops
from solvingpapers_tpu.kernels.flash_attention import FLASH_RESIDUALS
from solvingpapers_tpu.kernels.gated_delta import DELTA_RESIDUALS
from solvingpapers_tpu.models.layers import (
    _by_blocks, blocked_swiglu, causal_attention, remat_keeping,
    training_only,
)
from solvingpapers_tpu.models.mixers import HeldExpertsMoE, delta_a_log_init
from solvingpapers_tpu.ops import kda
from solvingpapers_tpu.ops.conv import causal_depthwise_conv

# every matrix starts normal(0, 0.02), the family's initializer_range
_INIT = nn.initializers.normal(0.02)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    # --- the source's config.json, under its names
    vocab_size: int = 163_840
    hidden_size: int = 2304
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    q_lora_rank: int | None = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    intermediate_size: int = 9216
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    # experts HELD by this device (the source's count when it holds all)
    num_experts: int = 256
    num_experts_per_token: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    routed_scaling_factor: float = 2.446
    num_expert_group: int = 1
    topk_group: int = 1
    rms_norm_eps: float = 1e-5
    # its `linear_attn_config` group; layers numbered from 1, whole, as
    # published: a cut in depth reads its first `num_hidden_layers`
    linear_num_heads: int = 32
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    full_attn_layers: tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    kda_layers: tuple[int, ...] = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15,
                                   17, 18, 19, 21, 22, 23, 25, 26)
    # --- this repo's
    # the router's width: every expert of the layer, here or elsewhere
    router_experts: int = 256
    first_expert: int = 0  # global index of the first expert held
    block_size: int = 16_384
    capacity_factor: float = 4.0
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
        unsupported = {
            "q_lora_rank": self.q_lora_rank is not None,
            "mla_use_nope": not self.mla_use_nope,
            "moe_router_activation_func":
                self.moe_router_activation_func != "sigmoid",
            "num_expert_group": self.num_expert_group != 1,
            "topk_group": self.topk_group != 1,
            "num_shared_experts": self.num_shared_experts != 1,
            "moe_layer_freq": self.moe_layer_freq != 1,
            "num_key_value_heads":
                self.num_key_value_heads != self.num_attention_heads,
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                f"kimi_linear: no path here for this value of {bad} "
                "(ROADMAP R-M1, R-M3)")
        attn, lin = set(self.full_attn_layers), set(self.kda_layers)
        for layer in range(1, self.num_hidden_layers + 1):
            if (layer in attn) == (layer in lin):
                raise ValueError(
                    f"layer {layer} must be in exactly one of "
                    "full_attn_layers and kda_layers")

    @property
    def compute_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)

    def is_attention_layer(self, index: int) -> bool:
        """`index` from 0; the source numbers its layers from 1."""
        return index + 1 in self.full_attn_layers

    def is_dense_layer(self, index: int) -> bool:
        return index < self.first_k_dense_replace


class LatentAttention(nn.Module):
    """Norm(x) -> latent attention without positions, decompressed for
    training. As in `KimiDeltaAttention` the input norm is applied here
    (`norm_w` is its weight) and the per-token stages run block by block:
    every projection before the attention product, `o_proj` after it."""

    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, x, norm_w):
        cfg = self.cfg
        b, s, d = x.shape
        n, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        d_n, d_r, d_v = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim)
        dt = cfg.compute_dtype
        w_q = self.param("q_proj", _INIT, (d, n * (d_n + d_r))).astype(dt)
        w_kva = self.param("kv_a_proj", _INIT, (d, rank + d_r)).astype(dt)
        c_norm = self.param("kv_a_norm", nn.initializers.ones, (rank,))
        w_kvb = self.param("kv_b_proj", _INIT,
                           (rank, n * (d_n + d_v))).astype(dt)
        w_out = self.param("o_proj", _INIT, (n * d_v, d)).astype(dt)

        def before(x):
            hid = ops.rms_norm(x.astype(jnp.float32), norm_w,
                               cfg.rms_norm_eps).astype(dt)
            lead = hid.shape[:2]
            kva = hid @ w_kva
            c = ops.rms_norm(kva[..., :rank].astype(jnp.float32), c_norm,
                             cfg.rms_norm_eps).astype(dt)
            kvb = (c @ w_kvb).reshape(lead + (n, d_n + d_v))
            # the positional part of the key, one for all heads, and no
            # rotation: with `mla_use_nope` it is 64 more shared channels
            k_r = jnp.broadcast_to(kva[..., None, rank:], lead + (n, d_r))
            k = jnp.concatenate([kvb[..., :d_n], k_r], axis=-1)
            return ((hid @ w_q).reshape(lead + (n, d_n + d_r)), k,
                    kvb[..., d_n:])

        with jax.named_scope("L_attn_proj"):
            q, k, v = _by_blocks(before, kda.SEGMENT, x)
        ctx = causal_attention(self, q, k, v, scale=(d_n + d_r) ** -0.5,
                               use_flash=cfg.use_flash)
        with jax.named_scope("L_attn_proj"):
            return _by_blocks(lambda c: c @ w_out, kda.SEGMENT,
                              ctx.reshape(b, s, n * d_v).astype(dt))


class KimiDeltaAttention(nn.Module):
    """Norm(x) -> the KDA mixer. The input norm is applied here (`norm_w` is
    its weight), inside the first of the two per-token stages that run
    block by block (`_by_blocks`): projections and decay before the rule,
    gated norm and `o_proj` after it. Each stage is one loop under its own
    scope, so a device trace still tells projections, convolution and rule
    apart."""

    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, x, norm_w):
        cfg = self.cfg
        b, s, _ = x.shape
        h, dk = cfg.linear_num_heads, cfg.linear_head_dim
        dt = cfg.compute_dtype
        n = h * dk  # q, k, v and the gate are all this wide
        w_qkv = self.param("in_proj_qkv", _INIT,
                           (cfg.hidden_size, 3 * n)).astype(dt)
        # [f | o | b]: the decay's and the output gate's low-rank inputs,
        # dk wide each (the family's convention), and beta's logit a head
        w_fob = self.param("in_proj_fob", _INIT,
                           (cfg.hidden_size, 2 * dk + h)).astype(dt)
        w_f = self.param("f_up", _INIT, (dk, n)).astype(dt)
        w_g = self.param("g_up", _INIT, (dk, n)).astype(dt)
        a_log = self.param("A_log", delta_a_log_init, (h,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (h, dk))
        k_conv = cfg.short_conv_kernel_size
        conv_w = self.param(
            "conv_w", nn.initializers.normal((3.0 * k_conv) ** -0.5),
            (k_conv, 3 * n))
        w_n = self.param("norm_weight", nn.initializers.ones, (dk,))
        w_out = self.param("o_proj", _INIT, (n, cfg.hidden_size)).astype(dt)

        def before(x):
            hid = ops.rms_norm(x.astype(jnp.float32), norm_w,
                               cfg.rms_norm_eps).astype(dt)
            fob = hid @ w_fob
            beta = jax.nn.sigmoid(fob[..., 2 * dk:].astype(jnp.float32))
            return hid @ w_qkv, fob[..., :dk], fob[..., dk:2 * dk], beta

        def decay(f_low):
            # made inside the rule's scope (`kda_rule`'s `decay`): the
            # (S, H, dk) float32 array lives beside the kernels that read it
            f = (f_low @ w_f).astype(jnp.float32).reshape(
                f_low.shape[:2] + (h, dk))
            return -jnp.exp(a_log)[:, None] * jax.nn.softplus(f + dt_bias)

        def after(o, o_low):
            shape = o.shape[:2] + (h, dk)
            gate = jax.nn.sigmoid((o_low @ w_g).astype(jnp.float32))
            o = ops.rms_norm(o.reshape(shape).astype(jnp.float32), w_n,
                             cfg.rms_norm_eps) * gate.reshape(shape)
            return o.reshape(o.shape[:2] + (n,)).astype(dt) @ w_out

        with jax.named_scope("L_kda_proj"):
            qkv, f_low, o_low, beta = _by_blocks(before, kda.SEGMENT, x)
        with jax.named_scope("L_kda_conv"):
            qkv = causal_depthwise_conv(qkv, conv_w, True)
            q, k, v = (qkv[..., i * n:(i + 1) * n].reshape(b, s, h, dk)
                       for i in range(3))
        with jax.named_scope("L_kda_core"):
            o = kda.kda_rule(q, k, v, f_low, beta, decay=decay)
        with jax.named_scope("L_kda_proj"):
            return _by_blocks(after, kda.SEGMENT, o.reshape(b, s, n), o_low)


class MixerBlock(nn.Module):
    """x + Mixer(Norm(x)): latent attention or KDA."""

    cfg: KimiLinearConfig
    attention: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm_w = self.param("input_norm", nn.initializers.ones,
                            (cfg.hidden_size,))
        if self.attention:
            h = LatentAttention(cfg, name="attn")(x, norm_w)
        else:
            h = KimiDeltaAttention(cfg, name="kda")(x, norm_w)
        with jax.named_scope("L_attn_proj" if self.attention
                             else "L_kda_proj"):
            return x + h.astype(jnp.float32)


def held_moe(cfg: KimiLinearConfig, name: str | None = None) -> HeldExpertsMoE:
    """The held-experts layer as this family's config words it."""
    return HeldExpertsMoE(
        router_experts=cfg.router_experts, held=cfg.num_experts,
        first_expert=cfg.first_expert, top_k=cfg.num_experts_per_token,
        expert_hidden=cfg.moe_intermediate_size,
        shared_hidden=cfg.moe_intermediate_size * cfg.num_shared_experts,
        capacity_factor=cfg.capacity_factor, dtype=cfg.compute_dtype,
        scoring="sigmoid", renorm=cfg.moe_renormalize,
        scale=cfg.routed_scaling_factor, name=name,
    )


class FFNBlock(nn.Module):
    """x + FFN(Norm(x)): the dense SwiGLU, block by block, or this rank's
    part of the MoE."""

    cfg: KimiLinearConfig
    dense: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm_w = self.param("post_norm", nn.initializers.ones,
                            (cfg.hidden_size,))
        if self.dense:
            dt = cfg.compute_dtype
            shape = (cfg.hidden_size, cfg.intermediate_size)
            w_gate = self.param("mlp_gate", _INIT, shape).astype(dt)
            w_up = self.param("mlp_up", _INIT, shape).astype(dt)
            w_down = self.param("mlp_down", _INIT, shape[::-1]).astype(dt)
            return blocked_swiglu(x, norm_w, w_gate, w_up, w_down,
                                  eps=cfg.rms_norm_eps, block=kda.SEGMENT)
        with jax.named_scope("L_moe_gate"):
            h = ops.rms_norm(x, norm_w, cfg.rms_norm_eps)
        h = held_moe(cfg, name="moe")(h)
        with jax.named_scope("L_moe_combine"):
            return x + h


class KimiLinearLayer(nn.Module):
    cfg: KimiLinearConfig
    attention: bool = False
    dense: bool = False

    @nn.compact
    def __call__(self, x):
        x = MixerBlock(self.cfg, self.attention, name="mixer")(x)
        return FFNBlock(self.cfg, self.dense, name="ffn")(x)


class KimiLinear(nn.Module):
    cfg: KimiLinearConfig

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
            "kimi_linear", cfg, tokens, caches,
            "a KDA layer keeps recurrent state, which no cache manager here "
            "holds yet (ROADMAP R-M7)")
        with jax.named_scope("L_embed"):
            x = nn.Embed(
                cfg.vocab_size, cfg.hidden_size, dtype=jnp.float32,
                embedding_init=_INIT, name="tok_emb",
            )(tokens)
        # the kernels' forward runs are kept, not run again: the attention
        # layer's flash o and lse (130 MiB at 32 heads of 16,384 tokens),
        # a KDA layer's o and the float32 state entering each of its 64
        # grid steps (128 + 128 MiB at 32 heads of 128 x 128); everything
        # else of a layer is made again, and the dense layer's feed-forward
        # part, with nothing named, remats whole
        layer_cls = remat_keeping(
            KimiLinearLayer, cfg.remat, *FLASH_RESIDUALS, *DELTA_RESIDUALS)
        for i in range(cfg.num_hidden_layers):
            x = layer_cls(
                cfg, cfg.is_attention_layer(i), cfg.is_dense_layer(i),
                name=f"layer_{i}",
            )(x)
        with jax.named_scope("L_loss_head"):
            norm_f = self.param("norm_f", nn.initializers.ones,
                                (cfg.hidden_size,))
            x = ops.rms_norm(x, norm_f, cfg.rms_norm_eps).astype(
                cfg.compute_dtype)
            lm_head = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=cfg.compute_dtype,
                kernel_init=_INIT, name="lm_head")
            if not head and not self.is_initializing():
                return x, None
            return lm_head(x), None

    def head_kernel(self, params) -> jax.Array:  # (D, V), the loss's to apply
        return params["lm_head"]["kernel"]
