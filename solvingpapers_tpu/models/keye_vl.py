"""Keye-VL-2.0's language model: a Qwen3-MoE-shaped decoder whose every
query attends only to the keys a lightning indexer picks for it
(DeepSeek Sparse Attention), for training.

Capability target: the published `KeyeVL2` language model
(huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B, config.json; the fields of
`KeyeVLConfig` that the source states carry the source's names, `sa_config`'s
flat). Text tokens only: the vision tower is not built, and a text token's
three position axes are one position, so `mrope_section` is the plain
rotation.

  * norm: x * rsqrt(mean(x^2) + eps) * w, float32, w one at the start
  * layer l: h = x + Attn(Norm(x)); out = h + MoE(Norm(h)); every layer an
    expert layer; final norm, then an untied head
  * attention: 32 query heads on 4 key-value heads of width 128, no bias;
    q and k normed a head, then rotate-half rotary over all 128 features
    at theta 1e7; causal softmax attention over the SELECTED keys only
  * the lightning indexer, on the layer's normed input DETACHED: 16 query
    heads of 64 on ONE key head, both rotated over all 64 features, and 16
    weights a token, times 1 / sqrt(16 * 64) (DeepSeek-V3.2-Exp's two
    factors); I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s]); a
    query's `topk` largest causal scores name its keys (all of them while
    there are no more than `topk`), one set for all 32 heads
    (`ops/dsa.py`, which also says how it runs)
  * the indexer's loss (the sparse training stage of DeepSeek-V3.2-Exp):
    KL(p_t || softmax over the selected keys of I[t]) with p_t the 32
    heads' mean probability, detached; a mean over tokens, sown a layer
    (`dsa_metrics`: `index_kl`, beside `selected_fraction`, selected pairs
    over causal pairs, and `live_tile_fraction`, the backward kernels'
    causal tiles that hold a selected pair) for `train/objectives.py` `keye_vl_loss_fn`, which
    adds the layers' mean to the loss. It moves `indexer_*` and nothing
    else; the cross-entropy and the balance term move everything else
  * MoE (`HeldExpertsMoE` of models/mixers.py, softmax scoring, NO shared
    expert): softmax over all `router_experts` in float32, top-k
    renormalised; this device computes the experts it holds, [first_expert,
    first_expert + num_experts), with no exchange

The residual stream is float32; products take bfloat16 operands over
float32 weights (`dtype`); softmaxes, norms, the index scores'
accumulation and both losses are float32. Not here: decode (no cache holds
the indexer's keys, no decode step selects: ROADMAP R-M13), the image
tower, the indexer's dense warm-up stage.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from solvingpapers_tpu import ops
from solvingpapers_tpu.models.layers import (
    _by_blocks, remat_keeping, training_only,
)
from solvingpapers_tpu.models.mixers import HeldExpertsMoE
from solvingpapers_tpu.ops import dsa
from solvingpapers_tpu.sharding import get_ambient_mesh

# every matrix starts as the family does: normal, initializer_range 0.02
_INIT = nn.initializers.normal(0.02)
# tokens a block of the per-token stages (projections, `o_proj`); read at
# call time
SEGMENT = 2048


@dataclasses.dataclass(frozen=True)
class KeyeVLConfig:
    # --- the source's config.json, under its names
    vocab_size: int = 151_936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 10_000_000.0
    rms_norm_eps: float = 1e-6
    # experts HELD by this device (the source's count when it holds all);
    # the source states the count under both names
    num_experts: int = 128
    num_local_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    # sa_config
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    indexer_num_kv_heads: int = 1
    topk: int = 2048
    # --- this repo's
    # the router's width: every expert of the layer, here or elsewhere
    router_experts: int = 128
    first_expert: int = 0  # global index of the first expert held
    block_size: int = 16_384
    router_aux_loss_coef: float = 0.001
    capacity_factor: float = 2.0
    remat: bool = True
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.num_local_experts != self.num_experts:
            raise ValueError("num_experts and num_local_experts are one "
                             "count under two names")
        last = self.router_experts - self.num_experts
        if not 0 <= self.first_expert <= last:
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert} + "
                f"{self.num_experts}) are not among the router's "
                f"{self.router_experts}"
            )
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must be a multiple of their groups")
        if self.indexer_num_kv_heads != 1:
            raise ValueError("the indexer here has ONE key head")

    @property
    def compute_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)


class SelectedAttention(nn.Module):
    """Norm(x) -> attention over the indexer's keys. The input norm is
    applied here (`norm_w` is its weight); the per-token stages run block by
    block (`_by_blocks`): attention's projections with the heads' norms
    (`L_attn_proj`), the indexer's three (`L_dsa_index`), `o_proj`; the
    rotations are applied to the whole sequence between them (a block
    carries no positions). Sows the layer's `index_kl` and
    `selected_fraction`."""

    cfg: KeyeVLConfig

    @nn.compact
    def __call__(self, x, norm_w):
        cfg = self.cfg
        b, s, d = x.shape
        n, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        j, di = cfg.indexer_num_heads, cfg.indexer_head_dim
        dt, eps, f32 = cfg.compute_dtype, cfg.rms_norm_eps, jnp.float32
        ones = nn.initializers.ones
        with jax.named_scope("L_attn_proj"):
            w_q = self.param("q_proj", _INIT, (d, n * hd)).astype(dt)
            w_k = self.param("k_proj", _INIT, (d, kv * hd)).astype(dt)
            w_v = self.param("v_proj", _INIT, (d, kv * hd)).astype(dt)
            w_o = self.param("o_proj", _INIT, (n * hd, d)).astype(dt)
            q_norm = self.param("q_norm", ones, (hd,))
            k_norm = self.param("k_norm", ones, (hd,))
        with jax.named_scope("L_dsa_index"):
            w_qi = self.param("indexer_q_proj", _INIT, (d, j * di)).astype(dt)
            w_ki = self.param("indexer_k_proj", _INIT, (d, di)).astype(dt)
            w_wi = self.param("indexer_weights_proj", _INIT, (d, j)).astype(dt)

        def before(x):
            hid = ops.rms_norm(x, norm_w, eps).astype(dt)
            lead = hid.shape[:2]
            q = (hid @ w_q).reshape(lead + (n, hd)).astype(f32)
            k = (hid @ w_k).reshape(lead + (kv, hd)).astype(f32)
            return (ops.rms_norm(q, q_norm, eps), ops.rms_norm(k, k_norm, eps),
                    (hid @ w_v).reshape(lead + (kv, hd)))

        def indexer(x):
            hid = jax.lax.stop_gradient(
                ops.rms_norm(x, norm_w, eps)).astype(dt)
            lead = hid.shape[:2]
            # the weights carry DeepSeek-V3.2-Exp's two factors, 1 /
            # sqrt(heads) and 1 / sqrt(head width): no selection moves, the
            # KL's softmax gets its temperature
            return ((hid @ w_qi).reshape(lead + (j, di)), hid @ w_ki,
                    jnp.dot(hid, w_wi, preferred_element_type=f32)
                    * (j * di) ** -0.5)

        with jax.named_scope("L_attn_proj"):
            q, k, v = _by_blocks(before, SEGMENT, x)
            # the indexer's key stays float32 up to the block that multiplies
            # with it (its gradient adds up over the query blocks in
            # float32); k and v are cast once, where the kernels take them
            q = ops.partial_rotary(q, hd, cfg.rope_theta).astype(dt)
            k = ops.partial_rotary(k, hd, cfg.rope_theta)
        with jax.named_scope("L_dsa_index"):
            qi, ki, wi = _by_blocks(indexer, SEGMENT, x)
            qi = ops.partial_rotary(qi.astype(f32), di, cfg.rope_theta
                                    ).astype(dt)
            ki = ops.partial_rotary(ki.astype(f32)[:, :, None, :], di,
                                    cfg.rope_theta)[:, :, 0, :]
        ctx, kl, selected, live_tiles = dsa.selected_attention(
            q, k, v, qi, ki, wi, topk=cfg.topk, scale=hd ** -0.5,
            mesh=get_ambient_mesh())
        with jax.named_scope("L_dsa_loss"):
            self.sow("dsa_metrics", "stats", {
                "index_kl": kl / (b * s),
                "selected_fraction": selected / (b * s * (s + 1) / 2),
                "live_tile_fraction": live_tiles,
            })
        with jax.named_scope("L_attn_proj"):
            return _by_blocks(lambda c: c @ w_o, SEGMENT,
                              ctx.reshape(b, s, n * hd))


def held_moe(cfg: KeyeVLConfig, name: str | None = None) -> HeldExpertsMoE:
    """The layer as this family's config words it: no shared expert."""
    return HeldExpertsMoE(
        router_experts=cfg.router_experts, held=cfg.num_experts,
        first_expert=cfg.first_expert, top_k=cfg.num_experts_per_tok,
        expert_hidden=cfg.moe_intermediate_size, shared_hidden=0,
        capacity_factor=cfg.capacity_factor, dtype=cfg.compute_dtype,
        renorm=cfg.norm_topk_prob, name=name,
    )


class KeyeVLLayer(nn.Module):
    cfg: KeyeVLConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        ones = nn.initializers.ones
        in_norm = self.param("input_norm", ones, (cfg.hidden_size,))
        post_norm = self.param("post_norm", ones, (cfg.hidden_size,))
        h = SelectedAttention(cfg, name="attn")(x, in_norm)
        with jax.named_scope("L_attn_proj"):
            x = x + h.astype(jnp.float32)
        with jax.named_scope("L_moe_gate"):
            h = ops.rms_norm(x, post_norm, cfg.rms_norm_eps)
        h = held_moe(cfg, name="moe")(h)
        with jax.named_scope("L_moe_combine"):
            return x + h


class KeyeVL(nn.Module):
    cfg: KeyeVLConfig

    @nn.compact
    def __call__(self, tokens, *, caches=None, head: bool = True):
        """(B, S) tokens -> ((B, S, V) logits, None), as the other families
        return (logits, caches); with `head` False the normed hidden states
        (B, S, D) in the compute dtype instead, for a loss that applies
        `lm_head` itself a chunk of rows at a time (`keye_vl_loss_fn`, which
        takes the kernel from `head_kernel`). Training and scoring only."""
        cfg = self.cfg
        training_only(
            "keye_vl", cfg, tokens, caches,
            "no cache here holds the indexer's keys beside the keys and "
            "values, and no decode step selects (ROADMAP R-M13)")
        with jax.named_scope("L_embed"):
            x = nn.Embed(
                cfg.vocab_size, cfg.hidden_size, dtype=jnp.float32,
                embedding_init=_INIT, name="tok_emb",
            )(tokens)
        # the selection and the attention over it are kept, not made again:
        # a layer's mask (256 MiB at 16,384 tokens), the forward kernel's
        # output (128 MiB) and log-sum-exp, and the two sums; everything
        # else of a layer is made again in the backward pass
        layer_cls = remat_keeping(KeyeVLLayer, cfg.remat, *dsa.DSA_RESIDUALS)
        for i in range(cfg.num_hidden_layers):
            x = layer_cls(cfg, name=f"layer_{i}")(x)
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
