"""Ouro-style looped decoder: ONE stack of sandwich-normed RoPE layers run
`total_ut_steps` times with the same weights, a final norm, an untied head
and an exit gate after every pass, for training.

Capability target: the published `ouro` architecture
(huggingface.co/ByteDance/Ouro-2.6B, config.json and `modeling_ouro.py`;
"Scaling Latent Reasoning via Looped Language Models"; the fields of
`OuroConfig` that the source states carry the source's names).

With h = E[x], for pass t = 1..T and layer l = 1..L, the SAME L layers in
every pass:

  * a = Attn_l(Norm(h; input_layernorm)): `num_attention_heads` heads on
    `num_key_value_heads` of `head_dim`, q and k rotated over the whole
    width (`rope_theta`, halves paired as the source's `rotate_half`),
    causal softmax at head_dim^-0.5 through the flash kernels, no bias
  * h = h + Norm(a; input_layernorm_2): the sandwich, a norm on the
    sub-block's OUTPUT before the add
  * m = W_down(silu(W_gate u) * (W_up u)), u = Norm(h;
    post_attention_layernorm); h = h + Norm(m; post_attention_layernorm_2)
  * after layer L: h = Norm(h; norm_f), which is what exits here AND what
    pass t + 1 starts from; z = lm_head(h); the exit gate's logit
    w_g . h + b_g, one Linear(hidden, 1) shared by the passes
  * norm: x * rsqrt(mean(x^2) + eps) * w, float32, w one at the start

The residual stream is float32; products take bfloat16 operands over
float32 weights (`dtype`); the gate's product is float32 (2,048
multiply-adds a token). A weight's gradient adds up over its T uses in
float32. The loss (`train/objectives.py` `ouro_loss_fn`) is the expected
cross-entropy under the exit distribution the gates give, less
`exit_entropy_weight` times that distribution's entropy.

Not here: a decode cache (one a (pass, layer)) and a decode step that
leaves the loop at `early_exit_threshold` (ROADMAP R-M15); sliding windows,
layer types other than full attention, tied embeddings (refused).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from solvingpapers_tpu import ops
from solvingpapers_tpu.kernels.flash_attention import FLASH_RESIDUALS
from solvingpapers_tpu.models.layers import (
    _by_blocks, blocked_swiglu, causal_attention, remat_keeping,
    training_only,
)

# every matrix starts normal(0, 0.02), the family's initializer_range
_INIT = nn.initializers.normal(0.02)
# tokens a block of the per-token stages (read at call time: tests shrink it)
SEGMENT = 2048


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    # --- the source's config.json, under its names
    vocab_size: int = 49_152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    total_ut_steps: int = 4
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    use_sliding_window: bool = False
    sliding_window: int | None = None
    # None: every layer "full_attention", as published
    layer_types: tuple[str, ...] | None = None
    # --- this repo's
    block_size: int = 4096
    remat: bool = True
    use_flash: bool = True
    dtype: str = "bfloat16"
    # beta of the loss: weight of the exit distribution's entropy
    exit_entropy_weight: float = 0.1

    def __post_init__(self):
        unsupported = {
            "hidden_act": self.hidden_act != "silu",
            "tie_word_embeddings": self.tie_word_embeddings,
            "use_sliding_window": self.use_sliding_window,
            "sliding_window": self.sliding_window is not None,
            "layer_types": bool(
                set(self.layer_types or ()) - {"full_attention"}),
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise ValueError(
                f"ouro: no path here for this value of {bad}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must be a multiple of their groups")
        if self.total_ut_steps < 1:
            raise ValueError("total_ut_steps must be at least 1")

    @property
    def compute_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)


class OuroLayer(nn.Module):
    """One layer application: x + Norm(Attn(Norm(x))), then x +
    Norm(SwiGLU(Norm(x))). The per-token stages run block by block
    (`_by_blocks`): norm and projections before the attention product,
    `o_proj`, the sandwich norm and the add after it, and the whole
    feed-forward half; the rotation is applied to the whole sequence
    between them (a block carries no positions)."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, d = x.shape
        n, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        dt, eps = cfg.compute_dtype, cfg.rms_norm_eps
        ones = nn.initializers.ones
        g1 = self.param("input_layernorm", ones, (d,))
        g2 = self.param("input_layernorm_2", ones, (d,))
        g3 = self.param("post_attention_layernorm", ones, (d,))
        g4 = self.param("post_attention_layernorm_2", ones, (d,))
        # the weights' casts belong to the scope that multiplies with them
        # (each runs again in every pass and in its remat)
        with jax.named_scope("L_attn_proj"):
            w_q = self.param("q_proj", _INIT, (d, n * hd)).astype(dt)
            w_k = self.param("k_proj", _INIT, (d, kv * hd)).astype(dt)
            w_v = self.param("v_proj", _INIT, (d, kv * hd)).astype(dt)
            w_o = self.param("o_proj", _INIT, (n * hd, d)).astype(dt)
        ffn = (d, cfg.intermediate_size)
        with jax.named_scope("L_dense_ffn"):
            w_gate = self.param("gate_proj", _INIT, ffn).astype(dt)
            w_up = self.param("up_proj", _INIT, ffn).astype(dt)
            w_down = self.param("down_proj", _INIT, ffn[::-1]).astype(dt)

        def before(x):
            hid = ops.rms_norm(x, g1, eps).astype(dt)
            lead = hid.shape[:2]
            return ((hid @ w_q).reshape(lead + (n, hd)),
                    (hid @ w_k).reshape(lead + (kv, hd)),
                    (hid @ w_v).reshape(lead + (kv, hd)))

        def after(ctx, x):
            a = (ctx @ w_o).astype(jnp.float32)
            return x + ops.rms_norm(a, g2, eps)

        with jax.named_scope("L_attn_proj"):
            q, k, v = _by_blocks(before, SEGMENT, x)
            q = ops.partial_rotary(q, hd, cfg.rope_theta)
            k = ops.partial_rotary(k, hd, cfg.rope_theta)
        ctx = causal_attention(self, q, k, v, scale=hd ** -0.5,
                               use_flash=cfg.use_flash)
        with jax.named_scope("L_attn_proj"):
            x = _by_blocks(after, SEGMENT,
                           ctx.reshape(b, s, n * hd).astype(dt), x)
        return blocked_swiglu(x, g3, w_gate, w_up, w_down, eps=eps,
                              block=SEGMENT, post_norm_w=g4)


class Ouro(nn.Module):
    cfg: OuroConfig

    @nn.compact
    def __call__(self, tokens, *, caches=None, head: bool = True):
        """(B, S) tokens -> ((B, S, V) logits of the LAST pass, None), as
        the other families return (logits, caches): the published
        `early_exit_threshold` of 1 never leaves the loop early. With
        `head` False the T normed states, stacked (T, B, S, D) in the
        compute dtype, and the T gate logits (T, B, S) float32 instead, for
        a loss that applies `lm_head` itself a chunk of rows at a time
        (`ouro_loss_fn`). Training and scoring only: no decode cache."""
        cfg = self.cfg
        training_only(
            "ouro", cfg, tokens, caches,
            "a looped model keeps keys and values a (pass, layer), which no "
            "cache manager here holds yet (ROADMAP R-M15)")
        d = cfg.hidden_size
        with jax.named_scope("L_embed"):
            x = nn.Embed(
                cfg.vocab_size, d, dtype=jnp.float32,
                embedding_init=_INIT, name="tok_emb",
            )(tokens)
        # the flash forward kernel's o and lse are kept, not made again: 32.5
        # MiB a layer application at 16 heads of 2 x 4,096 tokens, 1.02 GiB
        # for the 32, dead before the step's largest live set
        layer_cls = remat_keeping(OuroLayer, cfg.remat, *FLASH_RESIDUALS)
        # ONE set of layers, applied in every pass
        layers = [layer_cls(cfg, name=f"layer_{i}")
                  for i in range(cfg.num_hidden_layers)]
        norm_f = self.param("norm_f", nn.initializers.ones, (d,))
        gate_w = self.param("exit_gate_kernel", _INIT, (d,))
        gate_b = self.param("exit_gate_bias", nn.initializers.zeros, ())
        lm_head = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.compute_dtype,
            kernel_init=_INIT, name="lm_head")
        states, gates = [], []
        for t in range(cfg.total_ut_steps):
            with jax.named_scope(f"ut_{t + 1}"):
                for layer in layers:
                    x = layer(x)
                with jax.named_scope("L_loss_head"):
                    x = ops.rms_norm(x, norm_f, cfg.rms_norm_eps)
                    states.append(x.astype(cfg.compute_dtype))
                with jax.named_scope("L_exit_gate"):
                    gates.append(jnp.sum(x * gate_w, -1) + gate_b)
        if not head and not self.is_initializing():
            return (jnp.stack(states), jnp.stack(gates)), None
        with jax.named_scope("L_loss_head"):
            return lm_head(states[-1]), None
