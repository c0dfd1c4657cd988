"""The sequence mixers and the expert layer that more than one family runs
(L2): `HeldExpertsMoE` (`qwen3next`, `kimi_linear`, `nemotron_h`,
`keye_vl`; each words it from its own config in its `held_moe`),
`Mamba2Mixer` and `NoPEAttention`
(`nemotron_h`, `granite_hybrid`; what they read of a family's config is
`Mamba2Config` / `NoPEAttentionConfig`), `delta_a_log_init` (`qwen3next`'s
Gated DeltaNet, `kimi_linear`'s KDA). A family's file imports from here and
from `models/layers.py`, never from another family
(`tests/test_layering.py`); a mixer with one family stays in its file.
"""

from __future__ import annotations

import math
from typing import Callable, Protocol

import jax
import jax.numpy as jnp
from flax import linen as nn

from solvingpapers_tpu import ops
from solvingpapers_tpu.kernels import moe_grouped
from solvingpapers_tpu.models.layers import (
    GLUFFN, MLP, _by_blocks, causal_attention,
)
from solvingpapers_tpu.ops import ssd
from solvingpapers_tpu.ops.conv import causal_depthwise_conv

HI = jax.lax.Precision.HIGHEST
# every matrix starts normal(0, 0.02): the `initializer_range` of every
# family that runs these
_INIT = nn.initializers.normal(0.02)


def delta_a_log_init(key, shape, dtype=jnp.float32):
    """A delta rule's `A_log`: log U(0, 16), the low end held off zero so the
    log stays finite."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


class HeldExpertsMoE(nn.Module):
    """One expert-parallel rank's MoE layer: routes over all
    `router_experts`, computes the `held` experts [first_expert,
    first_expert + held). The routing is the family's: "softmax" scores
    over all experts, the top_k largest, renormalised when `renorm`, a
    shared expert behind its own sigmoid gate, and the sums the family's
    balance loss needs; or "sigmoid" scores, the top_k largest of score +
    a selection bias that takes no gradient (the parameter `select_bias`),
    the scores themselves as weights, renormalised when `renorm`, times
    `scale`, and a shared expert added as it is. `shared_hidden` 0 is a
    layer with NO shared expert (`keye_vl`): neither its leaves nor its
    gate's exist, and the scope `L_moe_shared` holds nothing. An expert,
    and the shared one, is the gated unit w3 (act(w1 x) * w2 x) or, with
    `gated` false, the two-matrix w3 act(w1 x) (no `w2`; the shared one a
    plain `MLP`): the `nemotron_h` family's squared-ReLU experts. On one
    TPU, at a capacity of whole row tiles, the routed experts' unit runs as the
    kernels of `kernels/moe_grouped.py` over the tiles of rows that hold a
    token (`moe_grouped.engages`; the sown `live_tile_fraction` says how
    many); everywhere else as einsums over every slot."""

    router_experts: int
    held: int
    first_expert: int
    top_k: int
    expert_hidden: int
    shared_hidden: int
    capacity_factor: float
    dtype: jnp.dtype
    scoring: str = "softmax"
    renorm: bool = True
    scale: float = 1.0
    gated: bool = True
    activation: Callable[[jax.Array], jax.Array] = ops.silu

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        t = b * s
        held, h, k, dt = self.held, self.expert_hidden, self.top_k, self.dtype
        softmax = self.scoring == "softmax"
        with jax.named_scope("L_moe_gate"):
            x32 = x.reshape(t, d).astype(jnp.float32)
            xt = x32.astype(dt)
            logits = nn.Dense(
                self.router_experts, use_bias=False, dtype=jnp.float32,
                precision=HI, kernel_init=_INIT, name="gate",
            )(x32)
            if softmax:
                pair_w, pair_idx, probs = ops.moe.topk_renorm_weights(
                    logits, k, self.renorm
                )
            else:
                bias = self.param("select_bias", _INIT,
                                  (self.router_experts,))
                pair_w, pair_idx, probs = ops.moe.topk_sigmoid_weights(
                    logits, bias, k, self.renorm, self.scale
                )
        w1 = self.param("w1", _INIT, (held, d, h))
        w2 = self.param("w2", _INIT, (held, d, h)) if self.gated else None
        w3 = self.param("w3", _INIT, (held, h, d))

        # capacity from the layer's whole width: an expert's fair share of
        # the routed pairs is the same whichever device holds it
        cap = ops.moe.expert_capacity(
            t, self.router_experts, k, self.capacity_factor
        )
        # by what the call can see, no flag: on one TPU, with whole row
        # tiles, the same unit over the tiles of rows that hold a token
        # (`kernels/moe_grouped.py`); the slots behind an expert's fill are
        # zero rows, which give zero rows either way
        grouped = moe_grouped.engages(cap, d)

        def expert_fn(xe, fill):  # (held, C, D), (held,) -> (held, C, D)
            if grouped:
                return moe_grouped.grouped_glu(
                    xe, w1.astype(dt), w2 if w2 is None else w2.astype(dt),
                    w3.astype(dt), fill, activation=self.activation)
            a = jnp.einsum("ecd,edh->ech", xe, w1.astype(dt))
            if self.gated:
                g = jnp.einsum("ecd,edh->ech", xe, w2.astype(dt))
                a = self.activation(a) * g
            else:
                a = self.activation(a)
            return jnp.einsum("ech,ehd->ecd", a, w3.astype(dt))

        out, held_probs = ops.moe.moe_held_dispatch_combine(
            xt, pair_w, pair_idx, expert_fn, cap, self.first_expert, held
        )
        if self.shared_hidden:
            with jax.named_scope("L_moe_shared"):
                shared = (GLUFFN if self.gated else MLP)(
                    dim=d, hidden_dim=self.shared_hidden, use_bias=False,
                    activation=self.activation, dtype=dt,
                    name="shared_expert",
                )(xt).astype(jnp.float32)
                if softmax:
                    shared = jax.nn.sigmoid(nn.Dense(
                        1, use_bias=False, dtype=jnp.float32,
                        kernel_init=_INIT, name="shared_gate",
                    )(x32)) * shared
                out = out.astype(jnp.float32) + shared
        else:
            with jax.named_scope("L_moe_combine"):
                out = out.astype(jnp.float32)

        if self.is_mutable_collection("moe_metrics"):
            with jax.named_scope("L_moe_stats"):
                # over all experts, the share of tokens that chose each
                chosen = jnp.sum(
                    pair_idx[..., None] == jnp.arange(self.router_experts),
                    axis=(0, 1), dtype=jnp.float32) / t
                if softmax:
                    # with each one's mean probability, what the family's
                    # balance loss needs
                    self.sow("moe_metrics", "balance", {
                        "chosen": chosen,
                        "prob": jnp.mean(probs, axis=0),
                    })
                on_held = (pair_idx >= self.first_expert) & (
                    pair_idx < self.first_expert + held)
                stats = ops.moe.load_balance_stats(probs, ci=chosen)
                stats["held_pair_fraction"] = jnp.mean(
                    on_held.astype(jnp.float32))
            stats["drop_fraction"] = ops.moe.dispatch_drop_fraction(
                held_probs, cap)
            # share of the experts' row tiles that are multiplied: 1 where
            # the einsums run over every slot
            stats["live_tile_fraction"] = (
                ops.moe.live_tile_fraction(
                    held_probs, cap, moe_grouped.ROW_TILE)
                if grouped else jnp.ones(()))
            self.sow("moe_metrics", "stats", stats)
        with jax.named_scope("L_moe_combine"):
            return out.reshape(b, s, d)


class Mamba2Config(Protocol):
    """What `Mamba2Mixer` reads of its `cfg`, under `NemotronHConfig`'s
    names: a field or a property serves (nothing is written)."""

    hidden_size: int
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    d_inner: int  # heads x head width
    conv_dim: int  # d_inner + 2 * n_groups * ssm_state_size: [x | B | C]
    conv_kernel: int
    chunk_size: int
    layer_norm_epsilon: float
    time_step_min: float  # the step at the start: log-uniform, floored
    time_step_max: float
    time_step_floor: float
    compute_dtype: jnp.dtype


class NoPEAttentionConfig(Protocol):
    """What `NoPEAttention` reads of its `cfg`, as `Mamba2Config`."""

    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    attention_scale: float  # what multiplies q k^T before the softmax
    layer_norm_epsilon: float
    use_flash: bool
    compute_dtype: jnp.dtype


def _dt_bias_init(cfg):
    """The inverse softplus of a step drawn log-uniformly between
    `time_step_min` and `time_step_max`, floored at `time_step_floor`."""
    lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)

    def init(key, shape, dtype=jnp.float32):
        step = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, dtype, lo,
                                                      hi)),
                           cfg.time_step_floor)
        return step + jnp.log(-jnp.expm1(-step))

    return init


def _mamba_a_log_init(key, shape, dtype=jnp.float32):
    """Mamba-2's `A_log`: log U(1, 16), the families' range for -a."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Mamba2Mixer(nn.Module):
    """Norm(x) -> the Mamba-2 mixer. The input norm is applied here
    (`norm_w` is its weight), inside the first of the two per-token stages
    that run block by block (`_by_blocks`): projections and step before the
    rule, gated norm and `out_proj` after it. Each stage is one loop under
    its own scope, so a device trace tells projections, convolution and rule
    apart. `cfg` is any family's config that answers to `Mamba2Config`
    (`NemotronHConfig`: 8 groups, chunks of 128; `GraniteHybridConfig`: one
    group of 64 heads, chunks of 256); the residual add is the caller's."""

    cfg: Mamba2Config

    @nn.compact
    def __call__(self, x, norm_w):
        cfg = self.cfg
        b, s, _ = x.shape
        h, p = cfg.mamba_num_heads, cfg.mamba_head_dim
        g, n = cfg.n_groups, cfg.ssm_state_size
        d_in, d_conv = cfg.d_inner, cfg.conv_dim
        dt = cfg.compute_dtype
        # one weight, [z | xBC | dt] by columns; each part leaves by its own
        # product, so that no slice of the output is copied
        w_in = self.param("in_proj", _INIT,
                          (cfg.hidden_size, d_in + d_conv + h)).astype(dt)
        k_conv = cfg.conv_kernel
        conv_w = self.param(
            "conv_w", nn.initializers.normal((3.0 * k_conv) ** -0.5),
            (k_conv, d_conv))
        conv_b = self.param(
            "conv_b", nn.initializers.normal((3.0 * k_conv) ** -0.5),
            (d_conv,))
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (h,))
        a_log = self.param("A_log", _mamba_a_log_init, (h,))
        skip = self.param("D", nn.initializers.ones, (h,))
        w_n = self.param("norm_weight", nn.initializers.ones, (d_in,))
        w_out = self.param("out_proj", _INIT,
                           (d_in, cfg.hidden_size)).astype(dt)

        def before(x):
            hid = ops.rms_norm(x.astype(jnp.float32), norm_w,
                               cfg.layer_norm_epsilon).astype(dt)
            step = jax.nn.softplus(
                (hid @ w_in[:, d_in + d_conv:]).astype(jnp.float32)
                + dt_bias)
            xbc, z = hid @ w_in[:, d_in:d_in + d_conv], hid @ w_in[:, :d_in]
            return xbc, z, step

        @jax.checkpoint
        def conv(xbc):
            # the bias enters BEFORE the SiLU; the backward starts again
            # from the convolution's input, so its output is not kept
            y = causal_depthwise_conv(xbc, conv_w, False)
            return jax.nn.silu(y + conv_b.astype(y.dtype))

        def after(y, z):
            u = ssd.gate_then_group_norm(y, z, w_n, g,
                                         cfg.layer_norm_epsilon)
            return u @ w_out

        with jax.named_scope("L_ssm_proj"):
            xbc, z, step = _by_blocks(before, ssd.SEGMENT, x)
        with jax.named_scope("L_ssm_conv"):
            xbc = conv(xbc)
            xs = xbc[..., :d_in].reshape(b, s, h, p)
            bs = xbc[..., d_in:d_in + g * n].reshape(b, s, g, n)
            cs = xbc[..., d_in + g * n:].reshape(b, s, g, n)
        with jax.named_scope("L_ssm_core"):
            y, _ = ssd.ssd_chunked(xs, step, -jnp.exp(a_log), bs, cs, skip,
                                   chunk=cfg.chunk_size)
        with jax.named_scope("L_ssm_proj"):
            return _by_blocks(after, ssd.SEGMENT, y.reshape(b, s, d_in), z)


class NoPEAttention(nn.Module):
    """Norm(x) -> grouped-query causal attention without positions. As in
    `Mamba2Mixer` the input norm is applied here and the per-token stages
    run block by block: the three projections before the attention product,
    `o_proj` after it. The softmax's scale is the config's
    `attention_scale` (`cfg`: `NoPEAttentionConfig`)."""

    cfg: NoPEAttentionConfig

    @nn.compact
    def __call__(self, x, norm_w):
        cfg = self.cfg
        b, s, d = x.shape
        n, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        dt = cfg.compute_dtype
        w_q = self.param("q_proj", _INIT, (d, n * hd)).astype(dt)
        w_k = self.param("k_proj", _INIT, (d, kv * hd)).astype(dt)
        w_v = self.param("v_proj", _INIT, (d, kv * hd)).astype(dt)
        w_out = self.param("o_proj", _INIT, (n * hd, d)).astype(dt)

        def before(x):
            hid = ops.rms_norm(x.astype(jnp.float32), norm_w,
                               cfg.layer_norm_epsilon).astype(dt)
            lead = hid.shape[:2]
            return ((hid @ w_q).reshape(lead + (n, hd)),
                    (hid @ w_k).reshape(lead + (kv, hd)),
                    (hid @ w_v).reshape(lead + (kv, hd)))

        with jax.named_scope("L_attn_proj"):
            q, k, v = _by_blocks(before, ssd.SEGMENT, x)
        ctx = causal_attention(self, q, k, v, scale=cfg.attention_scale,
                               use_flash=cfg.use_flash)
        with jax.named_scope("L_attn_proj"):
            return _by_blocks(lambda c: c @ w_out, ssd.SEGMENT,
                              ctx.reshape(b, s, n * hd).astype(dt))
