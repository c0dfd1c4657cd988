"""Plain reference of the Granite-4.0-H hybrid decoder (`model_type:
granitemoehybrid` with no routed experts,
huggingface.co/ibm-granite/granite-4.0-h-micro config.json) as one pipeline
stage trains it: forward pass, loss, gradients and the AdamW step in
`jax.numpy`, float32, every matrix product at `precision="highest"`. No
kernel, no chunked rule: the Mamba-2 mixer is the token-by-token recurrence
(a `scan` over tokens, rematerialised in segments so that its backward
fits), attention is dense scores in blocks of query rows, one key-value head
after the other.

It imports nothing of `solvingpapers_tpu` and takes nothing the program
made: the weights come from `make_weights(seed, sizes)`, which the benchmark
also hands to the program. What it shares with the other references (the
rounded `einsum` of the control, blockwise causal attention, the causal
convolution, the token-by-token recurrence, the blockwise cross-entropy,
AdamW on one weight) is imported from them.

The equations, with E the embedding (V, D), which is also the head (tied):
  * x = `emb_scale` * E[tokens]                       embedding_multiplier
  * layer i of kind `pattern[i]` (`M` Mamba-2, `*` attention), with
    Norm(x; w) = x * rsqrt(mean(x^2) + eps) * w:
      h = Norm(x; norm_in)
      M: [z | xBC | dt] = h W_in (D -> d_inner + (d_inner + 2 G N) + H);
         xBC = SiLU(causal depthwise convolution of width `conv` over xBC +
         conv_b) = [x | B | C]; step = softplus(dt + dt_bias), a =
         -exp(A_log) a head; S_t = exp(step_t a) S_{t-1} + step_t x_t B_t^T,
         y_t = S_t C_t + D x_t, EVERY head reading the one group's B and C
         (G = 1); u = Norm(y * SiLU(z); ssm_norm) over all d_inner channels;
         m = u W_out
      *: q = h W_q (`heads` of `head_dim`), k = h W_k, v = h W_v
         (`kv_heads`); m = softmax(`attn_scale` q k^T, causal) v W_o; no
         bias, no rotation; attn_scale is the source's attention_multiplier
         (1/64 at width 64), not head_dim^-0.5
      x = x + `res_scale` * m                          residual_multiplier
      x = x + `res_scale` * W_down (SiLU(W_gate u) * (W_up u)), u = Norm(x;
         norm_post)
  * logits = Norm(x; norm_f) E^T / `logits_scale`     logits_scaling
  * loss: mean next-token cross-entropy.

Departures from the source, each because the configuration states it:
  * the source keeps [W_gate | W_up] as ONE matrix `input_linear` (D -> 2 F)
    and splits its output; here they are two matrices, drawn apart;
  * this is ONE STAGE's part: the layers are the first `layers` of the
    published `layer_types`, the vocabulary is the slice the configuration
    gives (ids and loss over the slice), and this stage holds the final norm
    and the tied head as well, so that the step has a loss;
  * initialisation (the source's config.json states no initializer_range):
    every matrix and the embedding normal(0, 0.02); the convolution and its
    bias normal with the std of torch's default U(+-conv^-0.5); A_log = log
    U(1, 16), D = 1, dt_bias the inverse softplus of a step drawn
    log-uniformly in [`dt_min`, `dt_max`], floored at `dt_floor` (Mamba-2's
    own defaults); norm weights 1.

`quant="int8"` is the control of the benchmark's correctness check: the same
mathematics with both operands of every matrix product (the projections,
attention's two products, the SwiGLU, the head) rounded to 8-bit integers,
the precision below the configuration's bfloat16 that this chip computes
natively. The recurrence's state stays float32 in it, as the configuration
states it.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.deepseekv3_ref import (
    Adam, einsum, global_norm, seed_key,
)
from benchmarks.reference.kimi_linear_ref import norm
from benchmarks.reference.nemotron_h_ref import state_space
from benchmarks.reference.qwen3next_ref import (
    adam_leaf, attention, causal_conv, cross_entropy, layer_weights, silu,
)


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    block: int
    dim: int
    layers: int
    pattern: str  # one character a layer: M or *
    heads: int
    kv_heads: int
    head_dim: int
    attn_scale: float
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    conv: int
    ffn: int
    emb_scale: float = 12.0
    res_scale: float = 0.22
    logits_scale: float = 8.0
    norm_eps: float = 1e-5
    init_std: float = 0.02
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    def __post_init__(self):
        if len(self.pattern) != self.layers or set(self.pattern) - set("M*"):
            raise ValueError(f"pattern {self.pattern!r} for {self.layers} "
                             "layers of kinds M, *")

    def is_attention(self, layer: int) -> bool:
        return self.pattern[layer] == "*"

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim


# ---------------------------------------------------------------- weights


def weight_shapes(sz: Sizes) -> dict[str, tuple[tuple[int, ...], object]]:
    """name -> (shape, how it starts): a float is the std of a normal draw,
    "ones" a constant, "a_log" log U(1, 16), "dt_bias" the inverse softplus
    of a log-uniform step."""
    d, std = sz.dim, sz.init_std
    d_in, gn = sz.d_inner, sz.ssm_groups * sz.ssm_state
    conv_std = (3.0 * sz.conv) ** -0.5
    out = {"tok_emb": ((sz.vocab, d), std)}
    for i, kind in enumerate(sz.pattern):
        p = f"l{i}."
        out[p + "norm_in"] = ((d,), "ones")
        if kind == "M":
            out[p + "in_proj"] = ((d, 2 * d_in + 2 * gn + sz.ssm_heads), std)
            out[p + "conv"] = ((sz.conv, d_in + 2 * gn), conv_std)
            out[p + "conv_b"] = ((d_in + 2 * gn,), conv_std)
            out[p + "dt_bias"] = ((sz.ssm_heads,), "dt_bias")
            out[p + "A_log"] = ((sz.ssm_heads,), "a_log")
            out[p + "D"] = ((sz.ssm_heads,), "ones")
            out[p + "ssm_norm"] = ((d_in,), "ones")
            out[p + "ssm_out"] = ((d_in, d), std)
        else:
            out[p + "q_proj"] = ((d, sz.heads * sz.head_dim), std)
            out[p + "k_proj"] = ((d, sz.kv_heads * sz.head_dim), std)
            out[p + "v_proj"] = ((d, sz.kv_heads * sz.head_dim), std)
            out[p + "o_proj"] = ((sz.heads * sz.head_dim, d), std)
        out[p + "norm_post"] = ((d,), "ones")
        out[p + "gate"] = ((d, sz.ffn), std)
        out[p + "up"] = ((d, sz.ffn), std)
        out[p + "down"] = ((sz.ffn, d), std)
    out["norm_f"] = ((d,), "ones")
    return out


def make_weights(seed: int, sz: Sizes) -> dict[str, np.ndarray]:
    """All weights, float32, made on the device in one jitted call and
    handed over ON THE HOST: at the cell's size they are 3.1 GB, and a copy
    that stays on the chip beside the program's own state (12.4 GB with its
    gradients and moments) would leave the step no room."""
    shapes = weight_shapes(sz)
    lo, hi = math.log(sz.dt_min), math.log(sz.dt_max)

    def make(key):
        out = {}
        for i, (name, (shape, how)) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if how == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif how == "a_log":
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0))
            elif how == "dt_bias":
                step = jnp.maximum(sz.dt_floor, jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, lo, hi)))
                out[name] = step + jnp.log(-jnp.expm1(-step))
            else:
                out[name] = how * jax.random.normal(k, shape, jnp.float32)
        return out

    return jax.device_get(jax.jit(make)(seed_key(seed)))


# ------------------------------------------------------------- arithmetic


def mamba2(lw, h, sz: Sizes, quant):
    bsz, s, _ = h.shape
    nh, p, g, n = sz.ssm_heads, sz.ssm_head_dim, sz.ssm_groups, sz.ssm_state
    d_in, gn = sz.d_inner, g * n
    zxbcdt = einsum("bsd,df->bsf", h, lw["in_proj"], quant)
    z, xbc, dt = (zxbcdt[..., :d_in], zxbcdt[..., d_in:2 * d_in + 2 * gn],
                  zxbcdt[..., 2 * d_in + 2 * gn:])
    xbc = silu(causal_conv(xbc, lw["conv"]) + lw["conv_b"])
    r = nh // g  # head h reads group h // r: with one group, every head
    x = xbc[..., :d_in].reshape(bsz, s, g, r, p)
    b = xbc[..., d_in:d_in + gn].reshape(bsz, s, g, n)
    c = xbc[..., d_in + gn:].reshape(bsz, s, g, n)
    dt = jax.nn.softplus(dt + lw["dt_bias"]).reshape(bsz, s, g, r)
    y = state_space(x, dt, -jnp.exp(lw["A_log"]).reshape(g, r), b, c)
    y = y + lw["D"].reshape(g, r, 1) * x
    # gate first, then the norm over each group's channels: all of them
    u = (y.reshape(bsz, s, d_in) * silu(z)).reshape(bsz, s, g, d_in // g)
    u = u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True) + sz.norm_eps)
    return einsum("bsf,fd->bsd", u.reshape(bsz, s, d_in) * lw["ssm_norm"],
                  lw["ssm_out"], quant)


def gqa_attention(lw, h, sz: Sizes, quant, q_block: int):
    bsz, s, _ = h.shape
    n, kv, hd = sz.heads, sz.kv_heads, sz.head_dim
    rep = n // kv
    q = einsum("bsd,df->bsf", h, lw["q_proj"], quant).reshape(
        bsz, s, kv, rep, hd)
    k = einsum("bsd,df->bsf", h, lw["k_proj"], quant).reshape(bsz, s, kv, hd)
    v = einsum("bsd,df->bsf", h, lw["v_proj"], quant).reshape(bsz, s, kv, hd)

    # a block of query rows holds (heads, q_block, S) float32 scores: 512
    # MiB for one key-value head's 4 query heads at 4,096 rows of 8,192, so
    # the key-value heads go one after the other, each rematerialised in
    # the backward pass
    def heads(xs):
        return attention(*xs, sz.attn_scale, quant, q_block)

    split = lambda a: jnp.moveaxis(a[:, :, :, None], 2, 0)  # noqa: E731
    ctx = jax.lax.map(jax.checkpoint(heads), (split(q), split(k), split(v)))
    ctx = jnp.moveaxis(ctx[:, :, :, 0], 0, 2).reshape(bsz, s, n * hd)
    return einsum("bsf,fd->bsd", ctx, lw["o_proj"], quant)


def swiglu(lw, u, quant):
    a = silu(einsum("bsd,df->bsf", u, lw["gate"], quant)) * einsum(
        "bsd,df->bsf", u, lw["up"], quant)
    return einsum("bsf,fd->bsd", a, lw["down"], quant)


def layer(lw, x, sz: Sizes, kind: str, quant, q_block: int):
    h = norm(x, lw["norm_in"], sz.norm_eps)
    if kind == "M":
        m = mamba2(lw, h, sz, quant)
    else:
        m = gqa_attention(lw, h, sz, quant, q_block)
    x = x + sz.res_scale * m
    u = norm(x, lw["norm_post"], sz.norm_eps)
    return x + sz.res_scale * swiglu(lw, u, quant)


def hidden_states(w, tokens, sz: Sizes, quant=None, q_block: int = 4096):
    """tokens (B, S) -> the final normed hidden states (B, S, D)."""
    x = sz.emb_scale * w["tok_emb"][tokens]
    for i, kind in enumerate(sz.pattern):
        fn = jax.checkpoint(functools.partial(
            layer, sz=sz, kind=kind, quant=quant, q_block=q_block))
        x = fn(layer_weights(w, i), x)
    return norm(x, w["norm_f"], sz.norm_eps)


def logits_of(w, hidden, sz: Sizes, quant=None):
    """The tied head: hidden E^T / logits_scale."""
    return einsum("...d,vd->...v", hidden, w["tok_emb"],
                  quant) / sz.logits_scale


def loss_fn(w, x, y, sz: Sizes, quant=None, q_block: int = 4096):
    """(loss, the cross-entropy): mean over the tokens of -log
    softmax(Norm(x) E^T / logits_scale)[next token], the logits made block
    by block of rows (the division is applied to the rows, which is the
    same number in float32 at a power of two)."""
    hid = hidden_states(w, x, sz, quant, q_block)
    ce = cross_entropy({"head": w["tok_emb"].T}, hid / sz.logits_scale, y,
                       quant)
    return ce, ce


# ------------------------------------------------------------ training


def follow_training(w0, batches, sz: Sizes, opt: Adam, quant=None,
                    q_block: int = 4096) -> dict:
    """Follow the first len(batches) steps from weights `w0`: gradients of
    the loss (the embedding's add up over the lookup and the head),
    clipping by the global norm, AdamW with decay on every weight. Returns
    the losses, the global gradient norms (before clipping), the per-weight
    norms of the first (clipped) gradient and of the weights' change over
    all the steps, and `dropped` (zeros: nothing routes here; the driver
    prints it).

    At the cell's size weights and gradients are 6.2 GB of the chip's 16
    and the float32 activations most of the rest, so Adam's two moments and
    the starting weights wait on the host and cross over a weight at a
    time, as in the other references."""
    grads = jax.jit(jax.value_and_grad(functools.partial(
        loss_fn, sz=sz, quant=quant, q_block=q_block), has_aux=True))
    update = jax.jit(functools.partial(adam_leaf, opt=opt),
                     donate_argnums=(0, 3))
    norm_of = jax.jit(global_norm)
    start = {k: np.asarray(v) for k, v in w0.items()}
    w = {k: jnp.asarray(v) for k, v in start.items()}
    mu = {k: np.zeros(v.shape, np.float32) for k, v in start.items()}
    nu = {k: np.zeros(v.shape, np.float32) for k, v in start.items()}
    out = {"loss": [], "grad_norm": [], "dropped": [], "first_grad": {}}
    for i, (x, y) in enumerate(batches):
        (loss, _), g = grads(w, jnp.asarray(x), jnp.asarray(y))
        gnorm = float(norm_of(g))
        factor = 1.0
        if opt.grad_clip > 0 and not gnorm < opt.grad_clip:
            factor = opt.grad_clip / gnorm
        for k in list(w):
            w[k], m, n, leaf = update(w[k], mu[k], nu[k], g.pop(k), i, factor)
            mu[k], nu[k] = np.asarray(m), np.asarray(n)
            if i == 0:
                out["first_grad"][k] = float(leaf)
        out["loss"].append(float(loss))
        out["grad_norm"].append(gnorm)
        out["dropped"].append(0.0)
    gap = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    out["delta"] = {k: float(gap(w[k], start[k])) for k in w}
    return out
