"""Plain reference of the Nemotron-H decoder (`model_type: nemotron_h`,
huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json) as
one expert-parallel rank trains it: forward pass, loss, gradients and the
AdamW step in `jax.numpy`, float32, every matrix product at
`precision="highest"`. No kernel, no chunked rule, no dispatch: the Mamba-2
layer is the token-by-token recurrence (a `scan` over tokens, rematerialised
in blocks so that its backward fits), attention runs in blocks of query rows
and groups of heads, and every expert held runs on every token and is
masked.

It imports nothing of `solvingpapers_tpu` and takes nothing the program
made: the weights come from `make_weights(seed, sizes)`, which the benchmark
also hands to the program. What it shares with the other references (the
rounded `einsum` of the control, blockwise causal attention, the causal
convolution, the blockwise cross-entropy, AdamW on one weight) is imported
from them.

The layer equations (layer i of kind `pattern[i]`):
  * Norm(x) = x * rsqrt(mean(x^2) + eps) * w, w one at the start; ONE
    sub-block a layer, out = x + Mixer_i(Norm_i(x)): `M` Mamba-2, `E` MoE,
    `*` attention; final norm, an untied head. No position encoding
    anywhere, and no bias but the convolution's.
  * Mamba-2 (H heads of P, d_inner = H * P, G groups, state N): `in_proj`
    gives [z (d_inner) | xBC (d_inner + 2 G N) | dt (H)]; xBC = SiLU(causal
    depthwise convolution of width `conv` over xBC + `conv_b`) = [x | B |
    C], B and C a group; dt = softplus(dt + dt_bias), a = -exp(A_log), one
    number a head. A head's state S (P x N, zero at the start), with g = h
    // (H / G):  S <- exp(dt_t a) S + dt_t x_t B_t^g^T;  y_t = S C_t^g + D
    x_t. Then u = y * SiLU(z), u * rsqrt(mean(u^2) + eps) over each group's
    d_inner / G channels, times `ssm_norm`; `ssm_out`.
  * attention: `q_proj` to `heads` heads of `head_dim`, `k_proj`, `v_proj`
    to `kv_heads`; causal softmax at head_dim^-0.5, each key-value head
    serving heads / kv_heads query heads; `o_proj`. No rotation.
  * MoE: s = sigmoid(x W_r) over all `router` experts; the `top_k` chosen
    are the largest of s + b (b: the weight `bias`, which takes no gradient;
    `n_group` = `topk_group` = 1, so the source's group-limited choice is
    this plain top-k); weights s / sum of the chosen s * `route_scale`; of
    those, the pairs on the experts held here, [first, first + held), give
    w_i * down_i(relu(up_i x)^2); plus the shared expert of the same form,
    added as it is.
  * loss: mean next-token cross-entropy (the source's config states no
    balance loss).

Departures from the source, each because the configuration states it:
  * d_inner is heads x head width (4,096), what the published code takes:
    the config's `expand` 2 would give 5,376, which is no width of this
    model (the published parameter total only comes out with 4,096);
    `rope_theta` and `partial_rotary_factor` are stated and used by no
    layer (the family's attention layers carry no positions);
  * this is ONE RANK's part: the other experts' share of each MoE layer is
    left out and that partial result goes on to the next layer; the
    vocabulary is the slice the configuration gives; the layers are the
    first `layers` of the published pattern;
  * experts have the repo's capacity: an expert takes at most
    max(8, 8*ceil(int(T*k/router*cf)/8)) tokens of a call, in token order;
    later ones lose that expert's share (None = no limit);
  * the selection bias b is a seeded weight (normal, the family's 0.02)
    that no rule updates: it has no gradient and AdamW's decay alone moves
    it, here as in the program;
  * initialisation: every matrix normal(0, 0.02) (the source's
    `rescale_prenorm_residual`, a rule of its initialiser that divides the
    output projections by sqrt(layers), is not applied); the convolution
    and its bias normal with the std of torch's default U(+-conv^-0.5);
    A_log = log U(1, 16), D = 1, dt_bias the inverse softplus of a step
    drawn log-uniformly in [`dt_min`, `dt_max`], floored at `dt_floor`
    (the config's `time_step_*`).

`quant="int8"` is the control of the benchmark's correctness check: the same
mathematics with both operands of every matrix product (the projections,
attention's two products, the experts, the head) rounded to 8-bit integers,
the precision below the configuration's bfloat16 that this chip computes
natively. The router and the recurrence's state stay float32 in it, as the
configuration states them.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.deepseekv3_ref import (
    HI, Adam, einsum, global_norm, seed_key,
)
from benchmarks.reference.kimi_linear_ref import norm
from benchmarks.reference.qwen3next_ref import (
    adam_leaf, attention, capacity, causal_conv, cross_entropy,
    layer_weights, silu,
)


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    block: int
    dim: int
    layers: int
    pattern: str  # one character a layer: M, E or *
    heads: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    conv: int
    router: int  # experts the router chooses among
    held: int  # experts computed here
    first: int  # global index of the first one held
    top_k: int
    expert_hidden: int
    shared_hidden: int
    renorm: bool = True
    route_scale: float = 1.0
    capacity_factor: float | None = None
    norm_eps: float = 1e-5
    init_std: float = 0.02
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    def __post_init__(self):
        if len(self.pattern) != self.layers or set(self.pattern) - set("ME*"):
            raise ValueError(f"pattern {self.pattern!r} for {self.layers} "
                             "layers of kinds M, E, *")

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
        out[p + "norm"] = ((d,), "ones")
        if kind == "M":
            out[p + "in_proj"] = ((d, 2 * d_in + 2 * gn + sz.ssm_heads), std)
            out[p + "conv"] = ((sz.conv, d_in + 2 * gn), conv_std)
            out[p + "conv_b"] = ((d_in + 2 * gn,), conv_std)
            out[p + "dt_bias"] = ((sz.ssm_heads,), "dt_bias")
            out[p + "A_log"] = ((sz.ssm_heads,), "a_log")
            out[p + "D"] = ((sz.ssm_heads,), "ones")
            out[p + "ssm_norm"] = ((d_in,), "ones")
            out[p + "ssm_out"] = ((d_in, d), std)
        elif kind == "*":
            out[p + "q_proj"] = ((d, sz.heads * sz.head_dim), std)
            out[p + "k_proj"] = ((d, sz.kv_heads * sz.head_dim), std)
            out[p + "v_proj"] = ((d, sz.kv_heads * sz.head_dim), std)
            out[p + "o_proj"] = ((sz.heads * sz.head_dim, d), std)
        else:
            out[p + "gate"] = ((d, sz.router), std)
            out[p + "bias"] = ((sz.router,), std)
            out[p + "w_up"] = ((sz.held, d, sz.expert_hidden), std)
            out[p + "w_down"] = ((sz.held, sz.expert_hidden, d), std)
            out[p + "s_up"] = ((d, sz.shared_hidden), std)
            out[p + "s_down"] = ((sz.shared_hidden, d), std)
    out["norm_f"] = ((d,), "ones")
    out["head"] = ((d, sz.vocab), std)
    return out


def make_weights(seed: int, sz: Sizes) -> dict[str, np.ndarray]:
    """All weights, float32, made on the device in one jitted call and
    handed over ON THE HOST: at the cell's size they are 2.7 GB, and a copy
    that stays on the chip beside the program's own state (10.7 GB with its
    gradients) would leave the step no room."""
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


def state_space(x, dt, a, b, c, token_block: int = 128):
    """The recurrence, token by token. x (B, S, G, R, P) (R heads a group),
    dt (B, S, G, R), a (G, R), b, c (B, S, G, N). Returns S_t c_t (B, S, G,
    R, P), without the skip term. Blocks of `token_block` tokens are
    rematerialised in the backward pass, so that only a block's states and
    the states between blocks are kept."""
    bsz, s, g, r, p = x.shape
    n = b.shape[-1]

    def step(state, xs):  # state (B, G, R, P, N)
        x_t, dt_t, b_t, c_t = xs
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None]
                 * b_t[:, :, None, None, :])
        return state, jnp.sum(state * c_t[:, :, None, None, :], -1)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
    state0 = jnp.zeros((bsz, g, r, p, n), jnp.float32)
    if s % token_block or s == token_block:
        _, y = jax.lax.scan(step, state0, xs)
        return jnp.moveaxis(y, 0, 1)

    @jax.checkpoint
    def block(state, blk):
        return jax.lax.scan(step, state, blk)

    xs = tuple(v.reshape((s // token_block, token_block) + v.shape[1:])
               for v in xs)
    _, y = jax.lax.scan(block, state0, xs)
    return jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1)


def mamba2(lw, h, sz: Sizes, quant):
    bsz, s, _ = h.shape
    nh, p, g, n = sz.ssm_heads, sz.ssm_head_dim, sz.ssm_groups, sz.ssm_state
    d_in, gn = sz.d_inner, g * n
    zxbcdt = einsum("bsd,df->bsf", h, lw["in_proj"], quant)
    z, xbc, dt = (zxbcdt[..., :d_in], zxbcdt[..., d_in:2 * d_in + 2 * gn],
                  zxbcdt[..., 2 * d_in + 2 * gn:])
    xbc = silu(causal_conv(xbc, lw["conv"]) + lw["conv_b"])
    r = nh // g  # head h reads group h // r
    x = xbc[..., :d_in].reshape(bsz, s, g, r, p)
    b = xbc[..., d_in:d_in + gn].reshape(bsz, s, g, n)
    c = xbc[..., d_in + gn:].reshape(bsz, s, g, n)
    dt = jax.nn.softplus(dt + lw["dt_bias"]).reshape(bsz, s, g, r)
    y = state_space(x, dt, -jnp.exp(lw["A_log"]).reshape(g, r), b, c)
    y = y + lw["D"].reshape(g, r, 1) * x
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
    # a block of query rows holds (heads, q_block, S) float32 scores: 8 GB
    # for 32 heads at 4,096 rows of 16,384, so the query heads of one
    # key-value head go in groups of at most 2 GiB of scores, each
    # rematerialised in the backward pass
    group = max(1, min(rep, 2 ** 29 // (min(q_block, s) * s)))

    def heads(q, k, v):
        return attention(q, k, v, hd ** -0.5, quant, q_block)

    ctx = jnp.concatenate([
        jnp.concatenate([
            jax.checkpoint(heads)(q[:, :, j:j + 1, i:i + group],
                                  k[:, :, j:j + 1], v[:, :, j:j + 1])
            for i in range(0, rep, group)], 3)
        for j in range(kv)], 2)
    return einsum("bsf,fd->bsd", ctx.reshape(bsz, s, n * hd), lw["o_proj"],
                  quant)


def relu2_mlp(x, w_up, w_down, quant):
    a = jax.nn.relu(einsum("td,dh->th", x, w_up, quant))
    return einsum("th,hd->td", a * a, w_down, quant)


def moe(lw, x, sz: Sizes, quant):
    """x (T, D) -> (this rank's output (T, D), pairs routed here, pairs of
    them dropped)."""
    t = x.shape[0]
    logits = jnp.einsum("td,de->te", x, lw["gate"], precision=HI)
    s = jax.nn.sigmoid(logits)
    steered = s + jax.lax.stop_gradient(lw["bias"])
    kth = jnp.sort(steered, -1)[:, -sz.top_k][:, None]
    chosen = steered >= kth
    w = jnp.where(chosen, s, 0.0)
    if sz.renorm:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * sz.route_scale
    sel = chosen[:, sz.first:sz.first + sz.held]
    cap = capacity(t, sz)
    keep = sel if cap is None else sel & (
        jnp.cumsum(sel.astype(jnp.int32), 0) - 1 < cap)
    w_here = jnp.where(keep, w[:, sz.first:sz.first + sz.held], 0.0)

    def expert(acc, e):
        w_up, w_down, col = e
        return acc + col[:, None] * relu2_mlp(x, w_up, w_down, quant), None

    out, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x),
                          (lw["w_up"], lw["w_down"], w_here.T))
    out = out + relu2_mlp(x, lw["s_up"], lw["s_down"], quant)
    return out, jnp.sum(sel), jnp.sum(sel) - jnp.sum(keep)


def layer(lw, x, sz: Sizes, kind: str, quant, q_block: int):
    bsz, s, d = x.shape
    h = norm(x, lw["norm"], sz.norm_eps)
    routed = dropped = jnp.zeros((), jnp.int32)
    if kind == "M":
        y = mamba2(lw, h, sz, quant)
    elif kind == "*":
        y = gqa_attention(lw, h, sz, quant, q_block)
    else:
        y, routed, dropped = moe(lw, h.reshape(bsz * s, d), sz, quant)
    return x + y.reshape(bsz, s, d), (routed, dropped)


def hidden_states(w, tokens, sz: Sizes, quant=None, q_block: int = 4096):
    """tokens (B, S) -> (final normed hidden (B, S, D), the MoE layers'
    (routed, dropped))."""
    x = w["tok_emb"][tokens]
    stats = []
    for i, kind in enumerate(sz.pattern):
        fn = jax.checkpoint(functools.partial(
            layer, sz=sz, kind=kind, quant=quant, q_block=q_block))
        x, st = fn(layer_weights(w, i), x)
        if kind == "E":
            stats.append(st)
    return norm(x, w["norm_f"], sz.norm_eps), stats


def loss_fn(w, x, y, sz: Sizes, quant=None, q_block: int = 4096):
    """(loss, (cross-entropy, share of the pairs routed here that were
    dropped, mean over the MoE layers))."""
    hid, stats = hidden_states(w, x, sz, quant, q_block)
    ce = cross_entropy(w, hid, y, quant)
    dropped = jnp.mean(jnp.stack(
        [s[1] / jnp.maximum(s[0], 1) for s in stats])) if stats else 0.0
    return ce, (ce, jax.lax.stop_gradient(dropped))


# ------------------------------------------------------------ training


def follow_training(w0, batches, sz: Sizes, opt: Adam, quant=None,
                    q_block: int = 4096) -> dict:
    """Follow the first len(batches) steps from weights `w0`: gradients of
    the loss, clipping by the global norm, AdamW with decay on every
    weight. Returns the losses, the global gradient norms (before
    clipping), the per-weight norms of the first (clipped) gradient and of
    the weights' change over all the steps, and the dropped shares.

    At the cell's size weights and gradients are 5.3 GB of the chip's 16
    and the float32 activations most of the rest, so Adam's two moments and
    the starting weights wait on the host and cross over a weight at a
    time."""
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
        (loss, (_, dropped)), g = grads(w, jnp.asarray(x), jnp.asarray(y))
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
        out["dropped"].append(float(dropped))
    gap = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    out["delta"] = {k: float(gap(w[k], start[k])) for k in w}
    return out
