"""Plain reference of the Kimi-Linear decoder (`model_type: kimi_linear`,
huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct config.json) as one
expert-parallel rank trains it: forward pass, loss, gradients and the AdamW
step in `jax.numpy`, float32, every matrix product at `precision="highest"`.
No kernel, no chunked rule, no dispatch: Kimi Delta Attention is the
token-by-token recurrence (a `scan` over tokens, rematerialised in blocks so
that its backward fits), attention runs in blocks of query rows and groups
of heads, and every expert held runs on every token and is masked.

It imports nothing of `solvingpapers_tpu` and takes nothing the program
made: the weights come from `make_weights(seed, sizes)`, which the benchmark
also hands to the program. What it shares with the other references
(`deepseekv3_ref`, `qwen3next_ref`: the rounded `einsum` of the control,
blockwise causal attention, the causal convolution, the blockwise
cross-entropy, AdamW on one weight) is imported from them.

The layer equations (l from 1, as the source numbers its layers):
  * Norm(x) = x * rsqrt(mean(x^2) + eps) * w, w one at the start; h = x +
    Mixer_l(Norm(x)); out = h + FFN_l(Norm(h)); the mixer is latent
    attention where l is in `attn_layers`, else KDA; FFN_l is a dense SwiGLU
    for l <= `dense_layers`, else the MoE; final norm, an untied head. No
    position encoding anywhere, no bias anywhere.
  * KDA (H heads, dk = dv wide): `qkv` gives [q | k | v] as contiguous
    blocks of H*dk columns; a causal depthwise convolution of width `conv`
    over them, then SiLU; q and k normalised to unit length a head, q scaled
    by dk^-0.5. `fob` gives [f | o | b]: f and o dk wide (low rank), b one a
    head. g = -exp(A_log[h]) * softplus(f `f_up` + dt_bias) (H, dk) a token;
    beta = sigmoid(b). A head's state S (dk x dv, zero at the start):
    S <- Diag(exp(g_t)) S;  r = v_t - S^T k_t;  S <- S + k_t (beta_t r)^T;
    o_t = S^T q_t  [which is S_t = (I - beta k k^T) Diag(alpha) S_{t-1} +
    beta k v^T]. Then o * rsqrt(mean(o^2) + eps) * w_n a head, times
    sigmoid(o_low `g_up`); `kda_out`.
  * latent attention: q = `q_proj` x, a head [q_n | q_r] (d_nope + d_rope);
    [c | k_r] = `kva` x (rank + d_rope); c normed; [k_n | v] = `kvb` c a
    head (d_nope + d_v); a head's key is [k_n | k_r], k_r shared by all
    heads, no rotation on either side (`mla_use_nope`); causal softmax at
    (d_nope + d_rope)^-0.5; `o_proj`.
  * MoE: s = sigmoid(x W_r) over all `router` experts; the `top_k` chosen
    are the largest of s + b (b: the weight `bias`, which takes no gradient;
    `num_expert_group` = `topk_group` = 1, so the source's group-limited
    choice is this plain top-k); weights s / sum of the chosen s *
    `route_scale`; of those, the pairs on the experts held here, [first,
    first + held), give w_i * down_i(SiLU(gate_i x) * up_i x); plus the
    shared expert, ungated.
  * loss: mean next-token cross-entropy (the source's config states no
    balance loss).

Departures from the source, each because the configuration states it:
  * the sizes the config does not give follow the family's convention (the
    paper's equations and flash-linear-attention's layer of that name):
    low-rank width dk for the decay and the output gate, A_log one a head,
    dt_bias a key channel, a sigmoid output gate, no bias on either
    low-rank product;
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
  * A_log = log U(1e-3, 16): the low end is held off zero; dt_bias = 1.

`quant="int8"` is the control of the benchmark's correctness check: the same
mathematics with both operands of every matrix product (the projections,
attention's two products, the dense layer, the experts, the head) rounded to
8-bit integers, the precision below the configuration's bfloat16 that this
chip computes natively. The router and the rule's state stay float32 in it,
as the configuration states them.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.deepseekv3_ref import (
    HI, Adam, einsum, global_norm, seed_key,
)
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
    attn_layers: tuple[int, ...]  # numbered from 1, as published
    dense_layers: int  # the first so many layers have a dense FFN
    heads: int
    latent: int  # kv_lora_rank
    nope_dim: int
    rope_dim: int
    v_dim: int
    kda_heads: int
    kda_dim: int  # dk = dv, and the low-rank width
    conv: int
    dense_hidden: int
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

    def is_attention(self, layer: int) -> bool:
        """`layer` from 0."""
        return layer + 1 in self.attn_layers

    def is_dense(self, layer: int) -> bool:
        return layer < self.dense_layers


# ---------------------------------------------------------------- weights


def weight_shapes(sz: Sizes) -> dict[str, tuple[tuple[int, ...], object]]:
    """name -> (shape, how it starts): a float is the std of a normal
    draw, "ones" a constant, "a_log" log U(1e-3, 16). Every matrix draws
    with `init_std` (the family's initializer_range 0.02), the convolution
    with the std of torch's default U(+-conv^-0.5)."""
    d, std = sz.dim, sz.init_std
    n, dk = sz.kda_heads * sz.kda_dim, sz.kda_dim
    out = {"tok_emb": ((sz.vocab, d), std)}
    for i in range(sz.layers):
        p = f"l{i}."
        out[p + "in_norm"] = ((d,), "ones")
        if sz.is_attention(i):
            out[p + "q_proj"] = ((d, sz.heads * (sz.nope_dim + sz.rope_dim)),
                                 std)
            out[p + "kva"] = ((d, sz.latent + sz.rope_dim), std)
            out[p + "kva_norm"] = ((sz.latent,), "ones")
            out[p + "kvb"] = ((sz.latent,
                               sz.heads * (sz.nope_dim + sz.v_dim)), std)
            out[p + "o_proj"] = ((sz.heads * sz.v_dim, d), std)
        else:
            out[p + "qkv"] = ((d, 3 * n), std)
            out[p + "fob"] = ((d, 2 * dk + sz.kda_heads), std)
            out[p + "f_up"] = ((dk, n), std)
            out[p + "g_up"] = ((dk, n), std)
            out[p + "conv"] = ((sz.conv, 3 * n), (3.0 * sz.conv) ** -0.5)
            out[p + "A_log"] = ((sz.kda_heads,), "a_log")
            out[p + "dt_bias"] = ((sz.kda_heads, dk), "ones")
            out[p + "kda_norm"] = ((dk,), "ones")
            out[p + "kda_out"] = ((n, d), std)
        out[p + "post_norm"] = ((d,), "ones")
        if sz.is_dense(i):
            out[p + "mlp_gate"] = ((d, sz.dense_hidden), std)
            out[p + "mlp_up"] = ((d, sz.dense_hidden), std)
            out[p + "mlp_down"] = ((sz.dense_hidden, d), std)
            continue
        out[p + "gate"] = ((d, sz.router), std)
        out[p + "bias"] = ((sz.router,), std)
        out[p + "w1"] = ((sz.held, d, sz.expert_hidden), std)
        out[p + "w2"] = ((sz.held, d, sz.expert_hidden), std)
        out[p + "w3"] = ((sz.held, sz.expert_hidden, d), std)
        out[p + "s_gate"] = ((d, sz.shared_hidden), std)
        out[p + "s_up"] = ((d, sz.shared_hidden), std)
        out[p + "s_down"] = ((sz.shared_hidden, d), std)
    out["norm_f"] = ((d,), "ones")
    out["head"] = ((d, sz.vocab), std)
    return out


def make_weights(seed: int, sz: Sizes) -> dict[str, np.ndarray]:
    """All weights, float32, made on the device in one jitted call and
    handed over ON THE HOST: at the cell's size they are 2.4 GB, and a copy
    that stays on the chip beside the program's own state (9.6 GB with its
    gradients) would leave the step no room."""
    shapes = weight_shapes(sz)

    def make(key):
        out = {}
        for i, (name, (shape, how)) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if how == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif how == "a_log":
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1e-3, 16.0))
            else:
                out[name] = how * jax.random.normal(k, shape, jnp.float32)
        return out

    return jax.device_get(jax.jit(make)(seed_key(seed)))


# ------------------------------------------------------------- arithmetic


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def latent_attention(lw, h, sz: Sizes, quant, q_block: int):
    b, s, _ = h.shape
    n, d_n, d_r, d_v = sz.heads, sz.nope_dim, sz.rope_dim, sz.v_dim
    q = einsum("bsd,df->bsf", h, lw["q_proj"], quant).reshape(
        b, s, n, d_n + d_r)
    kva = einsum("bsd,df->bsf", h, lw["kva"], quant)
    c = norm(kva[..., :sz.latent], lw["kva_norm"], sz.norm_eps)
    kvb = einsum("bsc,cf->bsf", c, lw["kvb"], quant).reshape(
        b, s, n, d_n + d_v)
    k_r = jnp.broadcast_to(kva[..., None, sz.latent:], (b, s, n, d_r))
    k, v = jnp.concatenate([kvb[..., :d_n], k_r], -1), kvb[..., d_n:]
    # a block of query rows holds (heads, q_block, S) float32 scores: 8 GB
    # for 32 heads at 4,096 rows of 16,384, so the heads go in groups of
    # at most 2 GiB of scores, each rematerialised in the backward pass
    group = max(1, min(n, 2 ** 29 // (min(q_block, s) * s)))

    def heads(q, k, v):
        return attention(q[:, :, :, None, :], k, v, (d_n + d_r) ** -0.5,
                         quant, q_block)

    ctx = jnp.concatenate([
        jax.checkpoint(heads)(q[:, :, i:i + group], k[:, :, i:i + group],
                              v[:, :, i:i + group])
        for i in range(0, n, group)], 2)
    return einsum("bsf,fd->bsd", ctx.reshape(b, s, n * d_v), lw["o_proj"],
                  quant)


def delta_rule(q, k, v, g, beta, token_block: int = 128):
    """The recurrence, token by token. q, k (B, S, H, dk) (unit length, q
    scaled), v (B, S, H, dv), g (B, S, H, dk), beta (B, S, H). Blocks of
    `token_block` tokens are rematerialised in the backward pass, so that
    only a block's states and the states between blocks are kept."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]

    def step(state, xs):  # state (B, H, dk, dv)
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None]
        r = v_t - jnp.sum(state * k_t[..., None], -2)
        state = state + k_t[..., None] * (b_t[..., None] * r)[..., None, :]
        return state, jnp.sum(state * q_t[..., None], -2)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    if s % token_block or s == token_block:
        _, o = jax.lax.scan(step, state0, xs)
        return jnp.moveaxis(o, 0, 1)

    @jax.checkpoint
    def block(state, blk):
        return jax.lax.scan(step, state, blk)

    xs = tuple(a.reshape((s // token_block, token_block) + a.shape[1:])
               for a in xs)
    _, o = jax.lax.scan(block, state0, xs)
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)


def kimi_delta_attention(lw, h, sz: Sizes, quant):
    b, s, _ = h.shape
    nh, dk = sz.kda_heads, sz.kda_dim
    n = nh * dk
    qkv = einsum("bsd,df->bsf", h, lw["qkv"], quant)
    fob = einsum("bsd,df->bsf", h, lw["fob"], quant)
    qkv = silu(causal_conv(qkv, lw["conv"]))
    q, k, v = (qkv[..., i * n:(i + 1) * n].reshape(b, s, nh, dk)
               for i in range(3))
    f = einsum("bsr,rf->bsf", fob[..., :dk], lw["f_up"], quant).reshape(
        b, s, nh, dk)
    g = -jnp.exp(lw["A_log"])[:, None] * jax.nn.softplus(f + lw["dt_bias"])
    beta = jax.nn.sigmoid(fob[..., 2 * dk:])
    unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    o = delta_rule(unit(q) * dk ** -0.5, unit(k), v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + sz.norm_eps) * lw["kda_norm"]
    gate = einsum("bsr,rf->bsf", fob[..., dk:2 * dk], lw["g_up"], quant)
    o = o * jax.nn.sigmoid(gate.reshape(b, s, nh, dk))
    return einsum("bsf,fd->bsd", o.reshape(b, s, n), lw["kda_out"], quant)


def glu(x, w_gate, w_up, w_down, quant):
    a = einsum("td,dh->th", x, w_gate, quant)
    u = einsum("td,dh->th", x, w_up, quant)
    return einsum("th,hd->td", silu(a) * u, w_down, quant)


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
        w1, w2, w3, col = e
        return acc + col[:, None] * glu(x, w1, w2, w3, quant), None

    out, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x),
                          (lw["w1"], lw["w2"], lw["w3"], w_here.T))
    out = out + glu(x, lw["s_gate"], lw["s_up"], lw["s_down"], quant)
    return out, jnp.sum(sel), jnp.sum(sel) - jnp.sum(keep)


def layer(lw, x, sz: Sizes, attn: bool, dense: bool, quant, q_block: int):
    b, s, d = x.shape
    h = norm(x, lw["in_norm"], sz.norm_eps)
    if attn:
        x = x + latent_attention(lw, h, sz, quant, q_block)
    else:
        x = x + kimi_delta_attention(lw, h, sz, quant)
    h = norm(x, lw["post_norm"], sz.norm_eps).reshape(b * s, d)
    if dense:
        y = glu(h, lw["mlp_gate"], lw["mlp_up"], lw["mlp_down"], quant)
        routed = dropped = jnp.zeros((), jnp.int32)
    else:
        y, routed, dropped = moe(lw, h, sz, quant)
    return x + y.reshape(b, s, d), (routed, dropped)


def hidden_states(w, tokens, sz: Sizes, quant=None, q_block: int = 4096):
    """tokens (B, S) -> (final normed hidden (B, S, D), the MoE layers'
    (routed, dropped))."""
    x = w["tok_emb"][tokens]
    stats = []
    for i in range(sz.layers):
        fn = jax.checkpoint(functools.partial(
            layer, sz=sz, attn=sz.is_attention(i), dense=sz.is_dense(i),
            quant=quant, q_block=q_block))
        x, st = fn(layer_weights(w, i), x)
        if not sz.is_dense(i):
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

    At the cell's size weights and gradients are 4.8 GB of the chip's 16
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
