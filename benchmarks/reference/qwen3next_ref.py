"""Plain reference of the Qwen3-Next decoder (`model_type: qwen3_next`,
huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct config.json) as one
expert-parallel rank trains it: forward pass, loss, gradients and the AdamW
step in `jax.numpy`, float32, every matrix product at `precision="highest"`.
No kernel, no chunked rule, no dispatch: the Gated DeltaNet is the
token-by-token recurrence (a `scan` over tokens, rematerialised in blocks
so that its backward fits), attention runs in blocks of query rows, and
every expert held runs on every token and is masked.

It imports nothing of `solvingpapers_tpu` and takes nothing the program
made: the weights come from `make_weights(seed, sizes)`, which the benchmark
also hands to the program.

The layer equations (l from 0):
  * Norm(x) = x * rsqrt(mean(x^2) + eps) * (1 + w), w zero at the start;
    h = x + Mixer_l(Norm(x)); out = h + MoE(Norm(h)); the mixer is gated
    attention where (l + 1) % interval == 0, else Gated DeltaNet; final
    norm, then an untied head.
  * gated attention: `q_proj` gives, a head, [query | gate]; query and key
    normed a head (the norm above); rotate-half rotary embedding on the
    first `rotary_dim` features; causal softmax attention, scale
    head_dim^-0.5, `heads` query heads on `kv_heads`; the heads' output
    times sigmoid(gate); `o_proj`. No bias anywhere.
  * Gated DeltaNet: `qkvz` gives [q | k | v | z] as contiguous blocks of
    Hk*dk, Hk*dk, Hv*dv, Hv*dv columns (value head h reads key head
    h // (Hv / Hk)); `ba` gives [b | a]; a causal depthwise convolution of
    width `conv`, no bias, over [q | k | v], then SiLU; beta = sigmoid(b);
    g = -exp(A_log) * softplus(a + dt_bias); q and k normalised to unit
    length a head, q scaled by dk^-0.5; a head's state S (dk x dv, zero at
    the start) follows  S <- exp(g_t) S;  r = v_t - S^T k_t;  S <- S + k_t
    (beta_t r)^T;  o_t = S^T q_t;  then o * rsqrt(mean(o^2) + eps) * w_n a
    head, times SiLU(z); `gdn_out`.
  * MoE: p = softmax(x W_g) over all `router` experts; the `top_k` largest,
    their weights divided by their sum; of those, the pairs on the experts
    held here, [first, first + held), give w_i * down_i(SiLU(gate_i x) *
    up_i x); plus sigmoid(x w_s) * SharedExpert(x).
  * loss: mean next-token cross-entropy + balance_weight * E * sum_e F_e
    P_e, F the share of tokens that chose expert e and P its mean
    probability over the tokens of all layers (the family's
    `load_balancing_loss_func`).

Departures from the source, each because the configuration states it:
  * the multi-token-prediction head the family is described with is not in
    the source's `config` and is left out;
  * this is ONE RANK's part: the other experts' share of each MoE layer is
    left out and that partial result goes on to the next layer; the
    vocabulary is the slice the configuration gives;
  * experts have the repo's capacity: an expert takes at most
    max(8, 8*ceil(int(T*k/router*cf)/8)) tokens of a call, in token order;
    later ones lose that expert's share (None = no limit);
  * A_log = log U(1e-3, 16): the low end is held off zero.

`quant="int8"` is the control of the benchmark's correctness check: the same
mathematics with both operands of every matrix product (the projections,
attention's two products, the experts, the head) rounded to 8-bit integers,
the precision below the configuration's bfloat16 that this chip computes
natively. The router and the DeltaNet's state stay float32 in it, as the
configuration states them.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.deepseekv3_ref import (
    HI, NEG, Adam, einsum, global_norm, seed_key,
)


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    block: int
    dim: int
    layers: int
    interval: int
    heads: int
    kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    gdn_k_heads: int
    gdn_v_heads: int
    gdn_k_dim: int
    gdn_v_dim: int
    conv: int
    router: int  # experts the router chooses among
    held: int  # experts computed here
    first: int  # global index of the first one held
    top_k: int
    expert_hidden: int
    shared_hidden: int
    renorm: bool = True
    capacity_factor: float | None = None
    balance_weight: float = 0.0
    norm_eps: float = 1e-6
    init_std: float = 0.02

    def is_attention(self, layer: int) -> bool:
        return (layer + 1) % self.interval == 0


# ---------------------------------------------------------------- weights


def weight_shapes(sz: Sizes) -> dict[str, tuple[tuple[int, ...], object]]:
    """name -> (shape, how it starts): a float is the std of a normal
    draw, "zeros"/"ones" a constant, "a_log" log U(1e-3, 16). Every matrix
    draws with `init_std` (the family's initializer_range 0.02), the
    convolution with the std of torch's default U(+-conv^-0.5)."""
    d, std = sz.dim, sz.init_std
    n_qk, n_v = sz.gdn_k_heads * sz.gdn_k_dim, sz.gdn_v_heads * sz.gdn_v_dim
    out = {"tok_emb": ((sz.vocab, d), std)}
    for i in range(sz.layers):
        p = f"l{i}."
        out[p + "in_norm"] = ((d,), "zeros")
        if sz.is_attention(i):
            out[p + "q_proj"] = ((d, sz.heads * sz.head_dim * 2), std)
            out[p + "k_proj"] = ((d, sz.kv_heads * sz.head_dim), std)
            out[p + "v_proj"] = ((d, sz.kv_heads * sz.head_dim), std)
            out[p + "q_norm"] = ((sz.head_dim,), "zeros")
            out[p + "k_norm"] = ((sz.head_dim,), "zeros")
            out[p + "o_proj"] = ((sz.heads * sz.head_dim, d), std)
        else:
            out[p + "qkvz"] = ((d, 2 * n_qk + 2 * n_v), std)
            out[p + "ba"] = ((d, 2 * sz.gdn_v_heads), std)
            out[p + "conv"] = ((sz.conv, 2 * n_qk + n_v),
                               (3.0 * sz.conv) ** -0.5)
            out[p + "A_log"] = ((sz.gdn_v_heads,), "a_log")
            out[p + "dt_bias"] = ((sz.gdn_v_heads,), "ones")
            out[p + "gdn_norm"] = ((sz.gdn_v_dim,), "ones")
            out[p + "gdn_out"] = ((n_v, d), std)
        out[p + "post_norm"] = ((d,), "zeros")
        out[p + "gate"] = ((d, sz.router), std)
        out[p + "w1"] = ((sz.held, d, sz.expert_hidden), std)
        out[p + "w2"] = ((sz.held, d, sz.expert_hidden), std)
        out[p + "w3"] = ((sz.held, sz.expert_hidden, d), std)
        out[p + "s_gate"] = ((d, sz.shared_hidden), std)
        out[p + "s_up"] = ((d, sz.shared_hidden), std)
        out[p + "s_down"] = ((sz.shared_hidden, d), std)
        out[p + "s_mix"] = ((d, 1), std)
    out["norm_f"] = ((d,), "zeros")
    out["head"] = ((d, sz.vocab), std)
    return out


def make_weights(seed: int, sz: Sizes) -> dict[str, np.ndarray]:
    """All weights, float32, made on the device in one jitted call and
    handed over ON THE HOST: at the cell's size they are 2.5 GB, and a copy
    that stays on the chip beside the program's own state (10 GB with its
    gradients) would leave the step no room."""
    shapes = weight_shapes(sz)

    def make(key):
        out = {}
        for i, (name, (shape, how)) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if how == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            elif how == "ones":
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
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


def silu(x):
    return x * jax.nn.sigmoid(x)


def rotary(x, rotary_dim: int, theta: float):
    """Rotate-half on the first `rotary_dim` features of x (B, S, n, hd)."""
    s, half = x.shape[1], rotary_dim // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], -1)
    return jnp.concatenate([rot * cos + turned * sin, rest], -1)


def attention(q, k, v, scale, quant, q_block: int):
    """Causal attention of q (B, S, G, R, W) (G kv heads, R query heads on
    each) over k, v (B, S, G, W), in blocks of `q_block` query rows."""
    s = q.shape[1]

    def rows(q_blk, keys, vals, start):
        sc = einsum("bsgrw,btgw->bgrst", q_blk, keys, quant) * scale
        qi = start + jnp.arange(q_blk.shape[1])
        mask = jnp.arange(keys.shape[1])[None, :] <= qi[:, None]
        p = jax.nn.softmax(jnp.where(mask, sc, NEG), -1)
        return einsum("bgrst,btgw->bsgrw", p, vals, quant)

    if s <= q_block:
        return rows(q, k, v, 0)
    if s % q_block:
        raise ValueError(f"{s} rows do not divide into blocks of {q_block}")
    return jnp.concatenate([
        jax.checkpoint(functools.partial(rows, start=start))(
            q[:, start:start + q_block], k[:, :start + q_block],
            v[:, :start + q_block])
        for start in range(0, s, q_block)], 1)


def gated_attention(lw, h, sz: Sizes, quant, q_block: int):
    b, s, _ = h.shape
    n, kv, hd = sz.heads, sz.kv_heads, sz.head_dim
    qg = einsum("bsd,df->bsf", h, lw["q_proj"], quant).reshape(b, s, n, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = einsum("bsd,df->bsf", h, lw["k_proj"], quant).reshape(b, s, kv, hd)
    v = einsum("bsd,df->bsf", h, lw["v_proj"], quant).reshape(b, s, kv, hd)
    q = rotary(norm(q, lw["q_norm"], sz.norm_eps), sz.rotary_dim,
               sz.rope_theta)
    k = rotary(norm(k, lw["k_norm"], sz.norm_eps), sz.rotary_dim,
               sz.rope_theta)
    ctx = attention(q.reshape(b, s, kv, n // kv, hd), k, v, hd ** -0.5,
                    quant, q_block)
    ctx = ctx.reshape(b, s, n, hd) * jax.nn.sigmoid(gate)
    return einsum("bsf,fd->bsd", ctx.reshape(b, s, n * hd), lw["o_proj"],
                  quant)


def delta_rule(q, k, v, g, beta, token_block: int = 128):
    """The recurrence, token by token. q, k (B, S, Hv, dk) (unit length, q
    scaled), v (B, S, Hv, dv), g, beta (B, S, Hv). Blocks of `token_block`
    tokens are rematerialised in the backward pass, so that only a block's
    states and the states between blocks are kept."""
    b, s, hv, dk = q.shape
    dv = v.shape[-1]

    def step(state, xs):  # state (B, Hv, dk, dv)
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        r = v_t - jnp.sum(state * k_t[..., None], -2)
        state = state + k_t[..., None] * (b_t[..., None] * r)[..., None, :]
        return state, jnp.sum(state * q_t[..., None], -2)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state0 = jnp.zeros((b, hv, dk, dv), jnp.float32)
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


def causal_conv(x, w):
    """y_t = sum_j w[j] x[t - (K-1) + j] a channel; x (B, S, C), w (K, C)."""
    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + s] * w[j] for j in range(k))


def gated_delta_net(lw, h, sz: Sizes, quant):
    b, s, _ = h.shape
    hk, hv, dk, dv = sz.gdn_k_heads, sz.gdn_v_heads, sz.gdn_k_dim, sz.gdn_v_dim
    n_qk, n_v = hk * dk, hv * dv
    qkvz = einsum("bsd,df->bsf", h, lw["qkvz"], quant)
    ba = einsum("bsd,df->bsf", h, lw["ba"], quant)
    qkv, z = qkvz[..., :2 * n_qk + n_v], qkvz[..., 2 * n_qk + n_v:]
    qkv = silu(causal_conv(qkv, lw["conv"]))
    q = qkv[..., :n_qk].reshape(b, s, hk, dk)
    k = qkv[..., n_qk:2 * n_qk].reshape(b, s, hk, dk)
    v = qkv[..., 2 * n_qk:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(lw["A_log"]) * jax.nn.softplus(ba[..., hv:] + lw["dt_bias"])
    unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(unit(q) * dk ** -0.5, hv // hk, axis=2)
    k = jnp.repeat(unit(k), hv // hk, axis=2)
    o = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + sz.norm_eps) * lw["gdn_norm"]
    o = o * silu(z.reshape(b, s, hv, dv))
    return einsum("bsf,fd->bsd", o.reshape(b, s, n_v), lw["gdn_out"], quant)


def capacity(tokens: int, sz: Sizes) -> int | None:
    if sz.capacity_factor is None:
        return None
    c = int(tokens * sz.top_k / sz.router * sz.capacity_factor)
    return max(8, -(-c // 8) * 8)


def moe(lw, x, sz: Sizes, quant):
    """x (T, D) -> (this rank's output (T, D), share of tokens that chose
    each expert (router,), mean probability (router,), pairs routed here,
    pairs of them dropped)."""
    t = x.shape[0]
    logits = jnp.einsum("td,de->te", x, lw["gate"], precision=HI)
    p = jax.nn.softmax(logits, -1)
    kth = jnp.sort(p, -1)[:, -sz.top_k][:, None]
    chosen = p >= kth
    w = jnp.where(chosen, p, 0.0)
    if sz.renorm:
        w = w / jnp.sum(w, -1, keepdims=True)
    sel = chosen[:, sz.first:sz.first + sz.held]
    cap = capacity(t, sz)
    keep = sel if cap is None else sel & (
        jnp.cumsum(sel.astype(jnp.int32), 0) - 1 < cap)
    w_here = jnp.where(keep, w[:, sz.first:sz.first + sz.held], 0.0)

    def glu(x, w_gate, w_up, w_down):
        a = einsum("td,dh->th", x, w_gate, quant)
        u = einsum("td,dh->th", x, w_up, quant)
        return einsum("th,hd->td", silu(a) * u, w_down, quant)

    def expert(acc, e):
        w1, w2, w3, col = e
        return acc + col[:, None] * glu(x, w1, w2, w3), None

    out, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x),
                          (lw["w1"], lw["w2"], lw["w3"], w_here.T))
    mix = jax.nn.sigmoid(jnp.einsum("td,do->to", x, lw["s_mix"],
                                    precision=HI))
    out = out + mix * glu(x, lw["s_gate"], lw["s_up"], lw["s_down"])
    share = jnp.mean(chosen.astype(jnp.float32), 0)
    return (out, share, jnp.mean(p, 0), jnp.sum(sel),
            jnp.sum(sel) - jnp.sum(keep))


def layer(lw, x, sz: Sizes, attn: bool, quant, q_block: int):
    b, s, d = x.shape
    h = norm(x, lw["in_norm"], sz.norm_eps)
    if attn:
        x = x + gated_attention(lw, h, sz, quant, q_block)
    else:
        x = x + gated_delta_net(lw, h, sz, quant)
    h = norm(x, lw["post_norm"], sz.norm_eps)
    y, share, prob, routed, dropped = moe(lw, h.reshape(b * s, d), sz, quant)
    return x + y.reshape(b, s, d), (share, prob, routed, dropped)


def layer_weights(w, i: int) -> dict:
    p = f"l{i}."
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def hidden_states(w, tokens, sz: Sizes, quant=None, q_block: int = 4096):
    """tokens (B, S) -> (final normed hidden (B, S, D), per-layer (share,
    prob, routed, dropped))."""
    x = w["tok_emb"][tokens]
    stats = []
    for i in range(sz.layers):
        fn = jax.checkpoint(functools.partial(
            layer, sz=sz, attn=sz.is_attention(i), quant=quant,
            q_block=q_block))
        x, st = fn(layer_weights(w, i), x)
        stats.append(st)
    return norm(x, w["norm_f"], sz.norm_eps), stats


def cross_entropy(w, hidden, targets, quant=None, row_block: int = 2048):
    """Mean next-token cross-entropy, the logits made block by block."""
    d = hidden.shape[-1]
    hid, tgt = hidden.reshape(-1, d), targets.reshape(-1)
    rows = hid.shape[0]

    def block_sum(hb, tb):
        lg = einsum("td,dv->tv", hb, w["head"], quant)
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0])

    if rows <= row_block or rows % row_block:
        return block_sum(hid, tgt) / rows
    sums = jax.lax.map(
        lambda a: jax.checkpoint(block_sum)(a[0], a[1]),
        (hid.reshape(-1, row_block, d), tgt.reshape(-1, row_block)))
    return jnp.sum(sums) / rows


def loss_fn(w, x, y, sz: Sizes, quant=None, q_block: int = 4096):
    """(total loss, (cross-entropy, share of the pairs routed here that
    were dropped, mean over the layers))."""
    hid, stats = hidden_states(w, x, sz, quant, q_block)
    ce = cross_entropy(w, hid, y, quant)
    share = jnp.mean(jnp.stack([s[0] for s in stats]), 0)
    prob = jnp.mean(jnp.stack([s[1] for s in stats]), 0)
    balance = sz.router * jnp.sum(jax.lax.stop_gradient(share) * prob)
    dropped = jnp.mean(jnp.stack(
        [s[3] / jnp.maximum(s[2], 1) for s in stats]))
    return ce + sz.balance_weight * balance, (
        ce, jax.lax.stop_gradient(dropped))


# ------------------------------------------------------------ training


def adam_leaf(w, mu, nu, g, count, factor, opt: Adam):
    """AdamW on one weight, decay on every weight; `g` times `factor` is the
    clipped gradient. Returns (w, mu, nu, the clipped gradient's norm)."""
    g = g * factor
    t = count + 1
    mu = opt.b1 * mu + (1 - opt.b1) * g
    nu = opt.b2 * nu + (1 - opt.b2) * jnp.square(g)
    m_hat = mu / (1 - opt.b1 ** t)
    v_hat = nu / (1 - opt.b2 ** t)
    upd = m_hat / (jnp.sqrt(v_hat) + opt.eps) + opt.weight_decay * w
    return w - opt.lr(count) * upd, mu, nu, jnp.sqrt(jnp.sum(jnp.square(g)))


def follow_training(w0, batches, sz: Sizes, opt: Adam, quant=None,
                    q_block: int = 4096) -> dict:
    """Follow the first len(batches) steps from weights `w0`: gradients of
    the total loss, clipping by the global norm, AdamW. Returns the losses,
    the global gradient norms (before clipping), the per-weight norms of
    the first (clipped) gradient and of the weights' change over all the
    steps, and the dropped shares.

    At the cell's size weights and gradients are 5 GB of the chip's 16 and
    the float32 activations most of the rest, so Adam's two moments and
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
