"""Plain reference of Keye-VL-2.0's language model (`model_type: KeyeVL2`,
huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B config.json; text tokens only)
as one expert-parallel rank trains it in the sparse stage: forward pass,
the three terms of the loss, gradients and the AdamW step in `jax.numpy`,
float32, under `jax.default_matmul_precision("highest")` and with every
matrix product at `precision="highest"`. No kernel, no dispatch, no remat
policy: attention runs in blocks of query rows against all the keys under
the block's mask, the selection is `jax.lax.top_k` on the float32 index
scores, and every expert held runs on every token and is masked.

It imports nothing of `solvingpapers_tpu` and takes nothing the program
made: the weights come from `make_weights(seed, sizes)`, which the benchmark
also hands to the program.

The equations, with the source's names (`sg` is `stop_gradient`):
  * Norm(x; w) = x * rsqrt(mean(x^2) + eps) * w, w one at the start;
    h = x + Attn(Norm(x)); out = h + MoE(Norm(h)); final norm, untied head.
  * attention: q = RoPE(Norm_128(h W_q)) for 32 heads, k = RoPE(Norm_128(h
    W_k)) and v = h W_v for 4, width 128, no bias, rotate-half rotation
    over all 128 features at `rope_theta` with plain positions (a text
    token's three position axes agree, so `mrope_section` changes nothing).
  * the lightning indexer, on sg(h): qI = RoPE_64(sg(h) W_qI), 16 heads of
    64; kI = RoPE_64(sg(h) W_kI), ONE head; wI = sg(h) W_wI / sqrt(16 * 64),
    16 a token (the two factors of DeepSeek-V3.2-Exp's indexer, 1 /
    sqrt(heads) and 1 / sqrt(head width): a positive scale moves no
    selection, it sets the temperature of the KL's softmax);
    I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s]) for s <= t.
  * S_t = the keys of the `topk` largest I[t, 0..t], all t + 1 of them
    while t < topk; ties go to the lower s. Here: every score above the
    k-th value and, of those equal to it, the first by s as many as are
    still missing.
  * A_h[t] = softmax over s in S_t of q_h[t] . k_g(h)[s] / sqrt(128); a[t] =
    concat_h sum_s A_h[t, s] v_g(h)[s]; x = x + a W_o. One S_t for all
    heads.
  * L_I += (1 / T) sum_t KL(p_t || softmax over s in S_t of I[t, s]), p_t =
    sg(mean_h A_h[t, S_t]): the indexer's loss, DeepSeek-V3.2-Exp's sparse
    training stage. It moves W_qI, W_kI, W_wI and nothing else; the other
    two terms move everything else.
  * MoE: r = softmax(x W_r) over all `router` experts; the `top_k` largest,
    their weights divided by their sum; of those, the pairs on the experts
    held here, [first, first + held), give r_e * down_e(SiLU(gate_e x) *
    up_e x). No shared expert.
  * loss = mean next-token cross-entropy + balance_weight * E * sum_e F_e
    P_e (F the share of tokens that chose expert e, P its mean probability
    over the tokens of all layers) + L_I / layers.

Departures from the source, each because the configuration states it:
  * this is ONE RANK's part: the other experts' share of each MoE layer is
    left out and that partial result goes on to the next layer; the
    vocabulary is the slice the configuration gives; the vision tower is
    not built;
  * experts have the repo's capacity: an expert takes at most
    max(8, 8*ceil(int(T*k/router*cf)/8)) tokens of a call, in token order;
    later ones lose that expert's share (None = no limit);
  * what the source's config does not fix (q- and k-norm, the indexer's
    rotation, the indexer's scale, the tie rule, the loss's weights) is
    the configuration file's `assumed`.

`quant="int8"` is the control of the benchmark's correctness check: the same
mathematics with both operands of every matrix product (the projections,
the indexer's, attention's two products, the experts, the head) rounded to
8-bit integers, the precision below the configuration's bfloat16 that this
chip computes natively. The router stays float32 in it, as the
configuration states it.
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
from benchmarks.reference.kimi_linear_ref import norm
from benchmarks.reference.qwen3next_ref import (
    adam_leaf, capacity, cross_entropy, layer_weights, rotary, silu,
)

# the float32 scores of a block of query rows (heads x keys a row) stay
# under this many bytes: `q_block` is cut to fit (512 rows at 32 heads and
# 16,384 keys; a block's backward holds four such arrays)
SCORE_BYTES = 1 << 30


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    block: int
    dim: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    idx_heads: int
    idx_dim: int
    topk: int
    router: int  # experts the router chooses among
    held: int  # experts computed here
    first: int  # global index of the first one held
    top_k: int
    expert_hidden: int
    renorm: bool = True
    capacity_factor: float | None = None
    balance_weight: float = 0.0
    norm_eps: float = 1e-6
    init_std: float = 0.02


# ---------------------------------------------------------------- weights


def weight_shapes(sz: Sizes) -> dict[str, tuple[tuple[int, ...], object]]:
    """name -> (shape, how it starts): a float is the std of a normal draw,
    "ones" a constant. Every matrix draws with `init_std` (the family's
    initializer_range 0.02); norm weights start at one."""
    d, std = sz.dim, sz.init_std
    out = {"tok_emb": ((sz.vocab, d), std)}
    for i in range(sz.layers):
        p = f"l{i}."
        out[p + "in_norm"] = ((d,), "ones")
        out[p + "q_proj"] = ((d, sz.heads * sz.head_dim), std)
        out[p + "k_proj"] = ((d, sz.kv_heads * sz.head_dim), std)
        out[p + "v_proj"] = ((d, sz.kv_heads * sz.head_dim), std)
        out[p + "q_norm"] = ((sz.head_dim,), "ones")
        out[p + "k_norm"] = ((sz.head_dim,), "ones")
        out[p + "o_proj"] = ((sz.heads * sz.head_dim, d), std)
        out[p + "idx_q"] = ((d, sz.idx_heads * sz.idx_dim), std)
        out[p + "idx_k"] = ((d, sz.idx_dim), std)
        out[p + "idx_w"] = ((d, sz.idx_heads), std)
        out[p + "post_norm"] = ((d,), "ones")
        out[p + "gate"] = ((d, sz.router), std)
        out[p + "w1"] = ((sz.held, d, sz.expert_hidden), std)
        out[p + "w2"] = ((sz.held, d, sz.expert_hidden), std)
        out[p + "w3"] = ((sz.held, sz.expert_hidden, d), std)
    out["norm_f"] = ((d,), "ones")
    out["head"] = ((d, sz.vocab), std)
    return out


def make_weights(seed: int, sz: Sizes) -> dict[str, np.ndarray]:
    """All weights, float32, made on the device in one jitted call and
    handed over ON THE HOST: at the cell's size they are 1.9 GB, and a copy
    that stays on the chip beside the program's own state would leave the
    step less room."""
    shapes = weight_shapes(sz)

    def make(key):
        out = {}
        for i, (name, (shape, how)) in enumerate(shapes.items()):
            if how == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = how * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return jax.device_get(jax.jit(make)(seed_key(seed)))


# ------------------------------------------------------------- arithmetic


def index_scores(qi, wi, ki, quant):
    """qi (B, Q, J, D), wi (B, Q, J), ki (B, K, D) -> I (B, Q, K)."""
    dots = einsum("bqjd,bkd->bqjk", qi, ki, quant)
    return jnp.sum(wi[..., None] * jax.nn.relu(dots), 2)


def select(scores, causal, topk: int):
    """The mask of each row's `topk` largest causal scores (all of them
    where a row has no more): every score above the row's k-th value and, of
    those equal to it, the first by key as many as are still missing."""
    if scores.shape[-1] <= topk:
        return jnp.broadcast_to(causal, scores.shape)
    masked = jnp.where(causal, scores, NEG)
    kth = jax.lax.top_k(masked, topk)[0][..., -1:]
    above, tie = masked > kth, masked == kth
    need = topk - jnp.sum(above, -1, keepdims=True)
    return causal & (above | (tie & (jnp.cumsum(tie, -1) <= need)))


def selected_rows(q, qi, wi, start, keys, vals, ki, scale, topk, quant):
    """A block of query rows from position `start` against the keys. q (B,
    Q, G, R, W), keys, vals (B, K, G, W) -> (a (B, Q, G, R, W), the block's
    sum of KL_t, its selected pairs)."""
    n_q, n_k = q.shape[1], keys.shape[1]
    causal = jnp.arange(n_k)[None, :] <= (start + jnp.arange(n_q))[:, None]
    scores = index_scores(qi, wi, ki, quant)
    sel = jax.lax.stop_gradient(
        select(jax.lax.stop_gradient(scores), causal, topk))
    sc = einsum("bsgrw,btgw->bgrst", q, keys, quant) * scale
    p = jax.nn.softmax(jnp.where(sel[:, None, None], sc, NEG), -1)
    out = einsum("bgrst,btgw->bsgrw", p, vals, quant)
    target = jax.lax.stop_gradient(jnp.mean(p, (1, 2)))
    log_q = jax.nn.log_softmax(jnp.where(sel, scores, NEG), -1)
    live = sel & (target > 0)
    kl = jnp.sum(jnp.where(
        live, target * (jnp.log(jnp.where(live, target, 1.0)) - log_q), 0.0))
    return out, kl, jnp.sum(sel)


def selected_attention(lw, h, sz: Sizes, quant, q_block: int):
    """h (B, S, D), the layer's normed input -> (a W_o (B, S, D), the
    layer's L_I, selected pairs over causal pairs). The query rows go in
    blocks of `q_block`, or fewer where a block's float32 scores would pass
    `SCORE_BYTES`, each against ALL the keys under its mask: one
    rematerialised body for all the blocks (a `lax.map`), because a body a
    block, with a key length of its own, costs the chip's compiler more
    memory than the benchmark's machine has."""
    b, s, _ = h.shape
    n, kv, hd = sz.heads, sz.kv_heads, sz.head_dim
    j, di = sz.idx_heads, sz.idx_dim

    def proj(x, name):
        return einsum("bsd,df->bsf", x, lw[name], quant)

    q = proj(h, "q_proj").reshape(b, s, n, hd)
    k = proj(h, "k_proj").reshape(b, s, kv, hd)
    v = proj(h, "v_proj").reshape(b, s, kv, hd)
    q = rotary(norm(q, lw["q_norm"], sz.norm_eps), hd, sz.rope_theta)
    k = rotary(norm(k, lw["k_norm"], sz.norm_eps), hd, sz.rope_theta)
    hi = jax.lax.stop_gradient(h)
    qi = rotary(proj(hi, "idx_q").reshape(b, s, j, di), di, sz.rope_theta)
    ki = rotary(proj(hi, "idx_k").reshape(b, s, 1, di), di,
                sz.rope_theta)[:, :, 0]
    wi = proj(hi, "idx_w") * (j * di) ** -0.5
    q = q.reshape(b, s, kv, n // kv, hd)
    rows = max(1, min(q_block, SCORE_BYTES // (4 * n * s * b)))
    if s <= rows or s % rows:
        rows = s
    fn = functools.partial(selected_rows, keys=k, vals=v, ki=ki,
                           scale=hd ** -0.5, topk=sz.topk, quant=quant)
    if rows == s:
        ctx, kl, count = fn(q, qi, wi, 0)
    else:
        cut = lambda a: jnp.moveaxis(  # noqa: E731
            a.reshape((b, s // rows, rows) + a.shape[2:]), 1, 0)
        ctx, kl, count = jax.lax.map(
            lambda xs: jax.checkpoint(fn)(*xs),
            (cut(q), cut(qi), cut(wi), jnp.arange(0, s, rows)))
        ctx = jnp.moveaxis(ctx, 0, 1).reshape((b, s) + ctx.shape[3:])
        kl, count = jnp.sum(kl), jnp.sum(count)
    out = einsum("bsf,fd->bsd", ctx.reshape(b, s, n * hd), lw["o_proj"],
                 quant)
    return out, kl / (b * s), count / (b * s * (s + 1) / 2)


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

    def expert(acc, e):
        w1, w2, w3, col = e
        a = einsum("td,dh->th", x, w1, quant)
        u = einsum("td,dh->th", x, w2, quant)
        return acc + col[:, None] * einsum("th,hd->td", silu(a) * u, w3,
                                           quant), None

    out, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x),
                          (lw["w1"], lw["w2"], lw["w3"], w_here.T))
    share = jnp.mean(chosen.astype(jnp.float32), 0)
    return (out, share, jnp.mean(p, 0), jnp.sum(sel),
            jnp.sum(sel) - jnp.sum(keep))


def layer(lw, x, sz: Sizes, quant, q_block: int):
    b, s, d = x.shape
    a, kl, frac = selected_attention(
        lw, norm(x, lw["in_norm"], sz.norm_eps), sz, quant, q_block)
    x = x + a
    h = norm(x, lw["post_norm"], sz.norm_eps)
    y, share, prob, routed, dropped = moe(lw, h.reshape(b * s, d), sz, quant)
    return x + y.reshape(b, s, d), (share, prob, routed, dropped, kl, frac)


def hidden_states(w, tokens, sz: Sizes, quant=None, q_block: int = 4096):
    """tokens (B, S) -> (final normed hidden (B, S, D), per-layer (share,
    prob, routed, dropped, L_I, selected fraction))."""
    x = w["tok_emb"][tokens]
    stats = []
    for i in range(sz.layers):
        fn = jax.checkpoint(functools.partial(
            layer, sz=sz, quant=quant, q_block=q_block))
        x, st = fn(layer_weights(w, i), x)
        stats.append(st)
    return norm(x, w["norm_f"], sz.norm_eps), stats


def loss_fn(w, x, y, sz: Sizes, quant=None, q_block: int = 4096):
    """(total loss, (cross-entropy, balance term, L_I a layer, share of the
    pairs routed here that were dropped, selected pairs over causal pairs;
    means over the layers))."""
    hid, stats = hidden_states(w, x, sz, quant, q_block)
    ce = cross_entropy(w, hid, y, quant)
    share = jnp.mean(jnp.stack([s[0] for s in stats]), 0)
    prob = jnp.mean(jnp.stack([s[1] for s in stats]), 0)
    balance = sz.router * jnp.sum(jax.lax.stop_gradient(share) * prob)
    dropped = jnp.mean(jnp.stack(
        [s[3] / jnp.maximum(s[2], 1) for s in stats]))
    index_kl = jnp.mean(jnp.stack([s[4] for s in stats]))
    selected = jnp.mean(jnp.stack([s[5] for s in stats]))
    return ce + sz.balance_weight * balance + index_kl, (
        ce, balance, index_kl, jax.lax.stop_gradient(dropped),
        jax.lax.stop_gradient(selected))


# ------------------------------------------------------------ training


def follow_training(w0, batches, sz: Sizes, opt: Adam, quant=None,
                    q_block: int = 4096) -> dict:
    """Follow the first len(batches) steps from weights `w0`: gradients of
    the total loss, clipping by the global norm, AdamW with decay on every
    weight. Returns the losses (and their three terms apart, `terms`), the
    global gradient norms (before clipping), the per-weight norms of the
    first (clipped) gradient and of the weights' change over all the steps,
    the dropped shares and the selected fractions.

    Adam's two moments and the starting weights wait on the host and cross
    over a weight at a time, as in the other references."""
    with jax.default_matmul_precision("highest"):
        grads = jax.jit(jax.value_and_grad(functools.partial(
            loss_fn, sz=sz, quant=quant, q_block=q_block), has_aux=True))
        update = jax.jit(functools.partial(adam_leaf, opt=opt),
                         donate_argnums=(0, 3))
        norm_of = jax.jit(global_norm)
        start = {k: np.asarray(v) for k, v in w0.items()}
        w = {k: jnp.asarray(v) for k, v in start.items()}
        mu = {k: np.zeros(v.shape, np.float32) for k, v in start.items()}
        nu = {k: np.zeros(v.shape, np.float32) for k, v in start.items()}
        out = {"loss": [], "grad_norm": [], "dropped": [], "first_grad": {},
               "terms": [], "selected": []}
        for i, (x, y) in enumerate(batches):
            (loss, (ce, bal, kl, dropped, selected)), g = grads(
                w, jnp.asarray(x), jnp.asarray(y))
            gnorm = float(norm_of(g))
            factor = 1.0
            if opt.grad_clip > 0 and not gnorm < opt.grad_clip:
                factor = opt.grad_clip / gnorm
            for k in list(w):
                w[k], m, n, leaf = update(w[k], mu[k], nu[k], g.pop(k), i,
                                          factor)
                mu[k], nu[k] = np.asarray(m), np.asarray(n)
                if i == 0:
                    out["first_grad"][k] = float(leaf)
            out["loss"].append(float(loss))
            out["terms"].append([float(ce), float(bal), float(kl)])
            out["grad_norm"].append(gnorm)
            out["dropped"].append(float(dropped))
            out["selected"].append(float(selected))
        gap = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        out["delta"] = {k: float(gap(w[k], start[k])) for k in w}
    return out
