"""Plain reference of the Ouro looped decoder (`model_type: ouro`,
huggingface.co/ByteDance/Ouro-2.6B config.json and `modeling_ouro.py`; the
family's report "Scaling Latent Reasoning via Looped Language Models") as
one pipeline stage trains it: forward pass, loss, gradients and the AdamW
step in `jax.numpy`, float32, every matrix product at
`precision="highest"`. No kernel: a Python loop over the passes and, inside
it, over the layers, the same weights in every pass; attention in blocks of
query rows and groups of heads.

It imports nothing of `solvingpapers_tpu` and takes nothing the program
made: the weights come from `make_weights(seed, sizes)`, which the benchmark
also hands to the program. What it shares with the other references (the
rounded `einsum` of the control, blockwise causal attention, the rotation,
AdamW on one weight) is imported from them.

The equations. h = E[x]; for pass t = 1..T and layer l = 1..L, the SAME L
layers in every pass:
  * Norm(x; w) = x * rsqrt(mean(x^2) + eps) * w, w one at the start.
  * a = Attn_l(Norm(h; norm1)): `heads` heads on `kv_heads` of `head_dim`,
    q and k rotated over the whole width (theta `rope_theta`, halves paired
    as the source's `rotate_half`), causal softmax at head_dim^-0.5, no
    bias; h = h + Norm(a; norm2): the sandwich, a norm on the sub-block's
    OUTPUT before the add.
  * m = down(silu(gate u) * (up u)), u = Norm(h; norm3); h = h + Norm(m;
    norm4).
  * after layer L of pass t: h = Norm(h; norm_f) =: h^(t), what exits here
    AND what pass t + 1 starts from; z^(t) = head h^(t), the one untied
    head; lambda^(t) = sigmoid(gate_w . h^(t) + gate_b), the exit gate, one
    Linear(dim, 1) with bias shared by the passes.
  * loss, a token i with label y_i: l_i^(t) = -log softmax(z_i^(t))[y_i];
    p_i^(t) = lambda_i^(t) prod_{j<t} (1 - lambda_i^(j)) for t < T, p_i^(T)
    = prod_{j<T} (1 - lambda_i^(j)); L = mean_i [sum_t p_i^(t) l_i^(t) -
    beta H(p_i)], H the entropy of the T exit probabilities: the report's
    stage-I objective, the expected loss under the learned exit
    distribution and an entropy term that is a KL to a uniform prior.

Departures from the source, each because the configuration states it:
  * this is ONE PIPELINE STAGE's part: the layers are the first `layers`
    of the published 48, run in every pass; the stage holds embedding,
    final norm, gate and head too;
  * beta is no key of the source's config: `entropy_weight`, 0.1, assumed;
  * the gate's product is float32 on both sides (the source computes it in
    the model's dtype);
  * every layer application is rematerialised in the backward pass
    (`jax.checkpoint`) and attention runs in groups of heads: memory only,
    the arithmetic is the same;
  * initialisation: every matrix and the gate's weight normal(0, 0.02)
    (the source's initializer_range), norm weights 1, the gate's bias 0.

`quant="int8"` is the control of the benchmark's correctness check: the same
mathematics with both operands of every matrix product (the projections,
attention's two products, the feed-forward, the head) rounded to 8-bit
integers, the precision below the configuration's bfloat16 that this chip
computes natively. The gate stays float32 in it, as the configuration
states it.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.deepseekv3_ref import (
    Adam, einsum, global_norm, seed_key,
)
from benchmarks.reference.kimi_linear_ref import norm
from benchmarks.reference.qwen3next_ref import (
    adam_leaf, attention, layer_weights, rotary, silu,
)


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    block: int
    dim: int
    layers: int  # held here, applied in every pass
    ut_steps: int  # passes
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    entropy_weight: float = 0.1
    init_std: float = 0.02


# ---------------------------------------------------------------- weights


def weight_shapes(sz: Sizes) -> dict[str, tuple[tuple[int, ...], object]]:
    """name -> (shape, how it starts): a float is the std of a normal draw,
    "ones" and "zeros" constants."""
    d, std = sz.dim, sz.init_std
    out = {"tok_emb": ((sz.vocab, d), std)}
    for i in range(sz.layers):
        p = f"l{i}."
        for n in ("norm1", "norm2", "norm3", "norm4"):
            out[p + n] = ((d,), "ones")
        out[p + "q_proj"] = ((d, sz.heads * sz.head_dim), std)
        out[p + "k_proj"] = ((d, sz.kv_heads * sz.head_dim), std)
        out[p + "v_proj"] = ((d, sz.kv_heads * sz.head_dim), std)
        out[p + "o_proj"] = ((sz.heads * sz.head_dim, d), std)
        out[p + "gate"] = ((d, sz.ffn), std)
        out[p + "up"] = ((d, sz.ffn), std)
        out[p + "down"] = ((sz.ffn, d), std)
    out["norm_f"] = ((d,), "ones")
    out["gate_w"] = ((d,), std)
    out["gate_b"] = ((), "zeros")
    out["head"] = ((d, sz.vocab), std)
    return out


def make_weights(seed: int, sz: Sizes) -> dict[str, np.ndarray]:
    """All weights, float32, made on the device in one jitted call and
    handed over ON THE HOST: at the cell's size they are 2.4 GB, and a copy
    that stays on the chip beside the program's own state (9.8 GB with its
    gradients and moments) would leave the step no room."""
    shapes = weight_shapes(sz)

    def make(key):
        out = {}
        for i, (name, (shape, how)) in enumerate(shapes.items()):
            if how == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif how == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = how * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return jax.device_get(jax.jit(make)(seed_key(seed)))


# ------------------------------------------------------------- arithmetic


def self_attention(lw, h, sz: Sizes, quant, q_block: int):
    bsz, s, _ = h.shape
    n, kv, hd = sz.heads, sz.kv_heads, sz.head_dim
    rep = n // kv
    q = einsum("bsd,df->bsf", h, lw["q_proj"], quant).reshape(bsz, s, n, hd)
    k = einsum("bsd,df->bsf", h, lw["k_proj"], quant).reshape(bsz, s, kv, hd)
    v = einsum("bsd,df->bsf", h, lw["v_proj"], quant).reshape(bsz, s, kv, hd)
    q = rotary(q, hd, sz.rope_theta).reshape(bsz, s, kv, rep, hd)
    k = rotary(k, hd, sz.rope_theta)
    # a block of query rows holds (B, heads, q_block, S) float32 scores:
    # 2 GiB at 2 x 16 heads of 4,096 x 4,096, so the key-value heads go in
    # groups of at most half a GiB of scores, one after the other, each
    # rematerialised in the backward pass
    group = max(1, min(kv, 2 ** 27 // (bsz * rep * min(q_block, s) * s)))
    while kv % group:
        group -= 1

    def heads(xs):
        return attention(*xs, hd ** -0.5, quant, q_block)

    split = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape(a.shape[:2] + (kv // group, group) + a.shape[3:]), 2, 0)
    ctx = jax.lax.map(jax.checkpoint(heads), (split(q), split(k), split(v)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(bsz, s, n * hd)
    return einsum("bsf,fd->bsd", ctx, lw["o_proj"], quant)


def swiglu(lw, u, quant):
    a = silu(einsum("bsd,df->bsf", u, lw["gate"], quant)) * einsum(
        "bsd,df->bsf", u, lw["up"], quant)
    return einsum("bsf,fd->bsd", a, lw["down"], quant)


def layer(lw, h, sz: Sizes, quant, q_block: int):
    eps = sz.norm_eps
    a = self_attention(lw, norm(h, lw["norm1"], eps), sz, quant, q_block)
    h = h + norm(a, lw["norm2"], eps)
    m = swiglu(lw, norm(h, lw["norm3"], eps), quant)
    return h + norm(m, lw["norm4"], eps)


def exits(w, tokens, sz: Sizes, quant=None, q_block: int = 4096):
    """tokens (B, S) -> (the T normed states, each (B, S, D); the T gate
    logits, each (B, S))."""
    h = w["tok_emb"][tokens]
    apply = jax.checkpoint(functools.partial(
        layer, sz=sz, quant=quant, q_block=q_block))
    states, gates = [], []
    for _ in range(sz.ut_steps):
        for i in range(sz.layers):
            h = apply(layer_weights(w, i), h)
        h = norm(h, w["norm_f"], sz.norm_eps)
        states.append(h)
        gates.append(jnp.sum(h * w["gate_w"], -1) + w["gate_b"])
    return states, gates


def token_losses(w, hidden, targets, quant=None, row_block: int = 2048):
    """-log softmax(hidden head)[target] a token, the logits made block by
    block: hidden (B, S, D), targets (B, S) -> (B, S)."""
    d = hidden.shape[-1]
    hid, tgt = hidden.reshape(-1, d), targets.reshape(-1)

    def block(hb, tb):
        lg = einsum("td,dv->tv", hb, w["head"], quant)
        return (jax.nn.logsumexp(lg, -1)
                - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0])

    if hid.shape[0] <= row_block or hid.shape[0] % row_block:
        return block(hid, tgt).reshape(targets.shape)
    out = jax.lax.map(
        lambda a: jax.checkpoint(block)(a[0], a[1]),
        (hid.reshape(-1, row_block, d), tgt.reshape(-1, row_block)))
    return out.reshape(targets.shape)


def exit_probabilities(gates):
    """The T exit probabilities of every token, from the T gate logits."""
    lam = [jax.nn.sigmoid(g) for g in gates]
    stayed, out = jnp.ones_like(lam[0]), []
    for t in range(len(lam) - 1):
        out.append(lam[t] * stayed)
        stayed = stayed * (1.0 - lam[t])
    return out + [stayed]


def loss_fn(w, x, y, sz: Sizes, quant=None, q_block: int = 4096):
    """(loss, (the T mean cross-entropies, mean entropy of the exit
    distribution))."""
    states, gates = exits(w, x, sz, quant, q_block)
    nll = [token_losses(w, h, y, quant) for h in states]
    ce = jnp.stack([jnp.mean(n) for n in nll])
    p = exit_probabilities(gates)
    entropy = -sum(jnp.where(q > 0, q * jnp.log(jnp.where(q > 0, q, 1.0)),
                             0.0) for q in p)
    expected = sum(q * n for q, n in zip(p, nll))
    loss = jnp.mean(expected - sz.entropy_weight * entropy)
    return loss, (ce, jax.lax.stop_gradient(jnp.mean(entropy)))


# ------------------------------------------------------------ training


def follow_training(w0, batches, sz: Sizes, opt: Adam, quant=None,
                    q_block: int = 4096) -> dict:
    """Follow the first len(batches) steps from weights `w0`: gradients of
    the loss (a shared weight's add up over its passes), clipping by the
    global norm, AdamW with decay on every weight. Returns the losses, the
    global gradient norms (before clipping), the per-weight norms of the
    first (clipped) gradient and of the weights' change over all the steps,
    and `dropped` (zeros: nothing routes here; the driver prints it).

    Adam's two moments and the starting weights wait on the host and cross
    over a weight at a time, as in the other references."""
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
