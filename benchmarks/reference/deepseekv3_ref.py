"""Plain reference of the DeepSeekV3-style decoder the repo reproduces
(`deepseekv3/deepseekv3.ipynb` of the reference collection): forward pass,
loss, gradients and the AdamW step, in `jax.numpy`, float32, every matrix
product at `precision="highest"`. No cache, no kernel, no dispatch: every
expert is computed for every token and masked.

It imports nothing of `solvingpapers_tpu` and takes nothing the program made:
the weights come from `make_weights(seed, sizes)`, which the benchmark also
hands to the program.

Departures from the notebook, each because the configuration states it:
  * one latent per layer shared by the heads, decompressed per head (the
    paper's MLA); the notebook gives each head its own down-projection;
  * `pe_scale` multiplies the sinusoidal table (registry: 0.02);
  * `rope_dim` > 0 adds the decoupled rotary branch: a rotary query per
    head and one shared rotary key, concatenated to the latent for scores;
  * `capacity_factor`: an expert takes at most
    max(8, 8*ceil(int(T*k/E*cf)/8)) tokens of a call, in token order; later
    ones lose that expert's share (None = no limit);
  * `balance_weight` adds weight * mean over layers of sum_e f_e * P_e.
For long sequences attention runs in blocks of query rows and the loss in
blocks of rows, each under `jax.checkpoint`, so that 16,384 tokens fit.

`quant="int8"` is the control of the benchmark's correctness check: the same
mathematics with both operands of every matrix product rounded to 8-bit
integers (symmetric, one scale per tensor), the precision below the
configurations' bfloat16 that this chip computes natively.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    block: int
    dim: int
    layers: int
    heads: int
    latent: int
    experts: int
    top_k: int
    rope_dim: int = 0
    rope_theta: float = 10000.0
    pe_scale: float = 1.0
    capacity_factor: float | None = None
    balance_weight: float = 0.0
    bias_rate: float = 0.001
    norm_eps: float = 1e-6

    @property
    def head_dim(self) -> int:
        return self.dim // self.heads

    @property
    def hidden(self) -> int:
        return (2 * self.dim * 4) // 3  # notebook cell 21: ((2D)*4)//3


# ---------------------------------------------------------------- weights


def weight_shapes(sz: Sizes) -> dict[str, tuple[tuple[int, ...], float]]:
    """name -> (shape, std); std 0 marks a norm weight (ones). Projections
    that are plain linear layers draw with std 1/sqrt(fan_in), the stacked
    tensors and the embedding with 0.02, as the program's initialisers do."""
    d, n, hd, lat, e, h = (sz.dim, sz.heads, sz.head_dim, sz.latent,
                           sz.experts, sz.hidden)
    lin = lambda fan_in: 1.0 / math.sqrt(fan_in)  # noqa: E731
    out = {"tok_emb": ((sz.vocab, d), 0.02)}
    for i in range(sz.layers):
        p = f"l{i}."
        out[p + "norm1"] = ((d,), 0.0)
        out[p + "w_dkv"] = ((d, lat), lin(d))
        out[p + "w_q"] = ((d, n, hd), 0.02)
        out[p + "w_k"] = ((lat, n, hd), 0.02)
        out[p + "w_v"] = ((lat, n, hd), 0.02)
        out[p + "w_o"] = ((n * hd, d), lin(n * hd))
        if sz.rope_dim:
            out[p + "w_qr"] = ((d, n, sz.rope_dim), 0.02)
            out[p + "w_kr"] = ((d, sz.rope_dim), lin(d))
        out[p + "norm2"] = ((d,), 0.0)
        out[p + "gate"] = ((d, e), lin(d))
        out[p + "w1"] = ((e, d, h), 0.02)
        out[p + "w2"] = ((e, d, h), 0.02)
        out[p + "w3"] = ((e, h, d), 0.02)
        out[p + "s_gate"] = ((d, h), lin(d))
        out[p + "s_up"] = ((d, h), lin(d))
        out[p + "s_down"] = ((h, d), lin(h))
    out["norm_f"] = ((d,), 0.0)
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (the driver's seeds
    pass 2**31): the two 32-bit halves are folded in one after the other."""
    key = jax.random.key(0)
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make_weights(seed: int, sz: Sizes) -> dict[str, jax.Array]:
    """All weights, float32, on the device, in one jitted call."""
    shapes = weight_shapes(sz)

    def make(key):
        out = {}
        for i, (name, (shape, std)) in enumerate(shapes.items()):
            if std == 0.0:
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return jax.jit(make)(seed_key(seed))


# ------------------------------------------------------------- arithmetic


def _int8(x):
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _fake_quant(x):
    """Round to int8 forward; pass the gradient straight through, as a
    quantised training step would."""
    return x + jax.lax.stop_gradient(_int8(x) - x)


def einsum(spec: str, a, b, quant: str | None):
    if quant == "int8":
        a, b = _fake_quant(a), _fake_quant(b)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def sinusoid(n: int, dim: int):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    i = jnp.arange(0, dim, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, i / dim)
    return jnp.stack([jnp.sin(ang), jnp.cos(ang)], -1).reshape(n, dim)


def rope(x, theta: float):
    """Rotate the feature pairs (2i, 2i+1) of x (..., S, heads, R) by
    position * theta^(-2i/R)."""
    s, r = x.shape[-3], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.stack([xe * cos - xo * sin, xe * sin + xo * cos],
                     -1).reshape(x.shape)


def swish(x):
    return x * jax.nn.sigmoid(x)


def attention(q, c, scale, quant, q_block: int):
    """Causal attention of queries q (B, S, n, W) over the shared keys =
    values c (B, S, W), in blocks of `q_block` query rows; a block sees the
    keys up to its own last row. Returns (B, S, n, W)."""
    s = q.shape[1]

    def rows(q_blk, keys, start):
        sc = einsum("bsnw,btw->bnst", q_blk, keys, quant) * scale
        qi = start + jnp.arange(q_blk.shape[1])
        mask = jnp.arange(keys.shape[1])[None, :] <= qi[:, None]
        p = jax.nn.softmax(jnp.where(mask[None, None], sc, NEG), -1)
        return einsum("bnst,btw->bsnw", p, keys, quant)

    if s <= q_block:
        return rows(q, c, 0)
    if s % q_block:
        raise ValueError(f"{s} rows do not divide into blocks of {q_block}")
    return jnp.concatenate([
        jax.checkpoint(functools.partial(rows, start=start))(
            q[:, start:start + q_block], c[:, :start + q_block])
        for start in range(0, s, q_block)], 1)


def capacity(tokens: int, sz: Sizes) -> int | None:
    if sz.capacity_factor is None:
        return None
    c = int(tokens * sz.top_k / sz.experts * sz.capacity_factor)
    return max(8, -(-c // 8) * 8)


def moe(lw, x, bias, sz: Sizes, quant):
    """x (T, D) -> (out (T, D), routed load (E,), balance term, dropped
    share). Every expert runs on every token; the gate's weights mask."""
    t = x.shape[0]
    logits = jnp.einsum("td,de->te", x, lw["gate"], precision=HI)
    biased = logits + bias
    kth = jnp.sort(biased, -1)[:, -sz.top_k][:, None]
    probs = jax.nn.softmax(jnp.where(biased >= kth, biased, NEG), -1)
    sel = probs > 0.0
    cap = capacity(t, sz)
    if cap is None:
        keep = sel
    else:
        keep = sel & (jnp.cumsum(sel.astype(jnp.int32), 0) - 1 < cap)
    weights = jnp.where(keep, probs, 0.0)

    def glu(x, w_gate, w_up, w_down):
        a = einsum("td,dh->th", x, w_gate, quant)
        g = einsum("td,dh->th", x, w_up, quant)
        return einsum("th,hd->td", swish(a) * g, w_down, quant)

    def expert(acc, e):
        w1, w2, w3, col = e
        return acc + col[:, None] * glu(x, w1, w2, w3), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (lw["w1"], lw["w2"], lw["w3"], weights.T))
    out = out + glu(x, lw["s_gate"], lw["s_up"], lw["s_down"])
    load = jax.lax.stop_gradient(jnp.sum(probs, 0))
    f = jnp.mean(sel.astype(jnp.float32), 0) * (sz.experts / sz.top_k)
    balance = jnp.sum(f * jnp.mean(jax.nn.softmax(logits, -1), 0))
    routed = jnp.sum(sel)
    dropped = (routed - jnp.sum(keep)) / jnp.maximum(routed, 1)
    return out, load, balance, jax.lax.stop_gradient(dropped)


def layer(lw, x, bias, sz: Sizes, quant, q_block: int):
    """One decoder layer with its weights `lw` (names without the layer)."""
    b, s, d = x.shape
    h = rms_norm(x, lw["norm1"], sz.norm_eps)
    lat = einsum("bsd,dl->bsl", h, lw["w_dkv"], quant)
    q = einsum("bsd,dnh->bsnh", h, lw["w_q"], quant)
    q_lat = einsum("bsnh,lnh->bsnl", q, lw["w_k"], quant)
    scale = sz.head_dim ** -0.5
    if sz.rope_dim:
        qr = rope(einsum("bsd,dnr->bsnr", h, lw["w_qr"], quant),
                  sz.rope_theta)
        kr = rope(einsum("bsd,dr->bsr", h, lw["w_kr"], quant)[:, :, None],
                  sz.rope_theta)[:, :, 0]
        q_lat = jnp.concatenate([q_lat, qr], -1)
        keys = jnp.concatenate([lat, kr], -1)
        scale = (sz.head_dim + sz.rope_dim) ** -0.5
    else:
        keys = lat
    ctx = attention(q_lat, keys, scale, quant, q_block)[..., :sz.latent]
    heads = einsum("bsnl,lnh->bsnh", ctx, lw["w_v"], quant)
    x = x + einsum("bsk,kd->bsd", heads.reshape(b, s, -1), lw["w_o"], quant)
    h = rms_norm(x, lw["norm2"], sz.norm_eps)
    y, load, balance, dropped = moe(lw, h.reshape(b * s, d), bias, sz, quant)
    return x + y.reshape(b, s, d), load, balance, dropped


def stack_layers(w, sz: Sizes) -> dict:
    """{name without layer: (L, ...) stack} of the per-layer weights."""
    names = [k[3:] for k in w if k.startswith("l0.")]
    return {n: jnp.stack([w[f"l{i}.{n}"] for i in range(sz.layers)])
            for n in names}


def hidden_states(w, tokens, biases, sz: Sizes, quant=None,
                  q_block: int = 4096):
    """tokens (B, S) -> (final normed hidden (B, S, D), loads (L, E),
    balance terms (L,), dropped shares (L,)). The layers run as one scan
    over their stacked weights, each under `jax.checkpoint`."""
    s = tokens.shape[1]
    x = w["tok_emb"][tokens] + sz.pe_scale * sinusoid(sz.block, sz.dim)[:s]

    def body(x, inp):
        lw, bias = inp
        x, load, bal, dr = layer(lw, x, bias, sz, quant, q_block)
        return x, (load, bal, dr)

    x, (loads, balances, drops) = jax.lax.scan(
        jax.checkpoint(body), x, (stack_layers(w, sz), biases))
    x = 2.0 * sz.layers ** -0.5 * x
    x = rms_norm(x, w["norm_f"], sz.norm_eps)
    return x, loads, balances, drops


def logits_of(w, hidden, quant=None):
    return einsum("...d,vd->...v", hidden, w["tok_emb"], quant)


def cross_entropy(w, hidden, targets, quant=None, row_block: int = 2048):
    """Mean next-token cross-entropy, the logits made block by block."""
    d = hidden.shape[-1]
    hid, tgt = hidden.reshape(-1, d), targets.reshape(-1)
    rows = hid.shape[0]

    def block_sum(hb, tb):
        lg = logits_of(w, hb, quant)
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0])

    if rows <= row_block:
        return block_sum(hid, tgt) / rows
    if rows % row_block:
        raise ValueError(f"{rows} rows do not divide into blocks of "
                         f"{row_block}")
    sums = jax.lax.map(
        lambda a: jax.checkpoint(block_sum)(a[0], a[1]),
        (hid.reshape(-1, row_block, d), tgt.reshape(-1, row_block)))
    return jnp.sum(sums) / rows


def loss_fn(w, biases, x, y, sz: Sizes, quant=None, q_block: int = 4096):
    """(total loss, (cross-entropy, loads, dropped share)) of one batch."""
    hid, loads, balances, drops = hidden_states(w, x, biases, sz, quant,
                                                q_block)
    ce = cross_entropy(w, hid, y, quant)
    total = ce + sz.balance_weight * jnp.mean(balances)
    return total, (ce, loads, jnp.mean(drops))


# ------------------------------------------------------------ training


@dataclasses.dataclass(frozen=True)
class Adam:
    max_lr: float
    warmup_steps: int
    total_steps: int
    min_lr_ratio: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0

    def lr(self, count):
        """Linear warm-up from 0, then a cosine to min_lr_ratio * max_lr;
        `count` is the number of updates already made."""
        count = jnp.asarray(count, jnp.float32)
        warm = self.max_lr * count / max(self.warmup_steps, 1)
        span = max(self.total_steps - self.warmup_steps, 1)
        frac = jnp.clip((count - self.warmup_steps) / span, 0.0, 1.0)
        cos = 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
        decayed = self.max_lr * ((1 - self.min_lr_ratio) * cos
                                 + self.min_lr_ratio)
        return jnp.where(count < self.warmup_steps, warm, decayed)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in tree.values()))


def train_step(w, mu, nu, biases, count, x, y, sz: Sizes, opt: Adam,
               quant=None, q_block: int = 4096):
    """One step as the configuration states it: gradients of the total
    loss, clipping by the global norm, AdamW with decay on every weight,
    and the routing bias moved by bias_rate * sign(mean load - load)."""
    (loss, (_, loads, dropped)), g = jax.value_and_grad(
        loss_fn, has_aux=True)(w, biases, x, y, sz, quant, q_block)
    gnorm = global_norm(g)
    if opt.grad_clip > 0:
        factor = jnp.where(gnorm < opt.grad_clip, 1.0, opt.grad_clip / gnorm)
        g = {k: v * factor for k, v in g.items()}
    t = count + 1
    lr = opt.lr(count)
    new_w, new_mu, new_nu = {}, {}, {}
    for k in w:
        new_mu[k] = opt.b1 * mu[k] + (1 - opt.b1) * g[k]
        new_nu[k] = opt.b2 * nu[k] + (1 - opt.b2) * jnp.square(g[k])
        m_hat = new_mu[k] / (1 - opt.b1 ** t)
        v_hat = new_nu[k] / (1 - opt.b2 ** t)
        upd = m_hat / (jnp.sqrt(v_hat) + opt.eps) + opt.weight_decay * w[k]
        new_w[k] = w[k] - lr * upd
    new_biases = biases + sz.bias_rate * jnp.sign(
        jnp.mean(loads, -1, keepdims=True) - loads)
    return new_w, new_mu, new_nu, new_biases, loss, gnorm, g, dropped


def follow_training(w0, batches, sz: Sizes, opt: Adam, quant=None,
                    q_block: int = 4096) -> dict:
    """Follow the first len(batches) steps from weights `w0`. Returns the
    losses, the global gradient norms, the per-weight norms of the first
    (clipped) gradient and of the weights' change over all the steps."""
    step = jax.jit(functools.partial(train_step, sz=sz, opt=opt, quant=quant,
                                     q_block=q_block))
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v)))
                               for k, v in t.items()})
    w = w0
    mu = {k: jnp.zeros_like(v) for k, v in w0.items()}
    nu = {k: jnp.zeros_like(v) for k, v in w0.items()}
    biases = jnp.zeros((sz.layers, sz.experts), jnp.float32)
    out = {"loss": [], "grad_norm": [], "dropped": []}
    for i, (x, y) in enumerate(batches):
        w, mu, nu, biases, loss, gnorm, g, dropped = step(
            w, mu, nu, biases, i, jnp.asarray(x), jnp.asarray(y))
        if i == 0:
            out["first_grad"] = {k: float(v) for k, v in norms(g).items()}
        del g
        out["loss"].append(float(loss))
        out["grad_norm"].append(float(gnorm))
        out["dropped"].append(float(dropped))
    delta = jax.jit(lambda a, b: {k: a[k] - b[k] for k in a})(w, w0)
    out["delta"] = {k: float(v) for k, v in norms(delta).items()}
    return out


# ------------------------------------------------------------- serving


def served_gaps(w, sz: Sizes, prompt, served, quant=None,
                q_block: int = 4096) -> dict:
    """One pass over prompt + served tokens. For each served token: how far
    its logit lies below the best logit at its position (`gap`), and how far
    the token that `quant` puts first lies below the float32 best
    (`control_gap`; equal to `gap` of an ideal program when quant is None)."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    n = len(seq)
    grain = 64 if n <= q_block else q_block  # whole attention blocks
    pad = -(-n // grain) * grain
    toks = np.zeros((1, pad), np.int32)
    toks[0, :n] = seq
    biases = jnp.zeros((sz.layers, sz.experts), jnp.float32)
    sz_nocap = dataclasses.replace(sz, capacity_factor=None)

    @functools.partial(jax.jit, static_argnames=("q",))
    def run(w, toks, q):
        hid, *_ = hidden_states(w, toks, biases, sz_nocap, q, q_block)
        return logits_of(w, hid[0], q)

    ref = np.asarray(run(w, jnp.asarray(toks), None))
    p = len(prompt)
    rows = ref[p - 1:n - 1]  # row j predicts served[j]
    best = rows.max(-1)
    out = {"gap": best - rows[np.arange(len(served)), np.asarray(served)]}
    if quant is not None:
        low = np.asarray(run(w, jnp.asarray(toks), quant))[p - 1:n - 1]
        pick = low.argmax(-1)
        out["control_gap"] = best - rows[np.arange(len(served)), pick]
    return out
