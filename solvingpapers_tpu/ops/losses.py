"""Loss functions.

One shared implementation of every loss in the reference:
  * token cross-entropy             (gpt/gpt-jax.ipynb cell 13; manual
                                     log-softmax gather llama3 cell 28)
  * CE with ignore_index            (deepseekv3/deepseekv3.ipynb cell 54)
  * multi-token-prediction loss     (deepseekv3 cell 46)
  * distillation CE + T^2*KL        (knowledge distillation/kd.py:48-68)
  * VAE summed BCE + analytic KL    (autoencoder/variational autoencoder.ipynb cell 6)
  * classification CE / MSE         (ViT cell 13; autoencoder cell 6 — via
                                     cross_entropy / plain jnp mean-square)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# past this many logit elements (f32 log-probs > 1 GB) the loss chunks
# itself; every CE caller (LM, DSV3, MTP) is covered without opting in.
# Threshold sized so the reference-scale dsv3 config (4096 rows x 50257 =
# 206M elements) stays single-pass (chunking costs it ~7% throughput for
# memory it does not need) while 16k-context LM runs (524M+) chunk.
_AUTO_CHUNK_ELEMENTS = 2**28
_AUTO_CHUNK_ROWS = 8192


@jax.named_scope("L_loss_head")
def cross_entropy(
    logits: jax.Array,
    labels: jax.Array,
    ignore_index: int | None = None,
    chunk_size: int | None | str = "auto",
) -> jax.Array:
    """Mean cross-entropy of integer labels; optionally masks ignore_index.

    logits: (..., V); labels: (...) int. Computed in float32.

    chunk_size: when set, rows are processed in `chunk_size` slices under
    jax.checkpoint — the f32 log-softmax exists for one chunk at a time and
    is recomputed in the backward, so peak HBM for the loss drops from
    O(rows x V) f32 to O(chunk x V). Long-context single-chip training
    (tools/scale_350m.py --seq 16384) OOMs without this: at seq 16k,
    vocab 32k the unchunked f32 logits + log-probs + cotangent cost ~6G of
    the 15.75G HBM. Same math, summation order differs only across chunks.
    The default "auto" chunks at 8192 rows once logits exceed 2^28 elements
    (small models keep the single-pass form); pass None to force one pass.
    """
    if chunk_size == "auto":
        chunk_size = (
            _AUTO_CHUNK_ROWS if logits.size > _AUTO_CHUNK_ELEMENTS else None
        )
    if chunk_size is not None:
        rows = logits.size // logits.shape[-1]
        # a single whole-size chunk still pays off: jax.checkpoint drops the
        # f32 log-softmax from the saved residuals either way
        return _chunked_cross_entropy(
            logits, labels, ignore_index, min(chunk_size, rows)
        )
    logits = logits.astype(jnp.float32)
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    if ignore_index is None:
        nll = -jnp.take_along_axis(log_probs, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(nll)
    valid = labels != ignore_index
    # Gather with sanitized indices: take_along_axis uses fill-mode for OOB
    # indices, so a sentinel like -100 gathers NaN, and NaN * 0 mask = NaN.
    safe = jnp.where(valid, labels, 0)
    nll = -jnp.take_along_axis(log_probs, safe[..., None], axis=-1)[..., 0]
    mask = valid.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _chunked_cross_entropy(
    logits: jax.Array, labels: jax.Array, ignore_index: int | None, chunk: int
) -> jax.Array:
    """Scan over row chunks; each chunk's f32 softmax is rematerialized in
    the backward (jax.checkpoint), so only the source-dtype logits persist."""
    tot, num = _chunked_nll_sum_count(logits, labels, ignore_index, chunk)
    return tot / jnp.maximum(num, 1.0)


def _chunked_nll_sum_count(
    logits: jax.Array, labels: jax.Array, ignore_index: int | None, chunk: int
) -> tuple[jax.Array, jax.Array]:
    """(masked nll SUM, valid COUNT) over rows via the chunked checkpoint
    scan — shared by cross_entropy (which divides here) and mtp_loss's CP
    path (which psums sum/count across shards before dividing)."""
    v = logits.shape[-1]
    flat = logits.reshape(-1, v)
    lab = labels.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % chunk
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
        # padded rows are masked out via an out-of-band label
        sentinel = -1 if ignore_index is None else ignore_index
        lab = jnp.pad(lab, (0, pad), constant_values=sentinel)
        if ignore_index is None:
            ignore_index = -1
    flat = flat.reshape(-1, chunk, v)
    lab = lab.reshape(-1, chunk)

    @jax.checkpoint
    def body(carry, xs):
        lg, lb = xs
        lg = lg.astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        if ignore_index is None:
            picked = jnp.take_along_axis(lg, lb[:, None], axis=-1)[:, 0]
            nll_sum = jnp.sum(lse - picked)
            cnt = jnp.float32(lb.shape[0])
        else:
            valid = lb != ignore_index
            safe = jnp.where(valid, lb, 0)
            picked = jnp.take_along_axis(lg, safe[:, None], axis=-1)[:, 0]
            m = valid.astype(jnp.float32)
            nll_sum = jnp.sum((lse - picked) * m)
            cnt = jnp.sum(m)
        tot, num = carry
        return (tot + nll_sum, num + cnt), None

    # under shard_map with vma tracking, the carry must match the body
    # output's varying axes (the logits are shard-varying on CP paths)
    zero = jnp.float32(0.0)
    vma = tuple(jax.typeof(flat).vma)
    if vma:
        zero = jax.lax.pcast(zero, vma, to="varying")
    (tot, num), _ = jax.lax.scan(body, (zero, zero), (flat, lab))
    return tot, num


def _head_chunks(hidden, kernel, labels, chunk_size):
    """What `head_nll_rows` and `head_cross_entropy` scan over: the rows of
    hidden (..., D) and labels (...) in chunks of `chunk_size` (rows it
    does not divide run as one chunk), and the body's per-row routine: a
    chunk's logits (the product in hidden's dtype, as `nn.Dense` gives
    them, then float32) and from them -log softmax[label] a row. The kernel
    (D, V), kept float32, is cast inside the scan's body, so its gradient
    adds up over the chunks in float32."""
    d = hidden.shape[-1]
    flat, lab = hidden.reshape(-1, d), labels.reshape(-1)
    n = flat.shape[0]
    chunk = chunk_size if n % chunk_size == 0 else n

    def nll(h, lb):
        lg = jnp.dot(h, kernel.astype(h.dtype)).astype(jnp.float32)
        picked = jnp.take_along_axis(lg, lb[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(lg, axis=-1) - picked

    return nll, (flat.reshape(-1, chunk, d), lab.reshape(-1, chunk)), n


@jax.named_scope("L_loss_head")
def head_nll_rows(
    hidden: jax.Array, kernel: jax.Array, labels: jax.Array,
    chunk_size: int = 2048,
) -> jax.Array:
    """-log softmax(hidden @ kernel)[label] of every row, float32 in the
    shape of `labels`, head and loss together a chunk of rows at a time:
    hidden (..., D) in the compute dtype, kernel (D, V) float32, labels
    (...) int. The kernel is an untied head's leaf as it is kept, or a TIED
    head's: the embedding transposed (`GraniteHybrid.head_kernel`), whose
    gradient then reaches the one leaf twice, from the lookup and, summed
    over the chunks in float32, from here; a scale on the logits is the
    caller's, folded into `hidden`. A chunk's logits exist
    inside a `jax.checkpoint`ed scan body only, so neither the (rows, V)
    logits nor their cotangent are ever whole in memory (16,384 x 20,480
    bfloat16: 640 MB each). For a loss that weights its rows (the looped
    family's, by exit probabilities that take gradients themselves)."""
    nll, chunks, _ = _head_chunks(hidden, kernel, labels, chunk_size)
    _, rows = jax.lax.scan(
        jax.checkpoint(lambda _, xs: (None, nll(*xs))), None, chunks)
    return rows.reshape(labels.shape)


@jax.named_scope("L_loss_head")
def head_cross_entropy(
    hidden: jax.Array, kernel: jax.Array, labels: jax.Array,
    chunk_size: int = 2048,
) -> jax.Array:
    """The mean of `head_nll_rows`, summed inside the scan (a carry in
    place of the rows: the program the two families that call it compiled
    to before the per-row form existed). Untied or tied kernel alike, as
    there."""
    nll, chunks, n = _head_chunks(hidden, kernel, labels, chunk_size)

    @jax.checkpoint
    def body(tot, xs):
        return tot + jnp.sum(nll(*xs)), None

    tot, _ = jax.lax.scan(body, jnp.float32(0.0), chunks)
    return tot / n


def distillation_loss(
    student_logits: jax.Array,
    teacher_logits: jax.Array,
    labels: jax.Array,
    temperature: float = 7.0,
    alpha: float = 0.3,
) -> jax.Array:
    """Hinton KD loss: alpha*CE(student, labels) + (1-alpha)*T^2*KL(teacher||student).

    Matches knowledge distillation/kd.py:48-68 (T=7, alpha=0.3): KL of
    temperature-softened distributions, scaled by T^2 to keep gradient
    magnitude comparable to the CE term.
    """
    hard = cross_entropy(student_logits, labels)
    t = temperature
    s_log = jax.nn.log_softmax(student_logits.astype(jnp.float32) / t, axis=-1)
    t_prob = jax.nn.softmax(teacher_logits.astype(jnp.float32) / t, axis=-1)
    # batchmean KL(teacher || student)
    kl = jnp.sum(t_prob * (jnp.log(jnp.maximum(t_prob, 1e-12)) - s_log), axis=-1)
    soft = jnp.mean(kl) * (t * t)
    return alpha * hard + (1.0 - alpha) * soft


def vae_loss(
    recon: jax.Array,
    target: jax.Array,
    mu: jax.Array,
    logvar: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Summed BCE reconstruction + analytic KL to N(0, I).

    Matches autoencoder/variational autoencoder.ipynb cell 6 (sum
    reduction, per batch). `recon` is post-sigmoid probabilities in (0,1).
    Returns (total, bce, kl).
    """
    recon32 = jnp.clip(recon.astype(jnp.float32), 1e-7, 1.0 - 1e-7)
    target32 = target.astype(jnp.float32)
    bce = -jnp.sum(
        target32 * jnp.log(recon32) + (1.0 - target32) * jnp.log(1.0 - recon32)
    )
    kl = -0.5 * jnp.sum(1.0 + logvar - jnp.square(mu) - jnp.exp(logvar))
    return bce + kl, bce, kl


@jax.named_scope("L_loss_head")
def mtp_loss(
    logits: jax.Array,
    tokens: jax.Array,
    num_heads: int,
    ignore_index: int | None = None,
    axis_names: tuple | None = None,
) -> jax.Array:
    """Multi-token-prediction loss (deepseekv3/deepseekv3.ipynb cell 46).

    logits: (B, T, K, V) where head k at position i predicts token i+k+1.
    tokens: (B, T + K) raw token stream providing the shifted targets.
    Flat mean CE over all (position, head) pairs with valid targets.

    axis_names: inside shard_map (context parallelism, T = local shard),
    psum the masked nll SUM and the valid COUNT across the axes before
    dividing — shards hold different valid counts (only the last shard
    loses the k tail targets), so a pmean of local means would weight the
    tail shard's targets differently from the dense computation.
    """
    b, t, k, v = logits.shape
    assert k == num_heads
    if tokens.shape[-1] != t + k:
        raise ValueError(
            f"tokens must have T+K={t + k} columns to provide shifted targets, "
            f"got {tokens.shape[-1]}"
        )
    # targets[b, i, k] = tokens[b, i + k + 1]
    idx = jnp.arange(t)[:, None] + jnp.arange(1, k + 1)[None, :]
    targets = tokens[:, idx]  # (B, T, K)
    if axis_names is None:
        return cross_entropy(
            logits.reshape(b * t * k, v), targets.reshape(-1), ignore_index
        )
    # CP path: masked-nll SUM and valid COUNT via cross_entropy's chunked
    # checkpoint scan (one chunk's f32 log-probs at a time — long-context
    # configs like dsv3_long_cp have 131k local rows x 50k vocab, which
    # unchunked would be ~26 GB of f32), then psum'd before dividing.
    rows = b * t * k
    chunk = (
        min(_AUTO_CHUNK_ROWS, rows)
        if logits.size > _AUTO_CHUNK_ELEMENTS else rows
    )
    s, c = _chunked_nll_sum_count(
        logits.reshape(rows, v), targets.reshape(-1), ignore_index, chunk
    )
    s = jax.lax.psum(s, axis_names)
    c = jax.lax.psum(c, axis_names)
    return s / jnp.maximum(c, 1.0)
