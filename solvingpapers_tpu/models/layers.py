"""Shared Flax building blocks (L2).

One `Attention` module serves every transformer in the zoo — the variants
the reference implements separately are config points here:
  * GPT: causal MHA, fused qkv, no RoPE (gpt/gpt-jax.ipynb cell 9)
  * LLaMA3: causal GQA + RoPE (llama3/LLaMA-jax.ipynb cell 24)
  * Gemma: causal MQA-grouped + RoPE (gemma/gemma.ipynb cell 8)
  * ViT: bidirectional MHA (vision transformer/ViT.ipynb cell 10)
MLA is structurally different (latent cache) and lives in models/deepseekv3.py.
Below them the train-only families' shared functions; the mixers more than
one of them runs are `models/mixers.py`'s.

All dense layers take a compute `dtype` (bf16 for TPU training) with f32
params; reductions inside ops.* are f32.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from flax import linen as nn

from solvingpapers_tpu import ops
from solvingpapers_tpu.infer.cache import KVCache, update_kv_cache


def default_positions(
    b: int, s: int, context_parallel: bool = False,
    context_axis: str = "context", max_positions: int | None = None,
) -> jax.Array:
    """Default (B, S) absolute positions. Under context parallelism the
    caller sees only its local sequence shard inside shard_map, so defaults
    must be GLOBAL (axis_index * s + arange) — otherwise RoPE/learned
    tables restart at 0 on every shard while the ring masks globally. One
    definition for Attention and every model's embedding path.

    `max_positions` (e.g. a learned table length) turns silent clipping
    into a trace-time error: jnp.take would clamp out-of-range global
    positions to the last row and train a silently wrong objective."""
    if context_parallel:
        axis_size = jax.lax.psum(1, context_axis)  # static under shard_map
        if max_positions is not None and axis_size * s > max_positions:
            raise ValueError(
                f"global sequence {axis_size * s} (= {axis_size} context "
                f"shards x {s}) exceeds max positions {max_positions}; "
                "jnp.take would silently clamp to the last table row"
            )
        start = jax.lax.axis_index(context_axis) * s
        return jnp.broadcast_to(start + jnp.arange(s), (b, s))
    if max_positions is not None and s > max_positions:
        raise ValueError(f"sequence {s} exceeds max positions {max_positions}")
    return jnp.broadcast_to(jnp.arange(s), (b, s))


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        weight = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        return ops.rms_norm(x, weight, self.eps)


class LayerNorm(nn.Module):
    eps: float = 1e-5
    use_bias: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        weight = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        bias = (
            self.param("bias", nn.initializers.zeros, (x.shape[-1],))
            if self.use_bias
            else None
        )
        return ops.layer_norm(x, weight, bias, self.eps)


class Attention(nn.Module):
    """Multi-head attention with optional GQA/MQA, RoPE, causality and KV cache.

    Call: (x, *, positions, cache, deterministic) -> (out, new_cache).
    `positions` (B, S) absolute positions are required when a cache is
    passed; otherwise default to arange. The KV cache is preallocated
    (infer/cache.py); masking is position-based so stale slots never leak.
    """

    dim: int
    n_heads: int
    n_kv_heads: int | None = None  # None => MHA
    head_dim: int | None = None
    causal: bool = True
    use_rope: bool = False
    rope_theta: float = 10000.0
    max_seq_len: int = 4096  # rope table length
    dropout: float = 0.0
    use_bias: bool = False
    dtype: jnp.dtype = jnp.float32
    # Pallas kernel for the uncached path (supports attention-prob dropout
    # in-kernel). Note: a pallas_call is opaque to GSPMD, so under a sharded
    # mesh this module's direct call would gather its operands — mesh runs
    # should use kernels.sharded_flash_attention (shard_map-wrapped: batch
    # over data/fsdp, heads over model); the dense path partitions anywhere.
    use_flash: bool = False
    # context parallelism: REQUIRES the module to be applied inside a
    # shard_map whose `context_axis` shards the sequence dimension
    # (positions must be global — derived from the axis index when None).
    # context_impl "ring" rotates K/V chunks via ppermute (memory-optimal,
    # any head count); "ulysses" all_to_alls to head sharding around a dense
    # core (needs n_heads and n_kv_heads divisible by the axis size). Decode
    # under CP uses the context-sharded CPKVCache (infer.generate_cp /
    # model.init_cp_caches); a plain per-shard KVCache is rejected.
    context_parallel: bool = False
    context_axis: str = "context"
    context_impl: str = "ring"  # ring | ulysses

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        *,
        positions: jax.Array | None = None,
        cache: KVCache | None = None,
        deterministic: bool = True,
        attend_len: int | None = None,
    ) -> tuple[jax.Array, KVCache | None]:
        b, s, _ = x.shape
        n_kv = self.n_kv_heads or self.n_heads
        head_dim = self.head_dim or self.dim // self.n_heads
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=self.use_bias, dtype=self.dtype, name=name
        )

        if positions is None:
            positions = default_positions(
                b, s, self.context_parallel, self.context_axis
            )

        if n_kv == self.n_heads:
            qkv = dense(3 * self.n_heads * head_dim, "qkv")(x)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            q = dense(self.n_heads * head_dim, "q")(x)
            kv = dense(2 * n_kv * head_dim, "kv")(x)
            k, v = jnp.split(kv, 2, axis=-1)
        q = q.reshape(b, s, self.n_heads, head_dim)
        k = k.reshape(b, s, n_kv, head_dim)
        v = v.reshape(b, s, n_kv, head_dim)

        if self.use_rope:
            cos, sin = ops.precompute_rope(head_dim, self.max_seq_len, self.rope_theta)
            q = ops.apply_rope(q, cos, sin, positions=positions)
            k = ops.apply_rope(k, cos, sin, positions=positions)

        cp_cache = cache is not None and self.context_parallel
        if cp_cache:
            from solvingpapers_tpu.infer.cache import (
                CPKVCache, validate_cp_cache,
            )

            validate_cp_cache(
                cache, CPKVCache,
                getattr(cache, "k_prompt", jnp.zeros((1, 0, 1, 1))).shape[1],
                s,
            )
            if s > 1:
                # CP PREFILL: this shard's contiguous chunk fills its
                # prompt slice in place; attention falls through to the
                # ring/ulysses branch below
                cache = cache.replace(
                    k_prompt=k.astype(cache.k_prompt.dtype),
                    v_prompt=v.astype(cache.v_prompt.dtype),
                )
        if cp_cache and s == 1:
            # CP DECODE STEP: replicated token, sharded prompt cache.
            # Shard-local logsumexp partials over the local prompt chunk
            # (+ the replicated tail on the last shard only, counted once)
            # combine with one pmax + two psums; the cache never moves.
            from solvingpapers_tpu.infer.cache import cp_cache_partial_softmax_kv
            from solvingpapers_tpu.ops.attention import BIG_NEG, repeat_kv

            axis = self.context_axis
            cp_size = jax.lax.psum(1, axis)
            idx = jax.lax.axis_index(axis)
            s0_glob = cache.k_prompt.shape[1] * cp_size
            tail_len = cache.k_tail.shape[1]
            pos = positions[0, 0]
            cache = cache.replace(
                k_tail=jax.lax.dynamic_update_slice(
                    cache.k_tail, k.astype(cache.k_tail.dtype),
                    (0, pos - s0_glob, 0, 0),
                ),
                v_tail=jax.lax.dynamic_update_slice(
                    cache.v_tail, v.astype(cache.v_tail.dtype),
                    (0, pos - s0_glob, 0, 0),
                ),
            )
            group = self.n_heads // n_kv
            q32 = q.astype(jnp.float32) * head_dim**-0.5
            # every prompt slot precedes pos (pos >= s0_glob): no mask
            scores_p = jnp.einsum(
                "bsnh,btnh->bnst", q32,
                repeat_kv(cache.k_prompt, group).astype(jnp.float32),
            )
            scores_t = jnp.einsum(
                "bsnh,btnh->bnst", q32,
                repeat_kv(cache.k_tail, group).astype(jnp.float32),
            )
            mask_t = (s0_glob + jnp.arange(tail_len) <= pos) & (
                idx == cp_size - 1
            )
            scores_t = jnp.where(
                mask_t[None, None, None, :], scores_t, BIG_NEG
            )
            vals = repeat_kv(
                jnp.concatenate([cache.v_prompt, cache.v_tail], axis=1),
                group,
            )
            out = cp_cache_partial_softmax_kv(
                scores_p, scores_t, vals, axis
            ).astype(self.dtype)
        elif cache is not None and not cp_cache:
            # single contiguous segment per step: write at the first position
            cache = update_kv_cache(cache, k, v, positions[0, 0])
            if attend_len is not None:
                # PREFILL contract: this chunk occupies cache slots
                # [attend_len - S, attend_len) and every earlier slot is
                # written — so attention is exactly end-aligned causal over
                # the first attend_len slots (a STATIC slice: no
                # (S, max_len) mask/prob tensor ever exists, which is what
                # makes 16k-prompt prefill fit in HBM). use_flash runs the
                # Pallas kernel's seq_q != seq_k end-aligned causal mode.
                k_att = jax.lax.slice_in_dim(cache.k, 0, attend_len, axis=1)
                v_att = jax.lax.slice_in_dim(cache.v, 0, attend_len, axis=1)
                if self.use_flash:
                    from solvingpapers_tpu.kernels import flash_attention

                    out = flash_attention(q, k_att, v_att, causal=True)
                else:
                    out = ops.dot_product_attention(
                        q, k_att, v_att, causal=True
                    )
            else:
                k_full, v_full = cache.k, cache.v
                kv_idx = jnp.arange(cache.max_len)
                # (B, 1, S, max_len): query at position p sees kv slots <= p
                mask = kv_idx[None, None, None, :] <= positions[:, None, :, None]
                out = ops.dot_product_attention(q, k_full, v_full, mask=mask)
        elif self.context_parallel:
            from solvingpapers_tpu.sharding.ring_attention import (
                ring_attention_local,
                ring_flash_attention_local,
                ulysses_attention_local,
            )

            from solvingpapers_tpu.kernels.flash_attention import is_tpu_backend

            drop_active = self.dropout > 0.0 and not deterministic
            if drop_active and self.context_impl == "ring" and not (
                self.use_flash and is_tpu_backend()
            ):
                raise NotImplementedError(
                    "attention-prob dropout under ring context parallelism "
                    "requires the flash path on real TPU (in-kernel masks "
                    "salted per (owner, chunk) — "
                    "sharding/ring_attention._chunk_seed); set dropout=0.0, "
                    "use_flash=True, or context_impl='ulysses'"
                )
            if drop_active and self.context_impl == "ulysses" \
                    and self.use_flash and not is_tpu_backend():
                raise NotImplementedError(
                    "in-kernel dropout needs the hardware PRNG: off-TPU "
                    "Ulysses dropout runs the dense core (use_flash=False)"
                )
            if self.context_impl == "ring":
                # GQA kv heads stay un-repeated: the ring repeats them after
                # each transfer so ppermute carries only n_kv heads.
                # use_flash swaps the per-chunk jnp einsum core for the
                # Pallas kernel (custom-VJP ring backward).
                if self.use_flash:
                    kwargs = {}
                    if drop_active:
                        # per-shard decorrelation comes from _chunk_seed's
                        # (owner, chunk) salt; the rng seed is shared so
                        # the same (owner, chunk) mask is used by fwd+bwd
                        kwargs = dict(
                            dropout_rate=self.dropout,
                            dropout_seed=jax.random.randint(
                                self.make_rng("dropout"), (), 0,
                                jnp.iinfo(jnp.int32).max,
                            ),
                        )
                    out = ring_flash_attention_local(
                        q, k, v, self.context_axis, causal=self.causal,
                        **kwargs,
                    )
                else:
                    out = ring_attention_local(
                        q, k, v, self.context_axis, causal=self.causal
                    )
            elif self.context_impl == "ulysses":
                # dropout: after the all_to_all each member computes FULL
                # attention for its own head group, so every (head, block)
                # mask is produced by exactly one member — the engine's
                # per-('context') rng fold already decorrelates members,
                # and the cores decorrelate heads internally (the kernel's
                # per-(bn, block) uid salt / the dense mask shape)
                if self.use_flash:
                    from solvingpapers_tpu.kernels import flash_attention

                    kwargs = {}
                    if drop_active:
                        kwargs = dict(
                            dropout_rate=self.dropout,
                            dropout_seed=jax.random.randint(
                                self.make_rng("dropout"), (), 0,
                                jnp.iinfo(jnp.int32).max,
                            ),
                        )
                    core = functools.partial(
                        flash_attention, causal=self.causal, **kwargs
                    )
                else:
                    kwargs = {}
                    if drop_active:
                        kwargs = dict(
                            dropout_rate=self.dropout,
                            dropout_rng=self.make_rng("dropout"),
                            deterministic=False,
                        )
                    core = functools.partial(
                        ops.dot_product_attention, causal=self.causal,
                        **kwargs,
                    )
                out = ulysses_attention_local(q, k, v, self.context_axis, core)
            else:
                raise ValueError(f"unknown context_impl {self.context_impl!r}")
        else:
            if self.use_flash:
                out = apply_flash_attention(
                    self, q, k, v, causal=self.causal,
                    dropout_rate=self.dropout, deterministic=deterministic,
                )
            else:
                out = ops.dot_product_attention(
                    q,
                    k,
                    v,
                    causal=self.causal,
                    dropout_rate=self.dropout,
                    dropout_rng=(
                        None if deterministic else self.make_rng("dropout")
                    ),
                    deterministic=deterministic,
                )

        out = out.reshape(b, s, self.n_heads * head_dim)
        out = dense(self.dim, "out")(out)
        if self.dropout > 0.0:
            out = nn.Dropout(self.dropout)(out, deterministic=deterministic)
        return out, cache


class MLP(nn.Module):
    """Plain 2-layer MLP (gpt/gpt-jax.ipynb cell 10; ViT.ipynb cell 10)."""

    dim: int
    hidden_dim: int
    activation: Callable[[jax.Array], jax.Array] = ops.gelu_tanh
    dropout: float = 0.0
    use_bias: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, *, deterministic: bool = True) -> jax.Array:
        x = nn.Dense(self.hidden_dim, use_bias=self.use_bias, dtype=self.dtype, name="fc")(x)
        x = self.activation(x)
        x = nn.Dense(self.dim, use_bias=self.use_bias, dtype=self.dtype, name="proj")(x)
        if self.dropout > 0.0:
            x = nn.Dropout(self.dropout)(x, deterministic=deterministic)
        return x


class GLUFFN(nn.Module):
    """Gated-linear-unit FFN: down(act(gate(x)) * up(x)).

    activation=silu → SwiGLU (llama3 cell 25, deepseekv3 cell 21);
    activation=gelu_tanh → GeGLU (gemma cell 9).
    """

    dim: int
    hidden_dim: int
    activation: Callable[[jax.Array], jax.Array] = ops.silu
    use_bias: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        gate = nn.Dense(self.hidden_dim, use_bias=self.use_bias, dtype=self.dtype, name="gate")(x)
        up = nn.Dense(self.hidden_dim, use_bias=self.use_bias, dtype=self.dtype, name="up")(x)
        return nn.Dense(self.dim, use_bias=self.use_bias, dtype=self.dtype, name="down")(
            self.activation(gate) * up
        )


def swiglu_hidden_dim(dim: int, multiplier: int = 4) -> int:
    """The (2/3)·4·dim sizing convention (deepseekv3 cell 21: ((2D)*4)//3)."""
    return (2 * dim * multiplier) // 3


def apply_flash_attention(module, q, k, v, *, causal, scale=None,
                          dropout_rate=0.0, deterministic=True):
    """Flash attention with the framework's dropout policy, shared by every
    use_flash model (Attention here, DeepSeekV3's MLA, `causal_attention`'s
    callers: q (B, S, n, w), k (B, S, n_kv, w), v (B, S, n_kv, w_v), each
    key-value head serving n / n_kv query heads): in-kernel prob
    dropout on real TPU (same Bernoulli semantics as the dense path; mask
    regenerated in the backward from the seed, never materialized); when
    dropout is active OFF-TPU the dense path runs instead — interpret-mode
    pltpu PRNG is a zero stub, so in-kernel dropout cannot run there.

    On a >1-device GSPMD mesh (Trainer marks it via sharding.ambient_mesh)
    the call routes through kernels.sharded_flash_attention — pallas_call is
    opaque to GSPMD, so the direct call would silently all-gather q/k/v
    (losing DP batch partitioning and TP head partitioning alike)."""
    from solvingpapers_tpu.kernels import flash_attention, sharded_flash_attention
    from solvingpapers_tpu.kernels.flash_attention import is_tpu_backend
    from solvingpapers_tpu.sharding import get_ambient_mesh

    mesh = get_ambient_mesh()
    if mesh is not None and mesh.devices.size > 1:
        kernel = functools.partial(sharded_flash_attention, mesh=mesh)
    else:
        kernel = flash_attention

    if dropout_rate > 0.0 and not deterministic:
        if is_tpu_backend():
            seed = jax.random.randint(
                module.make_rng("dropout"), (), 0, jnp.iinfo(jnp.int32).max
            )
            return kernel(
                q, k, v, causal=causal, scale=scale,
                dropout_rate=dropout_rate, dropout_seed=seed,
            )
        return ops.dot_product_attention(
            q, k, v, causal=causal, scale=scale, dropout_rate=dropout_rate,
            dropout_rng=module.make_rng("dropout"), deterministic=False,
        )
    return kernel(q, k, v, causal=causal, scale=scale)


def causal_attention(module, q, k, v, *, scale: float, use_flash: bool):
    """Causal softmax attention of q (B, S, n, w) over k, v (B, S, n_kv, w)
    under the scope `L_attn_core`, as every train-only family runs it:
    through the flash kernels or, with `use_flash` false, the dense product."""
    with jax.named_scope("L_attn_core"):
        if use_flash:
            return apply_flash_attention(
                module, q, k, v, causal=True, scale=scale)
        return ops.dot_product_attention(q, k, v, causal=True, scale=scale)


def maybe_remat(block_cls, remat: bool, caches) -> type:
    """Wrap a decoder-block class in jax.checkpoint for training (trades
    recompute for HBM — dense attention at dim/seq 1024 OOMs one v5e
    without it). Requires the block's __call__ signature to be
    (self, x, positions, cache, deterministic): static_argnums=(4,) marks
    the python-bool `deterministic` static (self counts as 0). Decode
    (caches present) has no backward pass, so remat is skipped there.
    Numerical equivalence: tests/test_llama3.py::test_remat_matches_noremat.
    """
    if remat and caches is None:
        return nn.remat(block_cls, prevent_cse=False, static_argnums=(4,))
    return block_cls


def remat_keeping(layer_cls, remat: bool, *names: str) -> type:
    """`layer_cls` rematerialised with `prevent_cse=True` (with `False` the
    compiler kept the first forward: PERF.md 7 (s)) but for the arrays named
    `names`, the results a kernel's forward rule names: which of them a
    family has the room to keep is that family's line, with its bytes."""
    if not remat:
        return layer_cls
    policy = (jax.checkpoint_policies.save_only_these_names(*names)
              if names else None)
    return nn.remat(layer_cls, prevent_cse=True, policy=policy)


def training_only(family: str, cfg, tokens, caches, why: str) -> None:
    """What the `__call__` of a family that only trains starts with: it
    refuses a decode cache with the family's own reason `why`, and a
    sequence past the config's `block_size`."""
    if caches is not None:
        raise NotImplementedError(f"{family} has no decode cache: {why}")
    if tokens.shape[1] > cfg.block_size:
        raise ValueError(
            f"sequence {tokens.shape[1]} exceeds block_size {cfg.block_size}")


def _by_blocks(fn, block: int, *arrays):
    """`fn` over blocks of `block` tokens of (B, S, ...) arrays, each block
    rematerialised in the backward pass (`lax.map` of a checkpointed body):
    what `fn` keeps for its backward is then a block's, not the
    sequence's. `fn` is per token; S not a multiple of `block` runs whole."""
    b, s = arrays[0].shape[:2]
    if s <= block or s % block:
        return fn(*arrays)
    split = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((b, s // block, block) + a.shape[2:]), 1, 0)
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape(  # noqa: E731
        (b, s) + a.shape[3:])
    out = jax.lax.map(lambda xs: jax.checkpoint(fn)(*xs),
                      tuple(split(a) for a in arrays))
    return jax.tree.map(join, out)


def blocked_swiglu(x, norm_w, w_gate, w_up, w_down, *, eps: float,
                   block: int, post_norm_w=None, scale: float | None = None):
    """x + SwiGLU(Norm(x)) block by block under the scope `L_dense_ffn`:
    the dense feed-forward half of a layer (Kimi-Linear's leading layer,
    every Ouro layer, every Granite-hybrid layer). x (B, S, D) float32; the
    matrices already in the compute dtype. With `post_norm_w` the
    sub-block's output is normed before the add (Ouro's sandwich); with
    `scale` it is multiplied by that factor in the add (Granite's
    `residual_multiplier`)."""
    dt = w_gate.dtype

    def ffn(x):
        h = ops.rms_norm(x, norm_w, eps).astype(dt)
        h = ops.silu(h @ w_gate) * (h @ w_up)
        y = (h @ w_down).astype(jnp.float32)
        if post_norm_w is not None:
            y = ops.rms_norm(y, post_norm_w, eps)
        return x + (y if scale is None else scale * y)

    with jax.named_scope("L_dense_ffn"):
        return _by_blocks(ffn, block, x)
