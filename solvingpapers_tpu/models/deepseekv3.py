"""DeepSeek-V3-style decoder: MLA + MoE + optional MTP.

Capability target: deepseekv3/deepseekv3.ipynb — the reference's flagship.
  * config (cell 4): block 256, dim 512, 8 heads, 6 layers, latent 64,
    8 experts top-2 + shared expert, aux-free load balancing (rate 0.001),
    noisy top-k off, mtp_heads 0, vocab 50257, dropout 0.1
  * sinusoidal PE added to embeddings (cells 16-17; the `base_freq` config
    knob is dead in the reference — not reproduced)
  * MLA with absorbed query attending latents directly (cell 25)
  * MoE with masked-softmax top-2 over biased gate logits, shared expert,
    no-grad bias update sign(mean(load)-load) (cell 23)
  * depth scaling 2*L^-0.5 after the layer stack, final RMSNorm, lm_head
    weight-tied to the embedding (cell 31)
  * MTP: per extra head k, merge Linear(2D->D) of [norm(h), norm(emb of
    token i+k)] -> extra DecoderLayer -> proj head -> shared lm_head
    (cell 33's machinery, vectorized; the shipped config disables it)

TPU-first divergences (documented per SURVEY.md hard part #2):
  * One latent per layer shared by all heads with per-head decompression
    (the paper's MLA); the reference gives each head its own W_dkv and
    threads one growing cache through heads AND layers (cell 27 quirk).
  * MoE dispatch is static-shape row gathers into expert capacity slots
    (ops/moe.py), not a python loop; expert weights are stacked (E, ...)
    so the `expert` mesh axis shards them (EP via GSPMD all_to_all).
  * MTP is computed for all positions in parallel, not a per-position
    python loop.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from solvingpapers_tpu import ops
from solvingpapers_tpu.infer.cache import (
    CPLatentCache, LatentCache, update_latent_cache,
)
from solvingpapers_tpu.kernels import moe_grouped
from solvingpapers_tpu.models.layers import (
    GLUFFN, RMSNorm, LayerNorm, maybe_remat, swiglu_hidden_dim,
)


@dataclasses.dataclass(frozen=True)
class DeepSeekV3Config:
    vocab_size: int = 50257
    block_size: int = 256
    dim: int = 512
    n_layers: int = 6
    n_heads: int = 8
    latent_dim: int = 64
    n_experts: int = 8
    top_experts: int = 2
    # decoupled-RoPE branch width for MLA (real DeepSeek-V3's d_h^R; the
    # reference notebook's sinusoidal-only simplification is rope_dim=0).
    # Compressed-latent attention alone has no precise relative-position
    # channel — on position-critical data (e.g. the order-k Markov quality
    # corpus) the notebook variant cannot beat the unigram floor. A small
    # rotary query per head and ONE shared rotary key ride along the latent
    # score via concatenation, so k = v = cat(latent, k_rope) stays MQA and
    # every attention path (dense/flash/ring/cache) is unchanged in shape.
    rope_dim: int = 0
    rope_theta: float = 10000.0
    # Scale on the additive sinusoidal PE. The notebook adds O(1) sinusoids
    # to 0.02-std embeddings (cells 16-17, 31), so position carries ~50x the
    # token signal into layer 1 AND into the gate of every MoE layer — on
    # position-critical corpora the model cannot beat the unigram floor, and
    # the routing gate specializes experts by position (the drop_fraction
    # 0.2-0.5 / load_max 0.7 collapse the round-2 verdict flagged traces to
    # exactly this). 0.02 balances the two signals (measured: markov-corpus
    # val gap 1.80 -> 0.08 nats; drop_fraction 0.5 -> 0.0). Default 1.0 is
    # strict notebook parity (golden tests pin it); every shipped training
    # workload sets 0.02.
    pe_scale: float = 1.0
    use_shared_expert: bool = True
    noisy_topk: bool = False
    use_aux_free: bool = True
    aux_free_bias_update_rate: float = 0.001
    # optional complementary sequence-wise balance loss (DeepSeek-V3 paper's
    # L_Bal, eq. 17-18 — the notebook implements only the bias mechanism):
    # weight * sum_e f_e * P_e with f_e the scaled selection fraction and
    # P_e the mean gate probability. 0.0 = off (notebook parity); small
    # values (1e-3..1e-2) push residual imbalance the bias update alone
    # leaves (drop_fraction > 0 on clustered data).
    balance_loss_weight: float = 0.0
    moe_impl: str = "dispatch"  # dispatch | dense
    capacity_factor: float = 2.0
    mtp_heads: int = 0
    mtp_loss_weight: float = 0.3
    dropout: float = 0.1
    attn_dropout: float = 0.1
    remat: bool = False  # jax.checkpoint each decoder layer
    use_flash: bool = False  # MLA scores via the Pallas flash kernel (train path)
    # context parallelism (apply inside a shard_map whose 'context' axis
    # shards the sequence): MLA runs the kv ring over the LATENT stream
    # (absorbed-query MLA is MQA with k = v = latents, so the ring's
    # n_kv=1 path serves it; Ulysses cannot — 1 kv head can't split).
    # MoE load stats / bias updates are psum'd across the step's axes so
    # the routing state stays shard-invariant.
    context_parallel: bool = False
    # how the 'expert' mesh axis is used inside the CP shard_map:
    #   "sliced"     — tokens replicated over 'expert'; each member runs its
    #                  E/ep expert columns and partial combines psum
    #                  (ops.moe.moe_expert_sliced_combine).
    #   "all_to_all" — token-dispatch EP: each member owns 1/ep of the
    #                  tokens, all_to_all ships capacity slots to the
    #                  experts' owners and back, an all_gather restores the
    #                  replicated-token contract afterwards
    #                  (ops.moe.moe_all_to_all_combine) — communication
    #                  scales with routed capacity, not the full token count.
    ep_impl: str = "sliced"
    norm_eps: float = 1e-6
    dtype: str = "float32"

    def __post_init__(self):
        if self.ep_impl not in ("sliced", "all_to_all"):
            raise ValueError(
                f"ep_impl must be 'sliced' or 'all_to_all', got "
                f"{self.ep_impl!r}"
            )
        if self.moe_impl not in ("dispatch", "dense"):
            raise ValueError(
                f"moe_impl must be 'dispatch' or 'dense', got "
                f"{self.moe_impl!r}"
            )

    @property
    def stats_axes(self) -> tuple | None:
        """Axes MoE state/stats must be psum'd over under shard_map."""
        return ("data", "fsdp", "context") if self.context_parallel else None

    @property
    def compute_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def expert_hidden(self) -> int:
        return swiglu_hidden_dim(self.dim)  # ((2D)*4)//3, cell 21


class MLA(nn.Module):
    """Multi-head latent attention with absorbed queries (cell 25).

    The (B, S, L) latent is both the cache and the attention target:
    scores = (x W_q W_k^T) @ latent^T, context = probs @ latent, decompressed
    per head only on output (@ W_v). No (S, head_dim) k/v are materialized.
    """

    cfg: DeepSeekV3Config

    @nn.compact
    def __call__(self, x, positions=None, cache=None, deterministic=True,
                 attend_len=None):
        cfg = self.cfg
        b, s, _ = x.shape
        n, hd, lat = cfg.n_heads, cfg.head_dim, cfg.latent_dim
        if positions is None:
            # CP-aware default (global positions derived from the axis
            # index) — the PP stage_fn applies layers without positions, so
            # under CP x PP this default must not restart at 0 per shard
            from solvingpapers_tpu.models.layers import default_positions

            positions = default_positions(b, s, cfg.context_parallel)
        cp_cache = cache is not None and cfg.context_parallel
        if cp_cache:
            from solvingpapers_tpu.infer.cache import validate_cp_cache

            validate_cp_cache(
                cache, CPLatentCache,
                getattr(cache, "c_prompt", jnp.zeros((1, 0, 1))).shape[1], s,
            )

        with jax.named_scope("L_attn_proj"):
            latent = nn.Dense(
                lat, use_bias=False, dtype=cfg.compute_dtype, name="w_dkv"
            )(x)  # (B, S, L)
            init = nn.initializers.normal(0.02)
            w_q = self.param("w_q", init, (cfg.dim, n, hd))
            w_k = self.param("w_k", init, (lat, n, hd))
            w_v = self.param("w_v", init, (lat, n, hd))

            dt = cfg.compute_dtype
            q = jnp.einsum("bsd,dnh->bsnh", x.astype(dt), w_q.astype(dt))
            # absorbed query: project q into latent space once, score vs latents
            q_lat = jnp.einsum("bsnh,lnh->bsnl", q, w_k.astype(dt))

            R = cfg.rope_dim
            if R:
                # decoupled RoPE (real DSV3; see DeepSeekV3Config.rope_dim): the
                # rotary halves concatenate onto the latent score so the cache,
                # ring and flash paths below all operate on (L+R)-wide vectors
                cos, sin = ops.precompute_rope(R, cfg.block_size, cfg.rope_theta)
                w_qr = self.param("w_qr", init, (cfg.dim, n, R))
                q_rope = jnp.einsum("bsd,dnr->bsnr", x.astype(dt), w_qr.astype(dt))
                q_rope = ops.apply_rope(q_rope, cos, sin, positions=positions)
                k_rope = nn.Dense(R, use_bias=False, dtype=dt, name="w_kr")(x)
                k_rope = ops.apply_rope(
                    k_rope[:, :, None, :], cos, sin, positions=positions
                )[:, :, 0]
                q_lat = jnp.concatenate([q_lat, q_rope.astype(dt)], axis=-1)
                latent = jnp.concatenate(
                    [latent.astype(dt), k_rope.astype(dt)], axis=-1
                )
            scale = (hd + R) ** -0.5 if R else hd**-0.5

        with jax.named_scope("L_attn_core"):
            if cp_cache and s > 1:
                # CP PREFILL: this shard's contiguous prompt chunk exactly fills
                # its c_prompt slice — written in place, no resharding — and
                # attention falls through to the ring path below (cross-shard
                # causality is the ring's job, cache slots play no part yet)
                cache = cache.replace(
                    c_prompt=latent.astype(cache.c_prompt.dtype)
                )
            if cp_cache and s == 1:
                # CP DECODE STEP: the token is replicated across the context
                # axis; its latent lands in the replicated tail, shard-local
                # logsumexp partials over the sharded prompt chunk (+ tail on
                # the last shard only, counted once) combine with one pmax +
                # two psums — the 32k+ prompt cache never moves off its shard.
                from solvingpapers_tpu.infer.cache import cp_cache_partial_softmax
                from solvingpapers_tpu.ops.attention import BIG_NEG

                cp_size = jax.lax.psum(1, "context")
                idx = jax.lax.axis_index("context")
                s0_glob = cache.c_prompt.shape[1] * cp_size
                tail_len = cache.c_tail.shape[1]
                pos = positions[0, 0]
                cache = cache.replace(
                    c_tail=jax.lax.dynamic_update_slice(
                        cache.c_tail, latent.astype(cache.c_tail.dtype),
                        (0, pos - s0_glob, 0),
                    )
                )
                q32 = q_lat.astype(jnp.float32) * scale
                # every prompt slot precedes pos (pos >= s0_glob): no mask
                scores_p = jnp.einsum(
                    "bsnl,btl->bnst", q32, cache.c_prompt.astype(jnp.float32)
                )
                scores_t = jnp.einsum(
                    "bsnl,btl->bnst", q32, cache.c_tail.astype(jnp.float32)
                )
                tail_pos = s0_glob + jnp.arange(tail_len)
                mask_t = (tail_pos[None, None, None, :] <= pos) & (
                    idx == cp_size - 1
                )
                scores_t = jnp.where(mask_t, scores_t, BIG_NEG)
                vals = jnp.concatenate([cache.c_prompt, cache.c_tail], axis=1)
                ctx = cp_cache_partial_softmax(
                    scores_p, scores_t, vals, "context"
                ).astype(dt)
            elif cfg.context_parallel and (cache is None or s > 1):
                # ring over the latent stream (k = v = latents, one shared kv
                # head): long-context CP for the flagship family. The same
                # latent-space algebra as the dense path — decompression by
                # w_v happens after the ring, on the local ctx shard.
                from solvingpapers_tpu.sharding.ring_attention import (
                    ring_attention_local,
                    ring_flash_attention_local,
                )

                from solvingpapers_tpu.kernels.flash_attention import (
                    is_tpu_backend,
                )

                drop_active = cfg.attn_dropout > 0.0 and not deterministic
                if drop_active and not (cfg.use_flash and is_tpu_backend()):
                    raise NotImplementedError(
                        "attention-prob dropout under context_parallel MLA "
                        "requires the ring-flash path on real TPU (per-chunk "
                        "in-kernel masks); set attn_dropout=0.0 or use_flash"
                    )
                c_kv = latent.astype(dt)[:, :, None, :]  # (B, S_loc, 1, L)
                if cfg.use_flash:
                    kwargs = {}
                    if drop_active:
                        kwargs = dict(
                            dropout_rate=cfg.attn_dropout,
                            dropout_seed=jax.random.randint(
                                self.make_rng("dropout"), (), 0,
                                jnp.iinfo(jnp.int32).max,
                            ),
                        )
                    ctx = ring_flash_attention_local(
                        q_lat, c_kv, c_kv, "context", causal=True, scale=scale,
                        **kwargs,
                    ).astype(dt)
                else:
                    ctx = ring_attention_local(
                        q_lat, c_kv, c_kv, "context", causal=True, scale=scale
                    ).astype(dt)
            elif cache is None and cfg.use_flash:
                # absorbed-query MLA *is* MQA over the latent stream: scores are
                # q_lat . c and the context is probs @ c, i.e. attention with
                # k = v = c and one shared kv head — so the Pallas flash kernel
                # serves MLA directly (head_dim = latent_dim), giving the
                # flagship family the same long-context memory profile as the
                # GQA models (no (S, S) probs in HBM). Cached decode keeps the
                # dense einsum path (per-step scores are (1, t), already small).
                from solvingpapers_tpu.models.layers import apply_flash_attention

                c_kv = latent.astype(dt)[:, :, None, :]  # (B, S, 1, L)
                ctx = apply_flash_attention(
                    self, q_lat, c_kv, c_kv, causal=True, scale=scale,
                    dropout_rate=cfg.attn_dropout, deterministic=deterministic,
                ).astype(dt)
            elif cache is not None and attend_len is not None:
                # PREFILL: this chunk occupies cache slots [attend_len - S,
                # attend_len) with every earlier slot written, so attention is
                # end-aligned causal over a STATIC slice of the latent cache —
                # no (S, max_len) score tensor (16k-prompt prefill fits HBM).
                cache = update_latent_cache(cache, latent, positions[0, 0])
                c_att = jax.lax.slice_in_dim(cache.c, 0, attend_len, axis=1)
                c_kv = c_att[:, :, None, :]  # (B, attend_len, 1, L[+R])
                if cfg.use_flash:
                    from solvingpapers_tpu.models.layers import apply_flash_attention

                    ctx = apply_flash_attention(
                        self, q_lat, c_kv, c_kv, causal=True, scale=scale,
                    ).astype(dt)
                else:
                    ctx = ops.dot_product_attention(
                        q_lat, c_kv, c_kv, causal=True, scale=scale
                    ).astype(dt)
            else:
                if cache is not None:
                    cache = update_latent_cache(cache, latent, positions[0, 0])
                    c_full = cache.c
                    kv_idx = jnp.arange(cache.max_len)
                    mask = kv_idx[None, None, None, :] <= positions[:, None, :, None]
                else:
                    c_full = latent
                    q_idx = jnp.arange(s)
                    mask = (q_idx[None, :, None] >= q_idx[None, None, :])[:, None]

                scores = (
                    jnp.einsum("bsnl,btl->bnst", q_lat, c_full.astype(dt)).astype(
                        jnp.float32
                    )
                    * scale
                )
                scores = jnp.where(mask, scores, ops.attention.BIG_NEG)
                probs = jax.nn.softmax(scores, axis=-1)
                if cfg.attn_dropout > 0.0 and not deterministic:
                    keep = jax.random.bernoulli(
                        self.make_rng("dropout"), 1.0 - cfg.attn_dropout, probs.shape
                    )
                    probs = probs * keep / (1.0 - cfg.attn_dropout)
                probs = probs.astype(dt)
                ctx = jnp.einsum("bnst,btl->bsnl", probs, c_full.astype(dt))

        with jax.named_scope("L_attn_proj"):
            if R:
                # the rotary tail of cat(latent, k_rope) is score-only; values
                # decompress from the latent part alone
                ctx = ctx[..., :lat]
            out = jnp.einsum("bsnl,lnh->bsnh", ctx, w_v.astype(dt))
            out = out.reshape(b, s, n * hd)
            out = nn.Dense(cfg.dim, use_bias=False, dtype=dt, name="out")(out)
            if cfg.attn_dropout > 0.0:
                out = nn.Dropout(cfg.attn_dropout)(out, deterministic=deterministic)
        return out, cache


class MoELayer(nn.Module):
    """Top-k MoE with shared expert and aux-free load balancing (cell 23).

    Expert weights are stacked (E, ...) arrays (SwiGLU per expert, cell 21:
    w3(swish(w1 x) * (w2 x)), hidden ((2D)*4)//3). The routing bias lives in
    the 'moe_state' variable collection — the functional analogue of the
    reference's registered buffer updated under no_grad; the train step
    threads it through TrainState.model_state.
    """

    cfg: DeepSeekV3Config

    @nn.compact
    def __call__(self, x, *, deterministic=True):
        cfg = self.cfg
        b, s, d = x.shape
        h = cfg.expert_hidden
        e = cfg.n_experts
        dt = cfg.compute_dtype
        with jax.named_scope("L_moe_gate"):
            xt = x.reshape(b * s, d).astype(dt)
            gate_logits = nn.Dense(
                e, use_bias=False, dtype=jnp.float32, name="gate"
            )(xt.astype(jnp.float32))
            if cfg.noisy_topk:
                # layer created unconditionally so init (deterministic) still
                # builds its params; noise applied only in train mode
                noise_scale = jax.nn.softplus(
                    nn.Dense(
                        e, use_bias=False, dtype=jnp.float32, name="noise"
                    )(xt.astype(jnp.float32))
                )
                if not deterministic:
                    gate_logits = gate_logits + noise_scale * jax.random.normal(
                        self.make_rng("dropout"), gate_logits.shape
                    )
            bias = self.variable(
                "moe_state", "routing_bias",
                lambda: jnp.zeros((e,), jnp.float32),
            )
            biased = (
                gate_logits + bias.value if cfg.use_aux_free else gate_logits
            )
            # reference detail: both selection AND softmax weights use the
            # biased logits (cell 23 scatters top_k_values of the biased
            # tensor)
            probs = ops.moe.topk_gate_probs(biased, cfg.top_experts)

        init = nn.initializers.normal(0.02)
        w1 = self.param("w1", init, (e, d, h))
        w2 = self.param("w2", init, (e, d, h))
        w3 = self.param("w3", init, (e, h, d))

        # (probs, axes) the drop metric must count over — the a2a path
        # dispatches per-member token shards, so its drops are counted from
        # the shard's probs and psum'd over the expert axis too
        drop_probs = drop_axes = None
        # whether the routed experts run as kernels/moe_grouped.py's kernels
        grouped = False

        if cfg.moe_impl == "dense":
            def expert_fn_all(xt):
                a = jnp.einsum("td,edh->eth", xt, w1.astype(dt))
                g = jnp.einsum("td,edh->eth", xt, w2.astype(dt))
                return jnp.einsum("eth,ehd->etd", ops.swish(a) * g, w3.astype(dt))

            out = ops.moe.moe_dense_combine(xt, probs, expert_fn_all)
        else:
            def expert_body(xe, w1s, w2s, w3s):  # (E', C, D) -> (E', C, D)
                a = jnp.einsum("ecd,edh->ech", xe, w1s)
                g = jnp.einsum("ecd,edh->ech", xe, w2s)
                return jnp.einsum("ech,ehd->ecd", ops.swish(a) * g, w3s)

            def expert_fn(xe, fill):  # (E, C, D), (E,) -> (E, C, D)
                ws = w1.astype(dt), w2.astype(dt), w3.astype(dt)
                if grouped:
                    # the same product over the tiles of rows that hold a
                    # token; the slots behind an expert's fill are zero
                    # rows, which give zero rows either way
                    return moe_grouped.grouped_glu(
                        xe, *ws, fill, activation=ops.swish)
                return expert_body(xe, *ws)

            # under CP/shard_map b*s is the LOCAL token count, so capacity
            # is per-shard — the standard distributed-MoE dispatch
            # semantics. Parity with the dense single-device step is exact
            # in the drop-free regime; once capacity binds, drops are
            # decided per shard rather than globally (watch
            # moe_drop_fraction, psum'd across shards).
            cap = ops.moe.expert_capacity(
                b * s, e, cfg.top_experts, cfg.capacity_factor
            )
            # by what the call can see, no flag: one TPU and whole row
            # tiles (a train step's thousands of slots, not a decode
            # call's few); the shard_map paths below keep the einsums
            grouped = not cfg.context_parallel and moe_grouped.engages(cap, d)
            if cfg.context_parallel:
                # inside the CP shard_map the 'expert' mesh axis shards
                # expert COMPUTE, not just storage: the in-step ZeRO gather
                # hands every member the full (E, ...) stacks, but each
                # member dispatches only its E/ep expert columns against its
                # own slice and the partial combines psum over the axis
                # (ops.moe.moe_expert_sliced_combine). With ep == 1 the
                # slice is the whole stack and this is exactly the line
                # above. probs stay replicated over 'expert' (gate weights
                # are), so slot assignment per column matches unsharded.
                def expert_fn_sliced(xe, start):  # (E/ep, C, D), first idx
                    sl = lambda w: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                        w.astype(dt), start, xe.shape[0], 0
                    )
                    return expert_body(xe, sl(w1), sl(w2), sl(w3))

                if cfg.ep_impl == "all_to_all":
                    # token-dispatch EP: the gate ran on the full replicated
                    # tokens (cheap, and keeps probs identical across the
                    # axis for the stats below); dispatch/expert/combine run
                    # on this member's 1/ep token slice with tokens moved by
                    # all_to_all, then an all_gather restores the
                    # replicated-token contract for the residual stream.
                    ep = jax.lax.psum(1, "expert")
                    tl = (b * s) // ep
                    if (b * s) % ep:
                        raise ValueError(
                            f"{b * s} local tokens not divisible by the "
                            f"'expert' axis ({ep}) for ep_impl=all_to_all"
                        )
                    idx = jax.lax.axis_index("expert")
                    x_sh = jax.lax.dynamic_slice_in_dim(xt, idx * tl, tl, 0)
                    p_sh = jax.lax.dynamic_slice_in_dim(probs, idx * tl, tl, 0)
                    cap = ops.moe.expert_capacity(
                        tl, e, cfg.top_experts, cfg.capacity_factor
                    )
                    out = ops.moe.moe_all_to_all_combine(
                        x_sh, p_sh, expert_fn_sliced, cap, axis_name="expert"
                    )
                    out = jax.lax.all_gather(out, "expert", axis=0, tiled=True)
                    drop_probs, drop_axes = p_sh, (
                        tuple(cfg.stats_axes) + ("expert",)
                    )
                else:
                    out = ops.moe.moe_expert_sliced_combine(
                        xt, probs, expert_fn_sliced, cap, axis_name="expert"
                    )
            else:
                out = ops.moe.moe_dispatch_combine(
                    xt, probs, expert_fn, cap, pass_fill=True
                )

        if cfg.use_shared_expert:
            with jax.named_scope("L_moe_shared"):
                out = out + GLUFFN(
                    dim=d, hidden_dim=h, activation=ops.swish, dtype=dt,
                    name="shared_expert",
                )(xt)

        # one load reduction (+ one cross-shard collective under CP) shared
        # by the bias update and the sown stats. probs_g: along a ZeRO'd
        # 'expert' axis every member holds identical probs (tokens are
        # replicated across it) but the vma types them varying after the
        # gathered expert weights touch the residual stream — the pmean is
        # a numeric no-op that certifies the invariant-state contract.
        probs_g = (
            jax.lax.pmean(probs, "expert") if cfg.stats_axes is not None
            else probs
        )
        ci = None
        if (
            cfg.use_aux_free
            and not deterministic
            and self.is_mutable_collection("moe_state")
        ):
            # stats_axes: under shard_map the load is psum'd so every shard
            # applies the identical bias update (shard-invariant state)
            ci = ops.moe.expert_load(probs_g, cfg.stats_axes)
            bias.value = ops.moe.aux_free_bias_update(
                probs_g, bias.value, cfg.aux_free_bias_update_rate, ci=ci
            )

        if (
            cfg.balance_loss_weight > 0.0
            and self.is_mutable_collection("moe_metrics")
        ):
            # sequence-wise balance loss (differentiable — NOT under the
            # stop_gradient the stats below use): f_e = selection fraction
            # scaled by E/k, P_e = mean softmax gate prob over ALL experts.
            # dsv3_loss_fn reads the sown value and adds weight * mean.
            with jax.named_scope("L_moe_stats"):
                sel_frac = jnp.mean((probs > 0.0).astype(jnp.float32), axis=0)
                f = sel_frac * (e / cfg.top_experts)
                p_full = jnp.mean(
                    jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1),
                    axis=0,
                )
                balance = jnp.sum(f * p_full)
            self.sow("moe_metrics", "balance_loss", balance)

        if self.is_mutable_collection("moe_metrics"):
            # load-balance observability (SURVEY.md hard part #1): sown per
            # layer, aggregated into train metrics by dsv3_loss_fn
            if ci is None:
                ci = ops.moe.expert_load(probs_g, cfg.stats_axes)
            stats = ops.moe.load_balance_stats(
                probs_g, axis_names=cfg.stats_axes, ci=ci
            )
            # raw (E,) routed load: consumers that must re-derive the
            # aux-free bias update OUTSIDE the layer (the pipeline-parallel
            # wrapper, where the in-layer update can't run because the
            # GPipe stage_fn applies layers immutably) read it from here;
            # _aggregate_moe_metrics skips it (vector, not a train scalar)
            stats["ci"] = ci
            stats["drop_fraction"] = (
                jnp.zeros(()) if cfg.moe_impl == "dense"
                else ops.moe.dispatch_drop_fraction(
                    probs_g if drop_probs is None else drop_probs,
                    cap,
                    axis_names=(
                        cfg.stats_axes if drop_probs is None else drop_axes
                    ),
                )
            )
            # share of the experts' row tiles that are multiplied: 1 where
            # the einsums run over every slot (made from another stat, so
            # that inside a shard_map it varies over the axes they do)
            stats["live_tile_fraction"] = (
                ops.moe.live_tile_fraction(probs, cap, moe_grouped.ROW_TILE)
                if grouped else 1.0 + 0.0 * stats["drop_fraction"]
            )
            with jax.named_scope("L_moe_stats"):
                stats["bias_norm"] = jnp.linalg.norm(bias.value)
            self.sow("moe_metrics", "stats", stats)
        with jax.named_scope("L_moe_combine"):
            return out.reshape(b, s, d).astype(x.dtype)


class DSV3DecoderLayer(nn.Module):
    """Pre-RMSNorm MLA + residual; pre-RMSNorm MoE + residual (cell 29)."""

    cfg: DeepSeekV3Config

    @nn.compact
    def __call__(self, x, positions=None, cache=None, deterministic=True,
                 attend_len=None):
        cfg = self.cfg
        # the block's norms and residual adds go to the layer scope next
        # to them, so that no device time of the step is without one
        with jax.named_scope("L_attn_proj"):
            h = RMSNorm(eps=cfg.norm_eps, name="norm1")(x)
        h, cache = MLA(cfg, name="mla")(
            h,
            positions=positions,
            cache=cache,
            deterministic=deterministic,
            attend_len=attend_len,
        )
        with jax.named_scope("L_attn_proj"):
            x = x + h
        with jax.named_scope("L_moe_gate"):
            h = RMSNorm(eps=cfg.norm_eps, name="norm2")(x)
        h = MoELayer(cfg, name="moe")(h, deterministic=deterministic)
        with jax.named_scope("L_moe_combine"):
            x = x + h
        return x, cache


class DeepSeekV3(nn.Module):
    cfg: DeepSeekV3Config

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        *,
        positions: jax.Array | None = None,
        caches: list[LatentCache] | None = None,
        deterministic: bool = True,
        return_mtp: bool = False,
        attend_len: int | None = None,
        return_hidden: bool = False,
    ):
        """Returns (logits, caches) or ((logits, mtp_logits), caches) when
        return_mtp=True and mtp_heads > 0 (mtp_logits: (B, T, K, V)).
        return_hidden: return ((logits, hidden), caches) with the post-
        norm_f hidden stream — the MTP draft head's input during
        speculative decoding (infer/speculative.py)."""
        cfg = self.cfg
        if return_hidden and return_mtp and cfg.mtp_heads > 0:
            # the two returns share an unpack shape ((logits, X), caches),
            # so allowing both would silently hand mtp_logits to a caller
            # expecting the hidden stream
            raise ValueError("return_hidden and return_mtp are mutually exclusive")
        b, s = tokens.shape
        if positions is None:
            from solvingpapers_tpu.models.layers import default_positions

            # max_positions: the sinusoidal table length (same silent-clamp
            # hazard as a learned table)
            positions = default_positions(
                b, s, cfg.context_parallel, max_positions=cfg.block_size
            )
        embed = nn.Embed(
            cfg.vocab_size, cfg.dim, dtype=cfg.compute_dtype,
            embedding_init=nn.initializers.normal(0.02), name="tok_emb",
        )
        # no input dropout: the reference's forward goes embedding -> PE ->
        # decoder directly (cell 33); dropout appears only after the layer
        # stack (cell 31)
        with jax.named_scope("L_embed"):
            pe = ops.sinusoidal_position_encoding(cfg.block_size, cfg.dim)
            x = embed(tokens) + cfg.pe_scale * jnp.take(
                pe, positions, axis=0
            ).astype(cfg.compute_dtype)

        new_caches = [] if caches is not None else None
        layer_cls = maybe_remat(DSV3DecoderLayer, cfg.remat, caches)
        for i in range(cfg.n_layers):
            x, c = layer_cls(cfg, name=f"layer_{i}")(
                x,
                positions,
                None if caches is None else caches[i],
                deterministic,
                attend_len,
            )
            if new_caches is not None:
                new_caches.append(c)

        with jax.named_scope("L_loss_head"):
            if cfg.dropout > 0.0:
                x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)
            # deepseek depth scaling (cell 31)
            x = 2.0 * cfg.n_layers**-0.5 * x
            x = RMSNorm(eps=cfg.norm_eps, name="norm_f")(x)
            # weight-tied head
            logits = embed.attend(x.astype(cfg.compute_dtype))

        if not (return_mtp and cfg.mtp_heads > 0):
            if return_hidden:
                return (logits, x), new_caches
            return logits, new_caches

        # ---- MTP: vectorized version of cell 33's per-position loop ----
        # TWIN of DSV3Pipe.apply's functional MTP branch: changes here must
        # be mirrored there (test_dsv3_pipe_mtp_export_matches_dense_family
        # pins the equality).
        mtp_logits = []
        h_prev = x
        for k in range(1, cfg.mtp_heads + 1):
            # embedding of token at position i+k (zero-padded past the end;
            # the loss masks those targets out). Under CP the shift crosses
            # shard boundaries: a k-token halo from the right neighbor
            # (ppermute) makes it local — same global stream, shard-local
            # view (sharding.cp_halo_right)
            if cfg.context_parallel:
                from solvingpapers_tpu.sharding import cp_shift_left

                shifted = cp_shift_left(tokens, k, fill=0)
            else:
                shifted = jnp.pad(tokens[:, k:], ((0, 0), (0, k)))
            emb_k = embed(shifted)
            merged = jnp.concatenate(
                [
                    LayerNorm(name=f"mtp_norm_h_{k}")(h_prev),
                    LayerNorm(name=f"mtp_norm_e_{k}")(emb_k),
                ],
                axis=-1,
            )
            merged = nn.Dense(
                cfg.dim, use_bias=False, dtype=cfg.compute_dtype,
                name=f"mtp_merge_{k}",
            )(merged)
            h_k, _ = DSV3DecoderLayer(cfg, name=f"mtp_layer_{k}")(
                merged, positions=positions, deterministic=deterministic
            )
            proj = nn.Dense(
                cfg.dim, use_bias=False, dtype=cfg.compute_dtype,
                name=f"mtp_proj_{k}",
            )(h_k)
            mtp_logits.append(embed.attend(proj.astype(cfg.compute_dtype)))
            h_prev = h_k
        return (logits, jnp.stack(mtp_logits, axis=2)), new_caches

    @property
    def max_positions(self) -> int:
        return self.cfg.block_size

    def init_caches(self, batch: int, max_len: int, dtype=None) -> list[LatentCache]:
        cfg = self.cfg
        dtype = dtype or cfg.compute_dtype
        return [
            # the cache row is cat(latent, k_rope) when the decoupled-RoPE
            # branch is on (MLA concatenates before the cache update)
            LatentCache.init(batch, max_len, cfg.latent_dim + cfg.rope_dim, dtype)
            for _ in range(cfg.n_layers)
        ]

    def init_cp_caches(
        self, batch: int, prompt_local: int, tail_len: int, dtype=None
    ) -> list[CPLatentCache]:
        """Context-sharded decode caches (one per layer): `prompt_local` is
        the per-shard prompt chunk length (global prompt / context axis),
        `tail_len` the decode budget (replicated)."""
        cfg = self.cfg
        dtype = dtype or cfg.compute_dtype
        return [
            CPLatentCache.init(
                batch, prompt_local, tail_len,
                cfg.latent_dim + cfg.rope_dim, dtype,
            )
            for _ in range(cfg.n_layers)
        ]


def mtp_head_apply(cfg, params, moe_state, h, next_tokens, positions,
                   cache=None, attend_len=None, head=1, rngs=None,
                   collect_stats=False):
    """One MTP head applied functionally from the param dict — the ONE
    functional form of DeepSeekV3.__call__'s flax-module MTP branch (that
    branch is the only other copy; the module/functional boundary keeps
    them separate). Used by the staged family's training branch
    (models/deepseekv3_pipe.py, with `collect_stats`/`rngs`) and by
    speculative decoding (infer/speculative.py, with `cache`): merged =
    merge([norm(h), norm(emb of the NEXT token)]) -> mtp_layer (optionally
    with its OWN latent cache: at decode the head is a little
    autoregressive model over merged reps) -> proj -> tied head.

    h: (B, S, D) post-norm_f hiddens at `positions` (the previous head's
    output when chaining heads); next_tokens: (B, S) the token at
    position+head for each column. Returns (logits, y, cache, stats) —
    logits[:, i] predicts the token at positions[:, i] + head + 1, y is
    the head layer's hidden (the next head's h), stats the layer's sown
    MoE stats dict when collect_stats else None.
    """
    from solvingpapers_tpu.models.layers import LayerNorm

    dt = cfg.compute_dtype
    emb_table = params["tok_emb"]["embedding"]
    emb = jnp.take(emb_table, next_tokens, axis=0).astype(dt)
    merged = jnp.concatenate(
        [
            LayerNorm().apply({"params": params[f"mtp_norm_h_{head}"]}, h),
            LayerNorm().apply({"params": params[f"mtp_norm_e_{head}"]}, emb),
        ],
        axis=-1,
    ).astype(dt)
    merged = merged @ params[f"mtp_merge_{head}"]["kernel"].astype(dt)
    variables = {
        "params": params[f"mtp_layer_{head}"],
        "moe_state": moe_state[f"mtp_layer_{head}"],
    }
    det = rngs is None
    kwargs = {} if det else {"rngs": rngs}
    stats = None
    if collect_stats:
        (y, cache), mut = DSV3DecoderLayer(cfg).apply(
            variables, merged, positions, cache, det, attend_len,
            mutable=["moe_metrics"], **kwargs,
        )
        stats = mut["moe_metrics"]["moe"]["stats"][0]
    else:
        y, cache = DSV3DecoderLayer(cfg).apply(
            variables, merged, positions, cache, det, attend_len, **kwargs,
        )
    proj = y.astype(dt) @ params[f"mtp_proj_{head}"]["kernel"].astype(dt)
    return proj @ emb_table.T.astype(dt), y, cache, stats
