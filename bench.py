"""Benchmark of record — prints ONE JSON line.

Primary metric (top-level keys, driver contract): the reference's own GPT
char-LM training config (gpt/gpt-jax.ipynb cell 8: batch 128 x block 256 =
32,768 tok/step, dim 256, 1 head, 8 layers) trained with AdamW in bf16 on
this repo's engine, vs the reference's measured ~16.1k tok/s (1x T4,
BASELINE.md). Metric: steady-state training tokens/sec.

`scorecard` (same JSON line): the full driver-visible surface the round-2
verdict asked for (missing item 5) — the 350M MFU study point, flash-MLA
16k step time, cached-decode throughput incl. a 16k-prompt prefill row,
and the in-kernel dropout linearity identity, so the kernel's riskiest
path is verified every round. Each row is isolated: a failure records
{"error": ...} and the remaining rows still run, but the process then
exits non-zero. The script measures the TPU and refuses to start on any
other device.
"""

from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BASELINE_TOK_S = 16_100.0  # gpt-jax.ipynb cell 18 tqdm, 1x T4


def _fence(x) -> float:
    # fetching a scalar that depends on the timed computation: the host
    # cannot have the value before the device has finished
    return float(jax.device_get(x))


def _marginal_row(t_long, t_short, n_delta, prefix, batch=1):
    """Marginal-cost keys for a decode row: (T_long - T_short) / n_delta
    steps cancels whatever one program execution costs whatever its
    length (dispatch, launch, the fence's round trip). Units mirror the
    rows' unsuffixed keys exactly — tokens/sec counts DELIVERED tokens
    (batch rows per step), ms_per_token is per SCAN STEP — so suffixed
    and unsuffixed values differ only by the cancelled fixed cost. Records an error key instead of clamping when the two
    separately-timed runs cross (a clamped near-zero marginal would
    masquerade as an absurd tokens/sec)."""
    if t_long > t_short:
        step_s = (t_long - t_short) / n_delta
        return {
            f"{prefix}tokens_per_sec_marginal": round(batch / step_s),
            f"{prefix}ms_per_token_marginal": round(step_s * 1e3, 3),
        }
    return {f"{prefix}marginal_error":
            "t_long <= t_short; marginal unmeasurable"}


def _timed_windows(step, n_steps=40, n_windows=3, warmup=20):
    """Best-of-N windows of `n_steps` steps; step() must return a scalar-
    fence-able value. Returns (minimum, mean) per-step time over the
    windows; rows report the minimum as the steady-state figure."""
    for _ in range(warmup):
        out = step()
    _fence(out)
    windows = []
    for _ in range(n_windows):
        t0 = time.perf_counter()
        for _ in range(n_steps):
            out = step()
        _fence(out)
        windows.append(time.perf_counter() - t0)
    return min(windows) / n_steps, sum(windows) / (n_windows * n_steps)


def bench_gpt_train():
    from solvingpapers_tpu.metrics.mfu import (
        chip_peak_flops, transformer_flops_per_token,
    )
    from solvingpapers_tpu.models.gpt import GPT, GPTConfig
    from solvingpapers_tpu.train import OptimizerConfig, TrainConfig, Trainer

    # the framework's fast path: Pallas flash attention with in-kernel
    # dropout (same Bernoulli semantics as the reference's prob dropout)
    cfg = GPTConfig(
        vocab_size=65, block_size=256, dim=256, n_layers=8, n_heads=1,
        dropout=0.1, dtype="bfloat16", use_flash=True,
    )
    batch, scan_k = 128, 8
    tcfg = TrainConfig(
        steps=0, batch_size=batch, log_every=10_000, eval_every=0,
        scan_steps=scan_k,
        optimizer=OptimizerConfig(name="adamw", max_lr=1e-3, total_steps=1000),
    )
    from solvingpapers_tpu.data.batches import random_crop_batch

    trainer = Trainer(GPT(cfg), tcfg)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, size=1_000_000)
    )
    key = jax.random.key(0)

    @jax.jit
    def make_window(k):
        # all scan_k batches cropped on-device in ONE dispatch (same
        # random-crop distribution as lm_batch_iterator, which would issue
        # scan_k crop dispatches + a stack)
        x, y = random_crop_batch(toks, k, scan_k * batch, cfg.block_size)
        return {"x": x.reshape(scan_k, batch, cfg.block_size),
                "y": y.reshape(scan_k, batch, cfg.block_size)}

    counter = iter(range(1_000_000))

    def next_window():
        return make_window(jax.random.fold_in(key, next(counter)))

    state = trainer.init_state(jax.tree.map(lambda a: a[0], next_window()))
    trainer._build_steps()
    holder = {"state": state}

    def step():
        # one dispatch = scan_k on-device train steps (TrainConfig.scan_steps
        # — the engine's fit() path for small models); equality with
        # sequential stepping is pinned by test_scan_steps_window_equals_...
        holder["state"], metrics = trainer._train_step_scan(
            holder["state"], next_window()
        )
        return metrics["train_loss"]

    dt, dt_mean = _timed_windows(step, n_steps=10, n_windows=3, warmup=4)
    dt, dt_mean = dt / scan_k, dt_mean / scan_k
    tok_s = batch * cfg.block_size / dt
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    fpt = transformer_flops_per_token(
        n_params, cfg.n_layers, cfg.dim, cfg.block_size
    )
    return {
        "tokens_per_sec": round(tok_s, 1),
        "vs_baseline": round(tok_s / BASELINE_TOK_S, 3),
        "step_time_ms": round(1000 * dt, 2),
        "step_time_ms_mean": round(1000 * dt_mean, 2),
        "mfu": round(tok_s * fpt / chip_peak_flops(), 4),
        "n_params": int(n_params),
    }


def bench_350m_mfu():
    """The 342M llama3 single-chip MFU point (tools/scale_350m.py row):
    dim 1024, 24 layers, 16q/8kv heads, seq 1024, bf16, flash."""
    from solvingpapers_tpu.data.batches import lm_batch_iterator
    from solvingpapers_tpu.metrics.mfu import (
        chip_peak_flops, transformer_flops_per_token,
    )
    from solvingpapers_tpu.models.llama3 import Llama, LlamaConfig
    from solvingpapers_tpu.train import OptimizerConfig, TrainConfig, Trainer

    bs, seq = 8, 1024
    cfg = LlamaConfig(
        vocab_size=32_000, max_seq_len=seq, dim=1024, n_layers=24,
        n_heads=16, n_kv_heads=8, dropout=0.0, dtype="bfloat16",
        use_flash=True,
    )
    tcfg = TrainConfig(
        steps=0, batch_size=bs, log_every=10_000, eval_every=0,
        optimizer=OptimizerConfig(name="adamw", max_lr=3e-4, total_steps=100),
    )
    trainer = Trainer(Llama(cfg), tcfg)
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, size=500_000)
    it = lm_batch_iterator(toks, bs, seq, seed=0)
    b0 = next(it)
    state = trainer.init_state(b0)
    trainer._build_steps()
    holder = {"state": state}

    def step():
        holder["state"], metrics = trainer._train_step(
            holder["state"], next(it)
        )
        return metrics["train_loss"]

    dt, _ = _timed_windows(step, n_steps=10, n_windows=3, warmup=8)
    tok_s = bs * seq / dt
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    fpt = transformer_flops_per_token(n_params, cfg.n_layers, cfg.dim, seq)
    return {
        "tokens_per_sec": round(tok_s, 1),
        "step_time_ms": round(1000 * dt, 2),
        "mfu": round(tok_s * fpt / chip_peak_flops(), 4),
        "n_params": int(n_params),
    }


def bench_flash_mla_16k():
    """dsv3_long's core claim: a 16,384-token flagship train step on one
    chip via flash-MLA + remat (the dense path cannot even compile)."""
    from solvingpapers_tpu.data.batches import lm_batch_iterator
    from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3, DeepSeekV3Config
    from solvingpapers_tpu.train import OptimizerConfig, TrainConfig, Trainer
    from solvingpapers_tpu.train.objectives import dsv3_init_fn, dsv3_loss_fn

    seq = 16_384
    cfg = DeepSeekV3Config(
        vocab_size=32_000, block_size=seq, dtype="bfloat16", use_flash=True,
        remat=True, pe_scale=0.02, rope_dim=64, dropout=0.0, attn_dropout=0.0,
    )
    tcfg = TrainConfig(
        steps=0, batch_size=1, log_every=10_000, eval_every=0,
        optimizer=OptimizerConfig(name="adamw", max_lr=3e-4, total_steps=100),
    )
    trainer = Trainer(DeepSeekV3(cfg), tcfg, loss_fn=dsv3_loss_fn,
                      init_fn=dsv3_init_fn)
    toks = np.random.default_rng(2).integers(0, cfg.vocab_size, size=200_000)
    it = lm_batch_iterator(toks, 1, seq, seed=0)
    b0 = next(it)
    state = trainer.init_state(b0)
    trainer._build_steps()
    holder = {"state": state}

    def step():
        holder["state"], metrics = trainer._train_step(
            holder["state"], next(it)
        )
        return metrics["train_loss"]

    dt, _ = _timed_windows(step, n_steps=5, n_windows=2, warmup=3)
    return {
        "seq": seq,
        "step_time_ms": round(1000 * dt, 2),
        "tokens_per_sec": round(seq / dt, 1),
    }


def bench_decode():
    """Cached scan decode (llama3 d1024 L24) — the reference re-runs the
    full forward per token (SURVEY.md §3.4).

    Marginal timing — (T(256 new) - T(64 new)) / 192 — cancels the fixed
    cost of one program execution, so the *_marginal keys are the
    per-token cost alone. Raw walls stay in the row for audit."""
    from solvingpapers_tpu import ops
    from solvingpapers_tpu.infer import generate
    from solvingpapers_tpu.models.llama3 import Llama, LlamaConfig

    bs, prompt_len, new, new_short = 8, 128, 256, 64
    cfg = LlamaConfig(
        vocab_size=32_000, dim=1024, n_layers=24, n_heads=16, n_kv_heads=8,
        max_seq_len=prompt_len + new, dropout=0.0, dtype="bfloat16",
    )
    model = Llama(cfg)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (bs, prompt_len)),
        jnp.int32,
    )
    params = model.init({"params": jax.random.key(0)}, prompt)["params"]
    rng = jax.random.key(1)

    def timed(n_new):
        def run():
            return generate(model, params, prompt, rng, max_new_tokens=n_new,
                            sampler=ops.sample_greedy,
                            max_len=prompt_len + new)

        _fence(jnp.sum(run()[:, -1]))  # compile
        # min-of-5 walls
        return min(
            (lambda t0: (_fence(jnp.sum(run()[:, -1])),
                         time.perf_counter() - t0)[1])(time.perf_counter())
            for _ in range(5)
        )

    t_long = timed(new)
    t_short = timed(new_short)
    # `tokens_per_sec` is END-TO-END (the gated key); the marginal figure
    # rides alongside
    return {
        "bs": bs, "prompt": prompt_len, "new": new,
        "tokens_per_sec": round(bs * new / t_long),
        "ms_per_token": round(t_long / new * 1e3, 3),
        "wall_s_64": round(t_short, 3),
        "wall_s_256": round(t_long, 3),
        **_marginal_row(t_long, t_short, new - new_short, "", batch=bs),
    }


def bench_decode_16k_prefill():
    """Long-context generation: 16k-token prompt prefill through the
    end-aligned flash path into the MLA latent cache, then scan decode.

    Prefill and decode are each timed DIRECTLY as separate jitted programs
    over the same cache state (subtracting two independently measured
    end-to-end runs leaves a noise-dominated difference).

    Decode timing: a 32-token scan and a 128-token scan are both timed
    end to end; the MARGINAL cost — (T(128 tokens) - T(32 tokens)) / 96 —
    cancels the fixed cost of one program execution and is reported in
    the *_marginal keys, with both raw walls kept for audit. The
    unsuffixed keys are the end-to-end 32-token figures. The bs=8 row
    decodes over the same 16k-deep cache tiled eight times."""
    from solvingpapers_tpu import ops
    from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3, DeepSeekV3Config

    prompt_len, new, chunk = 16_384, 32, 2048
    new_long = 128
    # the cache/position budget must cover the LONG timing arm — 32 slots
    # would silently clamp the 128-token program's tail writes
    total = prompt_len + new_long
    cfg = DeepSeekV3Config(
        vocab_size=32_000, block_size=total, dtype="bfloat16",
        use_flash=True, pe_scale=0.02, rope_dim=64, dropout=0.0,
        attn_dropout=0.0,
    )
    model = DeepSeekV3(cfg)
    prompt = jnp.asarray(
        np.random.default_rng(3).integers(0, cfg.vocab_size, (1, prompt_len)),
        jnp.int32,
    )
    variables = model.init({"params": jax.random.key(2)},
                           jnp.zeros((1, 8), jnp.int32))

    @jax.jit
    def prefill(variables, prompt):
        caches = model.init_caches(1, total)
        logits = None
        for start in range(0, prompt_len, chunk):  # unrolled static chunks
            end = start + chunk
            tok = jax.lax.slice_in_dim(prompt, start, end, axis=1)
            positions = jnp.broadcast_to(jnp.arange(start, end), (1, chunk))
            logits, caches = model.apply(
                variables, tok, positions=positions, caches=caches,
                deterministic=True, attend_len=end,
            )
        return logits, caches

    @functools.partial(jax.jit, static_argnames=("length",))
    def decode(variables, first_tok, caches, rng, length=new):
        b = first_tok.shape[0]

        def body(carry, _):
            tok, pos, caches, rng = carry
            logits, caches = model.apply(
                variables, tok[:, None],
                positions=jnp.broadcast_to(pos[None, None], (b, 1)),
                caches=caches, deterministic=True,
            )
            rng, sub = jax.random.split(rng)
            nt = ops.sample_greedy(logits[:, -1], sub).astype(tok.dtype)
            return (nt, pos + 1, caches, rng), nt

        _, toks = jax.lax.scan(
            body, (first_tok, jnp.asarray(prompt_len), caches, rng), None,
            length=length,
        )
        return toks

    rng = jax.random.key(3)
    logits, caches = prefill(variables, prompt)  # compile
    _fence(jnp.sum(logits[:, -1]))
    prefill_s = min(
        (lambda t0: (
            _fence(jnp.sum(prefill(variables, prompt)[0][:, -1])),
            time.perf_counter() - t0,
        )[1])(time.perf_counter())
        for _ in range(3)
    )
    first_tok = ops.sample_greedy(logits[:, -1], rng).astype(prompt.dtype)

    def time_decode(tok, caches, length):
        _fence(jnp.sum(decode(variables, tok, caches, rng, length=length)))
        return min(
            (lambda t0: (
                _fence(jnp.sum(
                    decode(variables, tok, caches, rng, length=length)
                )),
                time.perf_counter() - t0,
            )[1])(time.perf_counter())
            for _ in range(3)
        )

    t_short = time_decode(first_tok, caches, new)
    t_long = time_decode(first_tok, caches, new_long)

    # bs=8 decode over the same 16k-deep cache (per-op overhead amortizes
    # across the batch; prompt processing replicated via tiled caches)
    bs = 8
    caches8 = jax.tree.map(lambda a: jnp.tile(a, (bs,) + (1,) * (a.ndim - 1)),
                           caches)
    tok8 = jnp.tile(first_tok, (bs,))
    t8_short = time_decode(tok8, caches8, new)
    t8_long = time_decode(tok8, caches8, new_long)

    # `decode_tokens_per_sec` is END-TO-END over the 32-token scan (the
    # gated key); the marginal keys carry the per-token figure
    return {
        "prompt": prompt_len, "new": new,
        "prefill_s": round(prefill_s, 3),
        "prefill_tokens_per_sec": round(prompt_len / prefill_s),
        "decode_tokens_per_sec": round(new / t_short),
        "decode_ms_per_token": round(t_short / new * 1e3, 3),
        "decode_wall_s_32": round(t_short, 3),
        "decode_wall_s_128": round(t_long, 3),
        **_marginal_row(t_long, t_short, new_long - new, "decode_"),
        **_marginal_row(t8_long, t8_short, new_long - new, "decode_bs8_",
                        batch=bs),
    }


def bench_speculative_decode():
    """MTP self-speculative decoding vs plain greedy decode on a briefly
    trained dsv3+MTP model (acceptance tracks model quality, so random
    params would only measure the fallback path). Output equality is
    pinned by tests/test_speculative.py; this row records the measured
    acceptance and the wall-clock ratio at the flagship's dims — where
    per-forward latency dominates and the forward savings become wall
    time (at toy dims decode is op-count-bound and the extra MTP-head
    pass eats the win: dim 256/L4 measured 0.78x)."""
    from solvingpapers_tpu import ops
    from solvingpapers_tpu.data.batches import lm_batch_iterator
    from solvingpapers_tpu.infer import generate, generate_speculative
    from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3, DeepSeekV3Config
    from solvingpapers_tpu.train import OptimizerConfig, TrainConfig, Trainer
    from solvingpapers_tpu.train.objectives import dsv3_init_fn, dsv3_loss_fn

    cfg = DeepSeekV3Config(
        vocab_size=64, block_size=512, dim=512, n_layers=6, n_heads=8,
        latent_dim=64, rope_dim=32, pe_scale=0.02, n_experts=8,
        top_experts=2, dropout=0.0, attn_dropout=0.0, mtp_heads=2,
        dtype="bfloat16",
    )
    model = DeepSeekV3(cfg)
    # word-structured synthetic text: predictable enough for real
    # acceptance after a short burst, not a degenerate loop
    from solvingpapers_tpu.data.synthetic import synthetic_text

    text = synthetic_text(400_000, seed=5)
    vocab = sorted(set(text))[: cfg.vocab_size]
    lut = {c: i for i, c in enumerate(vocab)}
    toks = np.asarray([lut.get(c, 0) for c in text], np.int32)
    tcfg = TrainConfig(
        steps=400, batch_size=32, log_every=10_000, eval_every=0,
        optimizer=OptimizerConfig(max_lr=1e-3, warmup_steps=40,
                                  total_steps=400),
    )
    trainer = Trainer(model, tcfg, loss_fn=dsv3_loss_fn, init_fn=dsv3_init_fn)
    state = trainer.fit(lm_batch_iterator(toks, 32, 256, seed=0))
    # keep params device-resident: a device_get here would re-ship the
    # whole model host->device on every timed call
    params = state.params
    extra = {"moe_state": state.model_state["moe_state"]}

    prompt = jnp.asarray(toks[:64][None, :], jnp.int32)
    new = 128
    rng = jax.random.key(0)

    def plain():
        return generate(model, params, prompt, rng, max_new_tokens=new,
                        sampler=ops.sample_greedy, extra_variables=extra,
                        max_len=prompt.shape[1] + new + 2)

    def spec(n_drafts=1):
        return generate_speculative(model, params, prompt,
                                    max_new_tokens=new,
                                    extra_variables=extra,
                                    n_drafts=n_drafts)

    _fence(jnp.sum(plain()[:, -1]))
    plain_s = min(
        (lambda t0: (_fence(jnp.sum(plain()[:, -1])),
                     time.perf_counter() - t0)[1])(time.perf_counter())
        for _ in range(3)
    )

    def time_spec(n_drafts):
        out, stats = spec(n_drafts)
        _fence(jnp.sum(out[:, -1]))
        s = min(
            (lambda t0: (_fence(jnp.sum(spec(n_drafts)[0][:, -1])),
                         time.perf_counter() - t0)[1])(time.perf_counter())
            for _ in range(3)
        )
        f = int(jax.device_get(stats["forwards"]))
        a = int(jax.device_get(stats["accepted"]))
        return s, f, a

    spec_s, f, a = time_spec(1)
    # chained 2-head drafts (round 5): both trained MTP heads draft, cap 3
    # tokens/forward — must push tokens/forward past the 1-draft cap of 2
    spec2_s, f2, a2 = time_spec(2)
    return {
        "new_tokens": new,
        "forwards": f,
        "accepted": a,
        "tokens_per_forward": round((f + a) / max(f, 1), 3),
        "plain_ms_per_token": round(plain_s / new * 1e3, 3),
        "spec_ms_per_token": round(spec_s / new * 1e3, 3),
        "wall_speedup": round(plain_s / spec_s, 3),
        "draft2_forwards": f2,
        "draft2_accepted": a2,
        "draft2_tokens_per_forward": round((f2 + a2) / max(f2, 1), 3),
        "draft2_ms_per_token": round(spec2_s / new * 1e3, 3),
        "draft2_wall_speedup": round(plain_s / spec2_s, 3),
    }


def bench_dropout_identity():
    """In-kernel dropout backward verification (hardware PRNG): out is
    linear in v with a fixed seed, so <loss(v+u) - loss(v)> must equal
    <u, grad_v loss> EXACTLY when the backward kernels regenerate the
    forward's masks (tests/test_flash_dropout_tpu.py's identity)."""
    from solvingpapers_tpu.kernels import flash_attention

    key = jax.random.key(7)
    kq, kk, kv, kw, ku = jax.random.split(key, 5)
    q = jax.random.normal(kq, (1, 256, 2, 32))
    k = jax.random.normal(kk, (1, 256, 2, 32))
    v = jax.random.normal(kv, (1, 256, 2, 32))
    w = jax.random.normal(kw, q.shape)
    u = jax.random.normal(ku, v.shape)

    def loss(v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, dropout_rate=0.3,
                            dropout_seed=11) * w
        )

    gv = jax.grad(loss)(v)
    lhs = _fence(loss(v + u)) - _fence(loss(v))
    rhs = _fence(jnp.sum(u * gv))
    rel = abs(lhs - rhs) / max(abs(rhs), 1e-9)
    return {"rel_err": round(rel, 5), "pass": bool(rel < 2e-2)}


# Per-row keys compared against the prior round's record (higher = better).
_GATED_KEYS = ("tokens_per_sec", "prefill_tokens_per_sec",
               "decode_tokens_per_sec", "mfu")
_REGRESSION_TOL = 0.03  # flag drops > 3%, like tools/parity_suite.py's gates


def _load_prior_scorecard():
    """Latest BENCH_r{N}.json next to this file -> (round_n, {name: row}).

    The driver wraps our JSON line under a "parsed" key; accept both the
    wrapped and the raw layout.
    """
    import glob
    import os
    import re

    here = os.path.dirname(os.path.abspath(__file__))
    best_n, best = -1, None
    for path in glob.glob(os.path.join(here, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                obj = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        n = int(m.group(1))
        if n > best_n:
            best_n, best = n, obj.get("parsed", obj)
    if not isinstance(best, dict):
        return -1, {}
    rows = best.get("scorecard", [])
    return best_n, {r["name"]: r for r in rows if isinstance(r, dict) and "name" in r}


def _gate_vs_prior(rows):
    """Annotate each row with vs_prior ratios and collect >3% regressions
    against the latest BENCH_r*.json record, when one exists."""
    prior_n, prior = _load_prior_scorecard()
    regressions = []
    for row in rows:
        ref = prior.get(row.get("name"))
        if not ref:
            continue
        vs = {}
        for key in _GATED_KEYS:
            cur, old = row.get(key), ref.get(key)
            if not (isinstance(cur, (int, float)) and isinstance(old, (int, float))):
                continue
            if old <= 0 or not np.isfinite(old) or old > 1e9:
                # prior record invalid (a non-positive or absurd rate)
                vs[key] = {"prior": old, "note": "prior value invalid; skipped"}
                continue
            ratio = cur / old
            vs[key] = round(ratio, 4)
            if ratio < 1.0 - _REGRESSION_TOL:
                regressions.append(
                    {"row": row["name"], "key": key, "prior": old,
                     "current": cur, "ratio": round(ratio, 4)}
                )
        if vs:
            row["vs_prior"] = vs
    return prior_n, regressions


def _require_tpu() -> None:
    """Every row is a device measurement: refuse any other platform
    rather than time the CPU backend or the Pallas interpreter."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the TPU; JAX reports platform "
            f"{dev.platform!r} ({dev.device_kind}) — refusing to run"
        )


def main() -> int:
    from solvingpapers_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    _require_tpu()
    rows = []
    primary = None
    for name, fn in (
        ("gpt_charlm_train", bench_gpt_train),
        ("llama3_350m_mfu", bench_350m_mfu),
        ("flash_mla_16k_step", bench_flash_mla_16k),
        ("decode_llama3_350m", bench_decode),
        ("decode_dsv3_16k_prefill", bench_decode_16k_prefill),
        ("mtp_speculative_decode", bench_speculative_decode),
        ("flash_dropout_linearity", bench_dropout_identity),
    ):
        try:
            res = {"name": name, **fn()}
        except Exception as e:  # isolate rows; the exit code reports it
            res = {"name": name, "error": repr(e)[:300]}
        rows.append(res)
        if name == "gpt_charlm_train":
            primary = res

    prior_round, regressions = _gate_vs_prior(rows)
    out = {
        "metric": "gpt_charlm_train_tokens_per_sec",
        "value": primary.get("tokens_per_sec", 0.0),
        "unit": "tokens/sec",
        "vs_baseline": primary.get("vs_baseline", 0.0),
        "detail": {
            "config": "gpt-jax.ipynb cell 8 (bs128 x block256, dim256, L8)",
            "baseline": "16.1k tok/s on 1x T4 (reference cell 18)",
            "device": str(jax.devices()[0].device_kind),
        },
        "prior_round": prior_round,
        "regressions_vs_prior": regressions,
        "scorecard": rows,
    }
    print(json.dumps(out))
    return 1 if any("error" in r for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
