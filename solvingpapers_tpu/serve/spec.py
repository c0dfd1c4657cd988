"""Speculative decoding for the serving engine: per-slot draft-and-verify
with lossless rejection sampling.

The serve engine's plain decode block advances every slot ONE token per
scan iteration — each iteration is a full vmapped model forward whose
cost, on the dispatch-bound serving path, is dominated by per-step
overhead rather than by the single token it yields. Speculative decoding
(Leviathan et al., "Fast Inference from Transformers via Speculative
Decoding"; Chen et al., "Accelerating LLM Decoding with Speculative
Sampling") turns each iteration into a DRAFT-AND-VERIFY round: a cheap
drafter proposes up to `k` next tokens per slot, one chunked forward
computes the model's distributions at all `1 + k` positions at once, and
the drafts are verified against those distributions — committing between
1 and ``k + 1`` tokens per forward with the OUTPUT DISTRIBUTION provably
unchanged:

* greedy slots verify by exact argmax match — the committed stream is
  token-identical to non-speculative greedy decode by construction
  (every committed token IS the model's argmax given its prefix);
* stochastic slots use modified rejection sampling against the SAME
  truncated per-request distribution `fused_sample` draws from: a draft
  `d` (a deterministic proposal, q = delta_d) is accepted with
  probability ``p(d)``; on rejection the token is redrawn from the
  residual ``p`` with `d` removed and renormalized, and when every draft
  survives a bonus token is drawn from the chunk's last row. Summing the
  two branches gives exactly ``p`` per committed position — lossless
  (`tests/test_spec.py` pins greedy byte-exactness and the stochastic
  empirical distribution).

Two drafters share the verify machinery (`ServeConfig.speculative`):

* ``"ngram"`` — a model-free prompt-lookup self-drafter (`ngram_drafts`):
  find the most recent earlier occurrence of the stream's trailing
  n-gram in its own history (prompt + committed tokens) and propose the
  tokens that followed it. Zero extra parameters, works for every
  decoder family, and runs INSIDE the jitted decode program over a
  history buffer that rides the engine's packed control transfer — so
  one program call runs `spec_rounds` draft-verify rounds back to back,
  amortizing host dispatch exactly like the plain block's scan.
* ``"mtp"`` — the DeepSeek-V3 multi-token-prediction heads
  (`infer/speculative.py` mechanics, vmapped over the slot axis): each
  round's chunk forward returns hidden states, the MTP head(s) advance
  their own per-slot latent-cache lanes and draft the next round's
  tokens in-program. deepseekv3 family, lane pool.

Draft length `k` is traced PER-SLOT (`avail`): a slot whose lookup found
nothing, a grammar-constrained slot (stale-mask contract: one token per
block), and a free lane all ride the same compiled program with zero
drafts — mixed speculative/non-speculative batches share ONE decode
program, which tests pin via the jit cache.

`SpecController` is the host-side adaptive policy: speculation helps
exactly when drafts get accepted, and the chunked forward is not free
(the model runs ``1 + k`` positions per round), so a workload whose
drafts keep rejecting — adversarial random-token traffic — would pay the
chunk width for nothing. The controller tracks an acceptance EMA per
engine and drops the engine back to the plain block program while the
EMA is below `spec_min_rate`, probing speculation again every
`spec_probe_every` steps — bounding the zero-acceptance overhead to the
occasional probe (not measured on the chip).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from solvingpapers_tpu import ops
from solvingpapers_tpu.serve.sampling import (
    PackedSampling,
    capped_support,
    request_key,
)

DRAFTERS = ("ngram", "mtp")


# ---------------------------------------------------------------- drafting


def ngram_drafts(hist, length, *, k: int, nmax: int = 3):
    """Prompt-lookup drafts for ONE slot (traced; vmap over the slot axis).

    `hist` is the slot's (H,) token history — prompt plus every committed
    token, garbage beyond `length` — and `length` the live token count.
    Tries tail n-grams from `nmax` down to 1: the first n whose trailing
    n-gram ``hist[length-n:length]`` recurs earlier in the history wins,
    and the proposal is the (up to) `k` tokens that FOLLOWED the most
    recent earlier occurrence. Returns ``(drafts (k,) i32, avail)`` with
    ``avail`` the usable draft count (0 = nothing to propose — the slot
    runs the round draft-free, committing exactly one token).

    Matches must end strictly before the final n-gram (``j + n <=
    length - 1``), so the trivial self-match never proposes, and drafts
    are clipped to committed history (a proposal never reads garbage).
    """
    h = jnp.asarray(hist)
    big = h.shape[0]
    idx = jnp.arange(big)
    best_start = jnp.int32(0)
    best_n = jnp.int32(0)
    found = jnp.bool_(False)
    # longest-n-gram-first fallback chain: a hit at larger n is a more
    # specific context and predicts the continuation better; ties at the
    # same n break toward the MOST RECENT occurrence (locality)
    for n in range(nmax, 0, -1):
        # rolling equality: window j matches iff h[j + t] == key[t] for
        # every t, with key = h[length - n : length]
        match = jnp.ones(big, bool)
        for t in range(n):
            key_t = h[jnp.clip(length - n + t, 0, big - 1)]
            match = match & (jnp.roll(h, -t) == key_t)
        match = match & (idx + n <= length - 1)
        j = jnp.max(jnp.where(match, idx, -1))
        hit = (j >= 0) & (length > n)
        take = hit & ~found
        best_start = jnp.where(take, j + n, best_start)
        best_n = jnp.where(take, n, best_n)
        found = found | hit
    start = jnp.clip(best_start, 0, big - 1)
    # gather k tokens from `start`; clip per-index so the slice never
    # wraps or reads past the buffer (avail masks the short tail anyway)
    drafts = h[jnp.clip(start + jnp.arange(k), 0, big - 1)]
    avail = jnp.where(found, jnp.clip(length - start, 0, k), 0)
    return drafts.astype(jnp.int32), avail.astype(jnp.int32)


# ------------------------------------------------------------ verification


def _fold_all(keys, tag):
    """fold_in over an arbitrary-rank array of typed keys."""
    flat = keys.reshape(-1)
    folded = jax.vmap(lambda kk: jax.random.fold_in(kk, tag))(flat)
    return folded.reshape(keys.shape)


def spec_verify(logits, drafts, avail, packed: PackedSampling, keys, *,
                cap: int, allow=None):
    """Verify one round of drafts and emit the committed-token matrix.

    ``logits`` is (S, L, V) with ``L = k + 1`` — row i is the model's
    distribution for the i-th position of the commit window (row j
    verifies draft j; row ``a`` supplies the correction/bonus draw).
    ``drafts`` (S, k) and ``avail`` (S,) come from the drafter (avail 0
    = non-speculative slot); ``keys`` (S, L) are the per-position
    sampling keys (chain: (seed, committed index) — ONE index per
    committed token, same contract as the plain path). Returns
    ``(out (S, L) i32, commits (S,) i32, logprobs (S, L) f32)``: the
    host keeps ``out[s, :commits[s]]``.

    Greedy slots: draft j accepted iff it equals row j's argmax; every
    committed token is a row argmax — byte-identical to non-speculative
    greedy decode. Stochastic slots: draft j accepted with probability
    ``p_j(d_j)`` under the request's truncated distribution (the same
    `capped_support` pipeline `fused_sample` uses); the cut position
    redraws from the residual (draft removed, renormalized) on a
    rejection or from the full row when every draft survived. Both
    branches compose to exactly ``p_j`` per committed position —
    lossless by the Leviathan/Chen argument specialized to a
    deterministic proposal (q = delta_draft: accept prob
    ``min(1, p/q) = p``, residual ``norm(max(0, p - q)) = p`` minus the
    draft).

    `allow` (S, cap) constrains ROW 0 ONLY of constrained slots (the
    grammar mask is stale after one draw; such slots ride with
    avail = 0, so row 0 is their single commit).
    """
    s_n, big_l, vocab = logits.shape
    k = big_l - 1
    cap = min(cap, vocab)
    logits32 = logits.astype(jnp.float32)
    greedy = packed.temperature <= 0.0
    within = jnp.arange(k)[None, :] < avail[:, None]
    greedy_tok = jnp.argmax(logits32, axis=-1).astype(jnp.int32)  # (S, L)
    if allow is not None:
        if allow.shape[-1] > cap:
            allow = allow[:, :cap]
        elif allow.shape[-1] < cap:
            allow = jnp.pad(allow, ((0, 0), (0, cap - allow.shape[-1])),
                            constant_values=-1)
        constrained = allow[:, 0] >= 0

    def _exact():
        """All-greedy, unconstrained: argmax rows + exact-match verify —
        no top_k, no masking, no rng (the cost of the plain greedy
        sampler, which is what keeps all-greedy serving fast)."""
        acc = (greedy_tok[:, :k] == drafts) & within
        commits = jnp.cumprod(acc.astype(jnp.int32), axis=1).sum(1) + 1
        return greedy_tok, commits

    def _mixed():
        flat = logits32.reshape(s_n * big_l, vocab)
        knobs = PackedSampling(
            temperature=jnp.repeat(packed.temperature, big_l),
            top_p=jnp.repeat(packed.top_p, big_l),
            min_p=jnp.repeat(packed.min_p, big_l),
            top_k=jnp.repeat(packed.top_k, big_l),
            need_lp=jnp.repeat(packed.need_lp, big_l),
        )
        allow_rows = None
        if allow is not None:
            # the grammar mask constrains row 0 only: rows >= 1 of a
            # constrained slot are discarded overshoot (avail = 0)
            allow_rows = jnp.full((s_n, big_l, cap), -1, jnp.int32)
            allow_rows = allow_rows.at[:, 0, :].set(allow)
            allow_rows = allow_rows.reshape(s_n * big_l, cap)
        masked, top_idx = capped_support(flat, knobs, cap=cap,
                                         allow=allow_rows)
        masked = masked.reshape(s_n, big_l, cap)
        top_idx = top_idx.reshape(s_n, big_l, cap)
        g_tok = greedy_tok
        if allow is not None:
            # greedy under a constraint = argmax over the allowed domain
            dom = jnp.take_along_axis(
                top_idx[:, 0], jnp.argmax(masked[:, 0], -1)[:, None], axis=-1
            )[:, 0]
            g_tok = g_tok.at[:, 0].set(
                jnp.where(constrained, dom, g_tok[:, 0]))
        probs = jax.nn.softmax(masked, axis=-1)  # -inf rows -> 0 mass
        d_hit = top_idx[:, :k, :] == drafts[:, :, None]
        d_prob = jnp.sum(jnp.where(d_hit, probs[:, :k], 0.0), axis=-1)
        u = jax.vmap(jax.vmap(jax.random.uniform))(
            _fold_all(keys[:, :k], 1))
        acc_st = u < d_prob
        acc_gr = g_tok[:, :k] == drafts
        acc = jnp.where(greedy[:, None], acc_gr, acc_st) & within
        a = jnp.cumprod(acc.astype(jnp.int32), axis=1).sum(1)  # (S,)
        commits = a + 1
        # cut-row draws: residual (draft zeroed, renormalized) after a
        # rejection, the full row after a clean sweep. Computed for every
        # row, selected at the cut — rows past the cut are discarded.
        resid = jnp.where(d_hit, -jnp.inf, masked[:, :k])
        cat_keys = _fold_all(keys, 2)
        cat = jax.vmap(jax.vmap(
            lambda row, kk: jax.random.categorical(kk, row)
        ))
        full_sel = cat(masked, cat_keys)                       # (S, L)
        resid_sel = cat(resid, cat_keys[:, :k])                # (S, k)
        full_tok = jnp.take_along_axis(top_idx, full_sel[..., None],
                                       axis=-1)[..., 0]
        resid_tok = jnp.take_along_axis(top_idx[:, :k],
                                        resid_sel[..., None], axis=-1)[..., 0]
        resid_tok = jnp.concatenate(
            [resid_tok, full_tok[:, -1:]], axis=1)             # row k: full
        rows = jnp.arange(big_l)[None, :]
        at_cut = rows == a[:, None]
        rejected = at_cut & (a < avail)[:, None]
        drafts_l = jnp.concatenate(
            [drafts, jnp.zeros((s_n, 1), drafts.dtype)], axis=1)
        stoch = jnp.where(rows < a[:, None], drafts_l,
                          jnp.where(rejected, resid_tok, full_tok))
        out = jnp.where(greedy[:, None], g_tok, stoch.astype(jnp.int32))
        return out, commits

    fast = jnp.all(greedy)
    if allow is not None:
        fast = fast & ~jnp.any(constrained)
    out, commits = jax.lax.cond(fast, _exact, _mixed)

    def _logprobs():
        chosen = jnp.take_along_axis(logits32, out[..., None],
                                     axis=-1)[..., 0]
        lse = jax.scipy.special.logsumexp(logits32, axis=-1)
        return chosen - lse

    logprobs = jax.lax.cond(
        jnp.any(packed.need_lp > 0), _logprobs,
        lambda: jnp.zeros(out.shape, jnp.float32),
    )
    return out, commits, logprobs


def round_keys(rng, step_tag, seeds, samp_cnt, big_l):
    """(S, L) per-position sampling keys for one draft-verify round:
    position i of slot s folds ``samp_cnt[s] + i`` — one sample index
    per COMMITTED token, so a seeded request's chain depends only on
    (seed, committed index), exactly like the non-speculative path."""
    s_n = seeds.shape[0]
    slots = jnp.arange(s_n, dtype=jnp.int32)

    def one(slot, seed, base):
        return jax.vmap(
            lambda i: request_key(rng, step_tag, slot, seed, base + i)
        )(jnp.arange(big_l, dtype=jnp.int32))

    return jax.vmap(one)(slots, seeds, samp_cnt)


# ------------------------------------------------------- adaptive control


class SpecController:
    """Host-side adaptive speculation policy (one per engine).

    Speculation pays for itself only while drafts get accepted: each
    round forwards ``1 + k`` positions to commit ``1 + accepted``, so a
    workload whose drafts keep rejecting must NOT pay the full chunked
    block every step. The controller runs a three-state loop:

    * ``probe`` (the cold-start state): the next spec step runs only a
      couple of draft-verify rounds — a cheap acceptance measurement,
      not a full block. Acceptance at or above `min_rate` (accepted
      drafts per round) promotes to ``full``; below it the engine
      drops to plain blocks for a hold.
    * ``full``: full `spec_rounds` blocks, with an EMA of per-round
      acceptance; the EMA sinking under `min_rate` demotes to a hold.
    * hold: plain block decoding for `probe_every` steps, DOUBLING on
      every failed probe (capped at ``probe_every x max_hold_mult``) —
      exponential backoff bounds the adversarial overhead to a few
      cheap probes over the whole run, while a workload that turns
      predictable again is picked up at the next probe.

    The acceptance EMA resets on demotion, so a probe is judged on its
    own evidence, not on the stale history that caused the hold.
    """

    def __init__(self, min_rate: float = 1.0, probe_every: int = 8,
                 decay: float = 0.7, max_hold_mult: int = 16):
        if probe_every < 1:
            raise ValueError(f"probe_every must be >= 1, got {probe_every}")
        self.min_rate = min_rate
        self.probe_every = probe_every
        self.decay = decay
        self.max_hold = probe_every * max_hold_mult
        self.ema: float | None = None
        self._mode = "probe"  # cold start: measure before committing
        self._hold = 0
        self._hold_len = probe_every
        self.fallback_steps = 0
        self.probes = 0

    def decide(self) -> str:
        """Called once per decode step: "full" = full spec block,
        "probe" = short measurement block, "off" = plain block."""
        if self._hold > 0:
            self._hold -= 1
            self.fallback_steps += 1
            return "off"
        if self._mode == "probe":
            self.probes += 1
            return "probe"
        return "full"

    def hold(self, steps: int) -> None:
        """External hold (the degradation ladder's rung 2): force plain
        blocks for at least `steps` upcoming decode steps WITHOUT
        touching the acceptance EMA or the backoff schedule — when the
        ladder steps back down, the controller resumes exactly the
        adaptive state it held before the squeeze."""
        if steps > 0:
            self._hold = max(self._hold, steps)

    def observe(self, accepted: int, rounds: int) -> None:
        """Feed one spec call's outcome (accepted drafts over `rounds`
        draft-verify rounds across the drafting slots)."""
        if rounds <= 0:
            return
        rate = accepted / rounds
        self.ema = rate if self.ema is None else (
            self.decay * self.ema + (1.0 - self.decay) * rate)
        if self.ema >= self.min_rate:
            self._mode = "full"
            self._hold_len = self.probe_every  # recovered: reset backoff
        else:
            self._mode = "probe"
            self._hold = self._hold_len
            self._hold_len = min(self._hold_len * 2, self.max_hold)
            self.ema = None  # the next probe is judged fresh

    def stats(self) -> dict:
        return {
            "acceptance_ema": (round(self.ema, 4)
                               if self.ema is not None else None),
            "mode": "hold" if self._hold > 0 else self._mode,
            "fallback_steps": self.fallback_steps,
            "probes": self.probes,
        }
