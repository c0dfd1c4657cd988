"""Speculative decoding subsystem (solvingpapers_tpu/serve/spec.py +
engine wiring).

The contract under test: speculation changes how many forwards a stream
takes, NEVER its content or distribution —

* greedy streams with speculation enabled are byte-identical to spec-off
  serving and to one-shot `generate`, for every decoder family, on both
  pool layouts, including across paged-pool preemption/recompute;
* stochastic slots use rejection sampling against `fused_sample`'s
  truncated distributions: the committed-token marginal matches the
  plain sampler's empirical distribution (fixed-seed statistical test),
  and a seeded stream replays identically run-to-run;
* mixed spec/non-spec batches (greedy + stochastic + grammar) share ONE
  compiled speculative decode program (jit-cache pinned);
* the scheduler's anti-starvation clock counts DELIVERED tokens, so a
  high-acceptance slot cannot starve the wait budget.
"""

import dataclasses as dc
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from solvingpapers_tpu.infer import generate
from solvingpapers_tpu.serve import SamplingParams, ServeConfig, ServeEngine
from solvingpapers_tpu.serve.engine import _spec_decode_program
from solvingpapers_tpu.serve.sampling import PackedSampling, fused_sample
from solvingpapers_tpu.serve.scheduler import FIFOScheduler, Request
from solvingpapers_tpu.serve.spec import (
    SpecController,
    ngram_drafts,
    spec_verify,
)


# builders are deterministic (fixed init keys) and everything downstream
# treats params as read-only, so each family's model/params build once
# per session — engine pools copy out of init_caches, never into params
@functools.lru_cache(maxsize=None)
def _gpt():
    from solvingpapers_tpu.models.gpt import GPT, GPTConfig

    model = GPT(GPTConfig(vocab_size=64, block_size=64, dim=32, n_layers=2,
                          n_heads=2, dropout=0.0))
    params = model.init({"params": jax.random.key(0)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, None, 64


@functools.lru_cache(maxsize=None)
def _llama3():
    from solvingpapers_tpu.models.llama3 import Llama, LlamaConfig

    model = Llama(LlamaConfig(vocab_size=64, max_seq_len=64, dim=32,
                              n_layers=2, n_heads=4, n_kv_heads=2,
                              dropout=0.0))
    params = model.init({"params": jax.random.key(1)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, None, 64


@functools.lru_cache(maxsize=None)
def _gemma():
    from solvingpapers_tpu.models.gemma import Gemma, GemmaConfig

    model = Gemma(GemmaConfig(vocab_size=64, max_seq_len=64, dim=32,
                              n_layers=2, n_heads=4, n_kv_heads=2,
                              dropout=0.0))
    params = model.init({"params": jax.random.key(2)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, None, 64


@functools.lru_cache(maxsize=None)
def _dsv3(mtp_heads=0):
    from solvingpapers_tpu.models.deepseekv3 import (
        DeepSeekV3,
        DeepSeekV3Config,
    )

    # 1 layer / 2 experts: the smallest config that still exercises the
    # family's serving particulars (latent-cache lanes, moe_state extra
    # variables, MTP heads) — dsv3 traces dominate this module's compile
    # bill, and the spec contract is model-size-independent
    model = DeepSeekV3(DeepSeekV3Config(
        vocab_size=64, block_size=96, dim=32, n_layers=1, n_heads=2,
        latent_dim=8, rope_dim=8, pe_scale=0.02, n_experts=2,
        top_experts=2, dropout=0.0, attn_dropout=0.0, mtp_heads=mtp_heads,
    ))
    variables = model.init(
        {"params": jax.random.key(3)}, jnp.zeros((1, 8), jnp.int32),
        **({"return_mtp": True} if mtp_heads else {}),
    )
    extra = {"moe_state": variables["moe_state"]}
    return model, variables["params"], extra, 64


_FAMILIES = {"gpt": _gpt, "llama3": _llama3, "gemma": _gemma,
             "deepseekv3": _dsv3}


def _prompts(n, seed=0, lo=5, hi=16, vocab=64):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


#: memoized one-shot `generate` references. The _FAMILIES builders are
#: deterministic (fixed init keys), so two tests asking for the same
#: (family, prompt, length) reference — e.g. the lane and paged arms of
#: the exactness matrix — would recompute an identical stream; passing
#: `cache_key=family` skips the duplicate generate compile + run, which
#: is most of this module's tier-1 cost.
_REF_CACHE: dict = {}


def _ref(model, params, extra, prompt, new, cache_key=None):
    if cache_key is not None:
        k = (cache_key, np.asarray(prompt, np.int32).tobytes(), new)
        if k in _REF_CACHE:
            return _REF_CACHE[k]
    out = generate(model, params, jnp.asarray(prompt)[None, :],
                   jax.random.key(0), max_new_tokens=new,
                   extra_variables=extra)
    toks = np.asarray(out[0, len(prompt):]).tolist()
    if cache_key is not None:
        _REF_CACHE[k] = toks
    return toks


# ------------------------------------------------------ greedy exactness


@pytest.mark.parametrize("paged", [False, True], ids=["lane", "paged"])
@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_spec_greedy_streams_exact(family, paged):
    """Greedy spec-on streams == spec-off streams == one-shot generate,
    for all four families on both pools — speculation must be invisible
    in the tokens (including the all-reject path: untrained models
    rarely accept, which is the hard case for the commit bookkeeping)."""
    model, params, extra, vocab = _FAMILIES[family]()
    prompts = _prompts(4, seed=4, vocab=vocab)

    def run(spec):
        # spec_rounds=2 == the controller's probe length, so probe and
        # full blocks share ONE compiled program per arm (the probe!=full
        # two-program path is covered once, by the S=2/max_len=64
        # cluster below)
        kw = dict(speculative="ngram", spec_k=4, spec_rounds=2) if spec \
            else {}
        if paged:
            kw.update(paged=True, page_size=8)
        eng = ServeEngine(model, params, ServeConfig(
            n_slots=2, max_len=48, decode_block=4, bucket=8, **kw,
        ), extra_variables=extra)
        hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        eng.run()
        return eng, hs

    eng_on, on = run(True)
    # one-shot generate IS the canonical reference (spec-off serving ==
    # generate is pinned by tests/test_serve.py); compiling a second
    # spec-off engine per family x pool would double this matrix's cost,
    # so the direct spec-off comparison runs once, on the cheapest combo
    if family == "gpt" and not paged:
        _, off = run(False)
        for i in range(len(prompts)):
            assert on[i].tokens == off[i].tokens, "spec-on != spec-off"
    for i, p in enumerate(prompts):
        ref = _ref(model, params, extra, p, 10, cache_key=family)
        assert on[i].tokens == ref, (
            f"{family}/{'paged' if paged else 'lane'} spec-on diverged: "
            f"{on[i].tokens} != {ref}"
        )
    snap = eng_on.metrics.snapshot()
    assert "serve/spec_acceptance_rate" in snap
    assert snap["serve/spec_tokens_per_step"] > 0


def test_spec_greedy_exact_across_paged_preemption():
    """A page budget too small for the offered load forces
    preempt-and-recompute mid-stream; with speculation on, resumed
    streams must still be byte-exact (the resume prefill + the spec
    block's accepted-window scatter compose losslessly)."""
    model, params, extra, vocab = _gpt()
    prompts = _prompts(4, seed=9, lo=8, hi=12, vocab=vocab)
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=4, max_len=48, decode_block=4, bucket=8,
        paged=True, page_size=8, page_budget=12,
        speculative="ngram", spec_k=4, spec_rounds=2,
    ))
    hs = [eng.submit(p, max_new_tokens=16) for p in prompts]
    eng.run()
    assert all(h.done for h in hs)
    for p, h in zip(prompts, hs):
        assert h.tokens == _ref(model, params, None, p, 16)
    assert eng.metrics.preemptions > 0, (
        "workload never preempted — shrink page_budget so the test "
        "exercises recompute under speculation"
    )


@pytest.mark.parametrize("paged", [False, True], ids=["lane", "paged"])
def test_spec_composes_with_prefix_cache(paged):
    """Speculation + the radix prefix cache (splice on the lane pool,
    zero-copy page sharing on the paged pool): shared-stem greedy
    streams stay byte-exact vs a cache-off spec-off engine, and the
    cache still hits."""
    model, params, _, vocab = _gpt()
    rng = np.random.default_rng(17)
    stem = rng.integers(0, vocab, size=16).astype(np.int32)
    prompts = [np.concatenate([stem, rng.integers(0, vocab, size=6)
                               .astype(np.int32)]) for _ in range(4)]
    kw = dict(paged=True, page_size=8) if paged else {}
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=48, decode_block=4, bucket=8,
        prefix_cache=True, prefix_page=8,
        speculative="ngram", spec_k=4, spec_rounds=2, **kw,
    ))
    hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run()
    for p, h in zip(prompts, hs):
        assert h.tokens == _ref(model, params, None, p, 10,
                                cache_key="gpt-prefix")
    assert eng.metrics.prefix_hits > 0, "stems never hit the cache"


def test_spec_eos_mid_chunk_truncates_exactly():
    """An EOS committed mid-chunk ends the stream at the EOS (kept),
    discarding the chunk's overshoot — same contract as the plain
    block's mid-block EOS."""
    model, params, _, vocab = _gpt()
    # the EOS id comes from the reference stream itself: the first prompt
    # whose greedy stream brings a NEW token at an index >= 2 (random-init
    # greedy streams repeat a lot, so a hand-picked index need not hold)
    for seed in range(11, 43):
        prompt = _prompts(1, seed=seed, lo=8, hi=9)[0]
        ref = _ref(model, params, None, prompt, 16)
        cut = next((i for i in range(2, 15) if ref[i] not in ref[:i]), None)
        if cut is not None:
            break
    else:
        pytest.fail("no prompt whose greedy stream has a late new token")
    eos = ref[cut]
    # n_slots=2/max_len=64 on purpose: the same program shapes as the
    # seeded/adversarial/compile-count/grammar tests below, so this
    # module compiles the cluster's spec program once
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=64, decode_block=4, bucket=8,
        speculative="ngram", spec_k=4, spec_rounds=4,
    ))
    h = eng.submit(prompt, max_new_tokens=16, eos_id=eos)
    eng.run()
    assert h.finish_reason == "eos"
    assert h.tokens == ref[:cut + 1] and h.tokens[-1] == eos


# ------------------------------------------------------------ MTP drafter


@pytest.mark.parametrize(
    "heads",
    [1, pytest.param(2, marks=pytest.mark.slow)],
)
def test_spec_mtp_greedy_exact(heads):
    """The MTP drafter (deepseekv3 heads, lane pool): greedy streams
    byte-identical to generate even when untrained drafts mostly
    reject, for 1 and 2 chained heads. The 2-head arm is slow-marked
    (a second trace of the whole MTP spec program for the wider chunk):
    tier-1 keeps 1-head serving exactness here plus 2-draft chain
    equality at the function level
    (tests/test_speculative.py::test_speculative_2draft_equals_plain_greedy
    and the full-context edge)."""
    model, params, extra, vocab = _dsv3(mtp_heads=heads)
    prompts = _prompts(2, seed=6, vocab=vocab)
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=48, decode_block=4, bucket=8,
        speculative="mtp", spec_rounds=2,
    ), extra_variables=extra)
    hs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run()
    for p, h in zip(prompts, hs):
        assert h.tokens == _ref(model, params, extra, p, 8)
    assert eng.metrics.spec_steps > 0


@pytest.mark.slow
def test_spec_mtp_accepts_on_predictable_stream():
    """On a memorized periodic corpus the MTP drafter must accept (the
    speedup mechanism is live, not just the all-reject fallback) while
    streams stay exact — the serving twin of
    tests/test_speculative.py's acceptance test. Marked slow (a 150-step
    training fit): tier-1 already gates MTP exactness (the untrained
    all-reject path above); the function-level twin
    (tests/test_speculative.py) is slow-marked for the same reason."""
    from solvingpapers_tpu.data.batches import lm_batch_iterator
    from solvingpapers_tpu.models.deepseekv3 import (
        DeepSeekV3,
        DeepSeekV3Config,
    )
    from solvingpapers_tpu.train import OptimizerConfig, TrainConfig, Trainer
    from solvingpapers_tpu.train.objectives import dsv3_init_fn, dsv3_loss_fn

    cfg = DeepSeekV3Config(
        vocab_size=64, block_size=128, dim=32, n_layers=2, n_heads=2,
        latent_dim=8, rope_dim=8, pe_scale=0.02, n_experts=4,
        top_experts=2, dropout=0.0, attn_dropout=0.0, mtp_heads=1,
    )
    model = DeepSeekV3(cfg)
    toks = np.tile(np.arange(8), 4000)
    tcfg = TrainConfig(
        steps=150, batch_size=8, log_every=1000, eval_every=0,
        optimizer=OptimizerConfig(max_lr=3e-3, warmup_steps=10,
                                  total_steps=150),
    )
    trainer = Trainer(model, tcfg, loss_fn=dsv3_loss_fn,
                      init_fn=dsv3_init_fn)
    state = trainer.fit(lm_batch_iterator(toks, 8, 32, seed=0))
    params = jax.device_get(state.params)
    extra = {"moe_state": jax.device_get(state.model_state)["moe_state"]}
    prompts = [np.tile(np.arange(8), 2).astype(np.int32),
               np.tile(np.arange(8), 2)[3:].astype(np.int32)]
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=64, decode_block=4, bucket=8,
        speculative="mtp", spec_rounds=4,
    ), extra_variables=extra)
    hs = [eng.submit(p, max_new_tokens=20) for p in prompts]
    eng.run()
    for p, h in zip(prompts, hs):
        assert h.tokens == _ref(model, params, extra, p, 20)
    assert eng.metrics.spec_accepted > 0, "trained drafts never accepted"


def test_spec_config_validation():
    model, params, _, _ = _gpt()
    with pytest.raises(ValueError, match="spec_rounds"):
        ServeEngine(model, params, ServeConfig(max_len=48, spec_rounds=4))
    with pytest.raises(ValueError, match="speculative must be one of"):
        ServeEngine(model, params, ServeConfig(max_len=48,
                                               speculative="oracle"))
    with pytest.raises(ValueError, match="mtp_heads == 0"):
        ServeEngine(model, params, ServeConfig(max_len=48,
                                               speculative="mtp"))
    dmodel, dparams, dextra, _ = _dsv3(mtp_heads=1)
    with pytest.raises(ValueError, match="lane pool"):
        ServeEngine(dmodel, dparams, ServeConfig(
            speculative="mtp", paged=True, page_size=16, max_len=48,
        ), extra_variables=dextra)
    with pytest.raises(ValueError, match="prefix"):
        ServeEngine(dmodel, dparams, ServeConfig(
            max_len=48, speculative="mtp", prefix_cache=True,
        ), extra_variables=dextra)
    with pytest.raises(ValueError, match="spec_k"):
        ServeEngine(model, params, ServeConfig(max_len=48,
                                               speculative="ngram",
                                               spec_k=0))


# ----------------------------------------------------- stochastic slots


def test_spec_seeded_streams_reproducible_and_greedy_in_mix_exact():
    """A seeded stochastic request replays the same stream across two
    spec-on engines (the rng chain folds only (seed, committed index)),
    and a greedy request sharing those batches stays exact vs spec-off."""
    model, params, _, vocab = _gpt()
    prompts = _prompts(3, seed=7, vocab=vocab)

    def run(spec):
        kw = dict(speculative="ngram", spec_k=4, spec_rounds=4) if spec \
            else {}
        # 2 slots for 3 requests: the third queues behind the first
        # free slot, which also exercises the chain's independence from
        # slot assignment/interleaving (and shares the module's S=2
        # compiled-program cluster)
        eng = ServeEngine(model, params, ServeConfig(
            n_slots=2, max_len=64, decode_block=4, bucket=8, **kw))
        hs = [
            eng.submit(prompts[0], max_new_tokens=10),
            eng.submit(prompts[1], max_new_tokens=10,
                       params=SamplingParams(temperature=1.2, top_p=0.9,
                                             seed=7)),
            eng.submit(prompts[2], max_new_tokens=10,
                       params=SamplingParams(temperature=0.8, top_k=8,
                                             seed=11, logprobs=True)),
        ]
        eng.run()
        return hs

    a, b, off = run(True), run(True), run(False)
    assert a[0].tokens == off[0].tokens == _ref(model, params, None,
                                                prompts[0], 10)
    assert a[1].tokens == b[1].tokens
    assert a[2].tokens == b[2].tokens
    assert len(a[2].logprobs) == len(a[2].tokens)
    assert all(np.isfinite(lp) and lp <= 0 for lp in a[2].logprobs)


@pytest.mark.parametrize("draft_kind", ["likely", "unlikely", "mixed"])
def test_spec_verify_matches_plain_sampler_distribution(draft_kind):
    """Fixed-seed statistical test: the committed token at the FIRST
    chunk position (a verify-or-resample position) must be distributed
    exactly like `fused_sample`'s draw from the same truncated
    distribution, whatever the draft was — the lossless rejection
    sampling claim, measured empirically (total variation under the
    sampling-noise floor)."""
    vocab, cap, n = 32, 16, 4000
    logits = jax.random.normal(jax.random.key(1), (1, vocab)) * 2.0
    packed = PackedSampling(
        temperature=jnp.asarray([0.9]), top_p=jnp.asarray([0.85]),
        min_p=jnp.asarray([0.02]), top_k=jnp.asarray([12]),
        need_lp=jnp.asarray([0]),
    )
    keysets = jax.random.split(jax.random.key(2), n)
    ref = jax.vmap(
        lambda kk: fused_sample(logits, packed, kk[None], cap=cap)[0][0]
    )(keysets)
    ref_hist = np.bincount(np.asarray(ref), minlength=vocab) / n

    order = np.asarray(jnp.argsort(-logits[0]))
    draft = {"likely": int(order[0]), "unlikely": int(order[-1]),
             "mixed": int(order[3])}[draft_kind]
    big_l = 3
    lg = jnp.broadcast_to(logits[0], (1, big_l, vocab))
    drafts = jnp.asarray([[draft, draft]], jnp.int32)
    avail = jnp.asarray([2], jnp.int32)

    def one(kk):
        keys = jax.vmap(
            lambda i: jax.random.fold_in(kk, i)
        )(jnp.arange(big_l))[None, :]
        out, _, _ = spec_verify(lg, drafts, avail, packed, keys, cap=cap)
        return out[0, 0]

    toks = jax.vmap(one)(jax.random.split(jax.random.key(3), n))
    hist = np.bincount(np.asarray(toks), minlength=vocab) / n
    tv = 0.5 * float(np.abs(hist - ref_hist).sum())
    assert tv < 0.05, (
        f"rejection-sampled marginal diverged from the plain sampler "
        f"(draft={draft_kind}, TV={tv:.4f})"
    )


def test_spec_verify_greedy_rows_are_argmax():
    """Greedy rows commit row argmaxes and accept only exact matches —
    the committed matrix IS the greedy continuation."""
    vocab, cap = 32, 16
    lg = jax.random.normal(jax.random.key(5), (2, 4, vocab))
    am = np.asarray(jnp.argmax(lg, -1))
    drafts = jnp.asarray(
        [[int(am[0, 0]), int(am[0, 1]), 0],
         [int(am[1, 0]) + 1, 0, 0]], jnp.int32) % vocab
    avail = jnp.asarray([3, 3], jnp.int32)
    packed = PackedSampling(
        temperature=jnp.zeros(2), top_p=jnp.ones(2), min_p=jnp.zeros(2),
        top_k=jnp.zeros(2, jnp.int32), need_lp=jnp.zeros(2, jnp.int32),
    )
    keys = jnp.stack([jax.random.split(jax.random.key(6), 4)] * 2)
    out, commits, _ = spec_verify(lg, drafts, avail, packed, keys, cap=cap)
    np.testing.assert_array_equal(np.asarray(out), am)
    # slot 0 accepted drafts 0,1 (exact argmaxes), rejected draft 2
    # unless it happened to be the argmax too
    expect0 = 3 + (int(am[0, 2]) == 0)
    assert int(commits[0]) == min(expect0, 4)
    # slot 1's first draft is wrong by construction: exactly 1 commit
    assert int(commits[1]) == 1


# ------------------------------------------------------ drafter + control


def test_ngram_drafts_lookup():
    """The device lookup proposes the continuation of the most recent
    earlier occurrence of the longest matching tail n-gram."""
    hist = jnp.asarray([5, 1, 2, 9, 9, 1, 2, 7, 3, 1, 2, 0, 0, 0, 0, 0],
                       jnp.int32)
    # live length 11: tail bigram (1, 2) last recurred at index 5 -> the
    # continuation is hist[7:] = [7, 3, ...]
    drafts, avail = ngram_drafts(hist, jnp.int32(11), k=3, nmax=3)
    assert int(avail) == 3
    np.testing.assert_array_equal(np.asarray(drafts), [7, 3, 1])
    # nothing recurs: no proposal
    fresh = jnp.asarray([1, 2, 3, 4, 5, 6, 7, 8], jnp.int32)
    _, avail = ngram_drafts(fresh, jnp.int32(8), k=3, nmax=3)
    assert int(avail) == 0
    # short history never proposes (nothing earlier to match)
    _, avail = ngram_drafts(fresh, jnp.int32(1), k=3, nmax=3)
    assert int(avail) == 0


def test_spec_controller_backoff_and_probe():
    """The three-state controller: cold start probes, zero acceptance
    holds (plain blocks) with EXPONENTIAL backoff between cheap probes,
    recovered acceptance promotes to full speculation."""
    ctl = SpecController(min_rate=1.0, probe_every=4, decay=0.0)
    assert ctl.decide() == "probe"  # cold start measures cheaply
    ctl.observe(accepted=0, rounds=8)  # 0/round < 1.0 -> hold 4
    assert [ctl.decide() for _ in range(4)] == ["off"] * 4
    assert ctl.decide() == "probe"
    ctl.observe(accepted=0, rounds=2)  # failed probe -> hold DOUBLES
    assert [ctl.decide() for _ in range(8)] == ["off"] * 8
    assert ctl.decide() == "probe"
    ctl.observe(accepted=16, rounds=2)  # 8/round: recovered
    assert ctl.decide() == "full"
    ctl.observe(accepted=12, rounds=6)  # still healthy
    assert ctl.decide() == "full"
    stats = ctl.stats()
    assert stats["fallback_steps"] == 12
    assert stats["probes"] == 3
    assert stats["mode"] == "full"
    # a healthy recovery reset the backoff: the next failure holds 4
    ctl.observe(accepted=0, rounds=6)  # decay=0 -> EMA drops instantly
    assert sum(1 for _ in range(20) if ctl.decide() == "off") == 4


def test_spec_adversarial_traffic_falls_back():
    """High-temperature random streams defeat the n-gram drafter; the
    engine must settle onto the plain block program (fallback steps
    dominate) instead of paying the chunk width every step — and the
    streams still finish correctly."""
    model, params, _, vocab = _gpt()
    prompts = _prompts(6, seed=13, vocab=vocab)
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=64, decode_block=4, bucket=8,
        speculative="ngram", spec_k=4, spec_rounds=4,
        spec_min_rate=0.5, spec_probe_every=4,
    ))
    hs = [eng.submit(p, max_new_tokens=24,
                     params=SamplingParams(temperature=2.0, seed=100 + i))
          for i, p in enumerate(prompts)]
    eng.run()
    assert all(h.done for h in hs)
    stats = eng.statusz()["spec"]
    assert stats["fallback_steps"] > 0, (
        "adversarial traffic never triggered the controller's fallback"
    )


def test_spec_compile_count_one_program_for_mixed_batches():
    """Greedy + stochastic + draft-less slots in one batch add ZERO
    compiled speculative decode programs over an all-greedy run — draft
    length and every sampling knob are traced operands."""
    model, params, _, vocab = _gpt()
    prompts = _prompts(4, seed=5, lo=4, hi=8, vocab=vocab)
    cfg = ServeConfig(n_slots=2, max_len=64, decode_block=4, bucket=8,
                      speculative="ngram", spec_k=4, spec_rounds=4)

    eng = ServeEngine(model, params, cfg)
    for p in prompts:
        eng.submit(p, max_new_tokens=6)
    eng.run()
    progs = _spec_decode_program._cache_size()
    assert progs >= 1

    eng = ServeEngine(model, params, cfg)
    mixes = (None,
             SamplingParams(temperature=1.3, top_p=0.8, seed=1),
             SamplingParams(temperature=0.7, top_k=5),
             SamplingParams(temperature=1.0, min_p=0.1, seed=2,
                            logprobs=True))
    for p, sp in zip(prompts, mixes):
        eng.submit(p, max_new_tokens=6, params=sp)
    eng.run()
    assert _spec_decode_program._cache_size() == progs


def test_spec_grammar_slot_stays_constrained():
    """A grammar-constrained request inside a speculative engine decodes
    draft-free (one committed token per step) and still produces a
    complete, parseable JSON document."""
    import json

    from solvingpapers_tpu.serve.grammar import JsonStepper

    model, params, _, vocab = _gpt()
    table = list(
        '{}[]":,-.0123456789 \nabcdefghijklmnopqrstuvwxyz'
        "ABCDEFGHIJKLMNOP\\"
    )[:vocab]
    stepper = JsonStepper(table)
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=64, decode_block=4, bucket=8,
        speculative="ngram", spec_k=4, spec_rounds=4,
    ), detokenize=lambda ids: "".join(table[i] or "" for i in ids))
    g = eng.submit(_prompts(1, seed=21)[0], max_new_tokens=40,
                   grammar=stepper)
    plain = eng.submit(_prompts(1, seed=22)[0], max_new_tokens=10)
    eng.run()
    assert g.finish_reason == "stop"
    text = "".join(table[t] or "" for t in g.tokens)
    json.loads(text)
    assert plain.tokens == _ref(model, params, None,
                                _prompts(1, seed=22)[0], 10)


# --------------------------------------------------- scheduler fairness


def _req(n=4):
    return Request(prompt=np.arange(n, dtype=np.int32), max_new_tokens=4,
                   eos_id=None)


def test_scheduler_tick_weight_normalizes_wait_to_delivered_tokens():
    """The anti-starvation budget is a DELIVERED-TOKEN quantum: a
    speculative engine passing weight = delivered/block must trip the
    override after the same delivered work as a plain engine ticking 1
    per block — high acceptance cannot stretch the head's wait."""
    # plain engine: 1.0/step; budget trips after max_wait_steps blocks
    plain = FIFOScheduler(decode_priority=True, max_prefills_per_step=1,
                          max_wait_steps=4)
    plain.submit(_req())
    for _ in range(5):
        plain.tick()
    assert len(plain.pick(n_free=2, n_active=4)) == 1  # budget fired

    # spec engine at 3x acceptance: each step delivers 3 blocks' worth;
    # the same delivered-token quantum is 2 steps, not 5
    spec = FIFOScheduler(decode_priority=True, max_prefills_per_step=1,
                         max_wait_steps=4)
    spec.submit(_req())
    for _ in range(2):
        spec.tick(weight=3.0)
    assert spec.queue[0].waited_steps == pytest.approx(6.0)
    assert len(spec.pick(n_free=2, n_active=4)) == 1  # same quantum

    # WITHOUT the weight (the regression): 2 high-acceptance steps =
    # 6 blocks of delivered work, yet the head would still be waiting
    legacy = FIFOScheduler(decode_priority=True, max_prefills_per_step=1,
                           max_wait_steps=4)
    legacy.submit(_req())
    for _ in range(2):
        legacy.tick()  # the old 1-per-iteration clock
    head = legacy.queue[0]
    assert head.waited_steps <= legacy.max_wait_steps  # still starved

    # sub-1 weights clamp: a purge-only step cannot age slower than 1
    clamp = FIFOScheduler(max_wait_steps=4)
    clamp.submit(_req())
    clamp.tick(weight=0.25)
    assert clamp.queue[0].waited_steps == pytest.approx(1.0)


def test_engine_spec_step_passes_delivered_weight():
    """End-to-end: with speculation accepting, the engine's tick weight
    exceeds 1 (waiting requests age faster than one unit per step)."""
    model, params, _, vocab = _gpt()
    # a repetitive prompt the untrained model continues repetitively —
    # the lookup accepts, so one step delivers more than a block
    prompt = np.tile(np.asarray([3, 9], np.int32), 8)
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=1, max_len=64, decode_block=2, bucket=8,
        speculative="ngram", spec_k=4, spec_rounds=2,
    ))
    h1 = eng.submit(prompt, max_new_tokens=24)
    waiter = eng.submit(_prompts(1, seed=30)[0], max_new_tokens=4)
    eng.step()  # admit h1 (prefill only)
    eng.step()  # first spec block
    if eng.metrics.spec_accepted > 0:
        assert waiter.waited_steps > 2.0, (
            "delivered-token weight never aged the waiting request "
            f"faster than the step clock (waited={waiter.waited_steps})"
        )
    eng.run()
    assert h1.done and waiter.done
    assert h1.tokens == _ref(model, params, None, prompt, 24)
