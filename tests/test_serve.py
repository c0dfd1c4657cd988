"""Continuous-batching serving engine tests (solvingpapers_tpu/serve/).

The contract under test: iteration-level scheduling over a slot pool must
be invisible in the tokens — every request's stream is exactly what a
per-request one-shot `generate` (greedy) would produce, no matter how
requests interleave, which lane they land in, how prompts are bucketed,
or how prefill is chunked. Plus the serving-specific behaviors: a lane
freed by early EOS is re-acquired by a queued request before the batch
drains, admission control bounds the queue, and decode priority bounds
per-iteration prefills.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from solvingpapers_tpu.infer import generate
from solvingpapers_tpu.models.gpt import GPT, GPTConfig
from solvingpapers_tpu.serve import (
    FIFOScheduler,
    KVSlotPool,
    Request,
    ServeConfig,
    ServeEngine,
)

GPT_TINY = GPTConfig(vocab_size=64, block_size=64, dim=32, n_layers=2,
                     n_heads=2, dropout=0.0)


@pytest.fixture(scope="module")
def gpt_tiny():
    model = GPT(GPT_TINY)
    rng = jax.random.key(0)
    params = model.init({"params": rng}, jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _prompts(n, seed=0, lo=4, hi=24):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, GPT_TINY.vocab_size,
                     size=int(rng.integers(lo, hi))).astype(np.int32)
        for _ in range(n)
    ]


def _ref_stream(model, params, prompt, max_new, eos_id=None):
    """Per-request one-shot generate, trimmed at the first EOS inclusive
    (generate pads with EOS after that — a static-shape artifact, not
    part of the stream contract)."""
    out = generate(model, params, jnp.asarray(prompt)[None, :],
                   jax.random.key(0), max_new_tokens=max_new, eos_id=eos_id)
    gen = np.asarray(out[0, len(prompt):]).tolist()
    if eos_id is not None and eos_id in gen:
        gen = gen[: gen.index(eos_id) + 1]
    return gen


# ----------------------------------------------------------------- engine


@pytest.mark.parametrize("paged", [False, True], ids=["lane", "paged"])
def test_staggered_requests_match_one_shot_generate(gpt_tiny, paged):
    """S slots, 2*S requests submitted in two staggered waves: every
    stream must be token-exact vs per-request one-shot generate — on
    both pool layouts (the paged pool's page-table indirection must be
    invisible in the tokens)."""
    model, params = gpt_tiny
    S = 4
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=S, max_len=64, decode_block=4, bucket=8, paged=paged,
        page_size=8 if paged else None,
    ))
    prompts = _prompts(2 * S, seed=1)
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts[:S]]
    for _ in range(3):  # first wave mid-flight when the second arrives
        eng.step()
    handles += [eng.submit(p, max_new_tokens=12) for p in prompts[S:]]
    eng.run()
    assert all(h.done for h in handles)
    assert all(h.finish_reason == "length" for h in handles)
    for p, h in zip(prompts, handles):
        assert h.tokens == _ref_stream(model, params, p, 12), (
            f"request {h.id} (slot {h.slot}, prompt len {len(p)}) diverged"
        )
    snap = eng.metrics.snapshot()
    assert snap["serve/requests_finished"] == 2 * S
    assert snap["serve/tokens_out"] == 2 * S * 12
    assert 0 < snap["serve/slot_occupancy"] <= 1


def test_early_eos_frees_slot_for_queued_request(gpt_tiny):
    """A slot freed by early EOS must be re-acquired by a queued request
    while the rest of the batch is still decoding."""
    model, params = gpt_tiny
    # request 0 is the first candidate whose greedy stream brings a NEW
    # token early (index 2..7); that token is its EOS id. Chosen from the
    # reference streams, not by hand: random-init greedy streams repeat
    candidates = _prompts(16, seed=2, lo=6, hi=12)
    for i, cand in enumerate(candidates):
        ref0 = _ref_stream(model, params, cand, 16)
        cut = next((j for j in range(2, 8) if ref0[j] not in ref0[:j]), None)
        if cut is not None:
            break
    else:
        pytest.fail("no prompt whose greedy stream has an early new token")
    eos = ref0[cut]
    prompts = [cand] + [c for j, c in enumerate(candidates) if j != i][:3]

    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=64, decode_block=2, bucket=8,
    ))
    h0 = eng.submit(prompts[0], max_new_tokens=16, eos_id=eos)
    rest = [eng.submit(p, max_new_tokens=16) for p in prompts[1:]]
    eng.run()
    assert h0.finish_reason == "eos"
    assert h0.tokens == _ref_stream(model, params, prompts[0], 16, eos_id=eos)
    assert h0.tokens[-1] == eos and len(h0.tokens) < 16
    for p, h in zip(prompts[1:], rest):
        assert h.finish_reason == "length"
        assert h.tokens == _ref_stream(model, params, p, 16)
    # the lane h0 vacated went to a queued request before the batch drained
    reused = [h for h in rest if h.slot == h0.slot and
              h.admit_time > h0.finish_time]
    assert reused, "freed slot was never re-acquired"
    still_decoding = [h for h in rest
                      if h.admit_time < reused[0].admit_time
                      and h.finish_time > reused[0].admit_time]
    assert still_decoding, "pool had drained before the slot was reused"


def test_chunked_prefill_and_bucketing_are_invisible(gpt_tiny):
    """Prefill chunking + right-pad bucketing must not change streams —
    including the case where the last real token's logits live in a
    non-final chunk (prompt 9 pads to 24, chunk 8: row in chunk 2 of 3)."""
    model, params = gpt_tiny
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=64, decode_block=4, bucket=24, prefill_chunk=8,
    ))
    prompts = [_prompts(1, seed=s, lo=9, hi=10)[0] for s in range(3)]
    prompts.append(_prompts(1, seed=9, lo=17, hi=18)[0])
    handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run()
    for p, h in zip(prompts, handles):
        assert h.tokens == _ref_stream(model, params, p, 8)


def test_deepseekv3_serves_with_latent_cache_lanes():
    """The flagship's MLA LatentCache pools/serves through the same
    engine (lane carving is pytree-generic), moe_state riding
    extra_variables exactly as in generate."""
    import dataclasses as dc

    from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3, DeepSeekV3Config

    cfg = DeepSeekV3Config(
        vocab_size=64, block_size=64, dim=32, n_layers=2, n_heads=4,
        latent_dim=8, rope_dim=8, n_experts=4, top_experts=2, dropout=0.0,
        attn_dropout=0.0,
    )
    model = DeepSeekV3(cfg)
    rng = jax.random.key(3)
    prompts = _prompts(3, seed=4, lo=5, hi=14)
    variables = model.init({"params": rng}, jnp.asarray(prompts[0])[None, :])
    params, extra = variables["params"], {"moe_state": variables["moe_state"]}

    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=32, decode_block=2, bucket=8,
    ), extra_variables=extra)
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run()
    for p, h in zip(prompts, handles):
        out = generate(model, params, jnp.asarray(p)[None, :],
                       jax.random.key(0), max_new_tokens=6,
                       extra_variables=extra)
        assert h.tokens == np.asarray(out[0, len(p):]).tolist()


def test_submit_validates_capacity(gpt_tiny):
    model, params = gpt_tiny
    eng = ServeEngine(model, params, ServeConfig(n_slots=1, max_len=32))
    with pytest.raises(ValueError, match="exceeds the engine capacity"):
        eng.submit(np.zeros(30, np.int32), max_new_tokens=8)
    with pytest.raises(ValueError, match="at least one token"):
        eng.submit(np.zeros(0, np.int32))


def test_submit_validates_prompt_dtype_and_shape(gpt_tiny):
    """Bad prompts raise host-side at submit, never inside a traced
    program: float dtypes (silent truncation hazard), non-1-D shapes,
    non-positive budgets and deadlines."""
    model, params = gpt_tiny
    eng = ServeEngine(model, params, ServeConfig(n_slots=1, max_len=32))
    with pytest.raises(ValueError, match="integer token ids"):
        eng.submit(np.asarray([1.0, 2.5, 3.0]), max_new_tokens=4)
    with pytest.raises(ValueError, match="must be 1-D"):
        eng.submit(np.zeros((2, 4), np.int32), max_new_tokens=4)
    with pytest.raises(ValueError, match="must be 1-D"):
        eng.submit(np.int32(3), max_new_tokens=4)
    with pytest.raises(ValueError, match="max_new_tokens must be >= 1"):
        eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=0)
    with pytest.raises(ValueError, match="deadline_s must be > 0"):
        eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=4,
                   deadline_s=0.0)
    # a python list of ints is still fine (integer-kind after asarray)
    h = eng.submit([1, 2, 3], max_new_tokens=4)
    assert h.prompt.dtype == np.int32


def test_submit_rejects_bad_sampling_params(gpt_tiny):
    """SamplingParams validates at construction (so the error carries the
    bad field, not a trace-time shape error), and stop strings demand a
    detokenizer."""
    from solvingpapers_tpu.serve import SamplingParams

    model, params = gpt_tiny
    eng = ServeEngine(model, params, ServeConfig(n_slots=1, max_len=32))
    for bad in (
        dict(temperature=-0.5),
        dict(top_p=0.0),
        dict(top_p=1.5),
        dict(min_p=1.0001),
        dict(top_k=-1),
        dict(seed=-3),
        dict(seed=2**31),  # must fit the engine's int32 control mirrors
        dict(max_tokens=0),
        dict(stop=("",)),
        dict(stop_token_ids=(50256.9,)),  # int() would stop on wrong id
        dict(stop_token_ids="abc"),
    ):
        with pytest.raises(ValueError):
            SamplingParams(**bad)
    # a lone id normalizes like a lone stop string does
    assert SamplingParams(stop_token_ids=7).stop_token_ids == (7,)
    with pytest.raises(ValueError, match="detokenize"):
        eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=4,
                   params=SamplingParams(stop=("xy",)))
    # max_tokens overrides the submit budget and still checks capacity
    with pytest.raises(ValueError, match="exceeds the engine capacity"):
        eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=1,
                   params=SamplingParams(max_tokens=64))


def test_admission_control_rejects_beyond_queue(gpt_tiny):
    model, params = gpt_tiny
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=1, max_len=64, max_waiting=2,
    ))
    handles = [eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=4)
               for _ in range(3)]
    assert [h.state for h in handles] == ["waiting", "waiting", "rejected"]
    assert eng.metrics.requests_rejected == 1
    eng.run()
    assert [h.done for h in handles] == [True, True, False]


# ------------------------------------------------------------------- pool


def test_kv_pool_acquire_release(gpt_tiny):
    model, _ = gpt_tiny
    pool = KVSlotPool(model, n_slots=3, max_len=16)
    assert pool.caches[0].k.shape[0] == 3  # slot dim IS the batch dim
    slots = [pool.acquire() for _ in range(3)]
    assert sorted(slots) == [0, 1, 2]
    assert pool.acquire() is None and pool.n_free == 0
    pool.release(slots[1])
    assert pool.occupancy == pytest.approx(2 / 3)
    assert pool.acquire() == slots[1]  # LIFO: freshest lane first
    pool.release(slots[1])
    with pytest.raises(ValueError, match="double release"):
        pool.release(slots[1])


def test_kv_pool_release_guard_is_membership_tracked(gpt_tiny):
    """Regression for the O(n_slots) `slot in free_list` scan on the
    hot release path: free membership is a boolean mask kept in sync
    with the LIFO list through arbitrary acquire/release interleavings,
    and the double-release guard still fires from any state."""
    model, _ = gpt_tiny
    pool = KVSlotPool(model, n_slots=4, max_len=16)
    held = [pool.acquire() for _ in range(4)]
    for s in held:
        assert not pool._free_mask[s]
    pool.release(held[2])
    pool.release(held[0])
    assert pool._free_mask[held[0]] and pool._free_mask[held[2]]
    assert pool.acquire() == held[0]  # LIFO order preserved by the list
    with pytest.raises(ValueError, match="double release"):
        pool.release(held[2])
    # mask and list agree exactly after the churn
    assert sorted(pool._free) == sorted(np.flatnonzero(pool._free_mask))


def test_kv_pool_acquire_on_exhausted_is_stable(gpt_tiny):
    """Exhaustion returns None (no exception, no state damage) and stays
    None until a release; the released lane is handed out next."""
    model, _ = gpt_tiny
    pool = KVSlotPool(model, n_slots=2, max_len=16)
    a, b = pool.acquire(), pool.acquire()
    for _ in range(3):
        assert pool.acquire() is None
    assert pool.n_free == 0 and pool.n_active == 2
    pool.release(a)
    assert pool.acquire() == a
    assert pool.acquire() is None


def test_kv_pool_splice_and_extract_roundtrip(gpt_tiny):
    """extract_prefix snapshots a COPY; splice_prefix writes it back at
    an offset without touching other lanes or the rest of the lane."""
    model, _ = gpt_tiny
    pool = KVSlotPool(model, n_slots=2, max_len=16)
    # fill lane 1's slots [0, 8) with a recognizable ramp
    ramp = jax.tree_util.tree_map(
        lambda a: jnp.arange(np.prod(a.shape[2:]) * 8, dtype=jnp.float32)
        .reshape((1, 8) + a.shape[2:]).astype(a.dtype),
        pool.extract_prefix(1, 0, 8),
    )
    pool.splice_prefix(1, ramp, offset=0)
    seg = pool.extract_prefix(1, 4, 4)
    np.testing.assert_array_equal(
        np.asarray(seg[0].k), np.asarray(ramp[0].k[:, 4:8])
    )
    # splice the snapshot into lane 0 at offset 8; lane 1 is untouched
    before_lane1 = np.asarray(pool.caches[0].k[1])
    pool.splice_prefix(0, seg, offset=8)
    np.testing.assert_array_equal(
        np.asarray(pool.caches[0].k[0, 8:12]), np.asarray(ramp[0].k[0, 4:8])
    )
    np.testing.assert_array_equal(np.asarray(pool.caches[0].k[1]), before_lane1)
    with pytest.raises(ValueError, match="exceeds the lane capacity"):
        pool.splice_prefix(0, seg, offset=14)
    with pytest.raises(ValueError, match="exceeds the lane capacity"):
        pool.extract_prefix(0, 14, 4)


def test_store_lane_and_splice_reject_dtype_mismatch(gpt_tiny):
    """The pool write paths never cast: a silent astype would down-cast
    an fp32 segment into a bf16 pool and quietly change every stream
    decoded over it. Mismatches must raise at trace time."""
    from solvingpapers_tpu.serve import extract_lane, store_lane

    model, _ = gpt_tiny
    pool = KVSlotPool(model, n_slots=2, max_len=16)
    lane = extract_lane(pool.caches, 0)
    pool_dtype = pool.caches[0].k.dtype
    wrong_dtype = jnp.bfloat16 if pool_dtype == jnp.float32 else jnp.float32
    wrong = jax.tree_util.tree_map(lambda a: a.astype(wrong_dtype), lane)
    with pytest.raises(TypeError, match="cast explicitly"):
        store_lane(pool.caches, wrong, 0)
    seg = jax.tree_util.tree_map(
        lambda a: a.astype(wrong_dtype), pool.extract_prefix(0, 0, 4)
    )
    with pytest.raises(TypeError, match="cast explicitly"):
        pool.splice_prefix(0, seg, offset=0)
    # matching dtypes round-trip fine
    pool.caches = store_lane(pool.caches, lane, 0)
    pool.splice_prefix(0, pool.extract_prefix(0, 0, 4), offset=4)


def test_kv_pool_positions_track_lane_fill(gpt_tiny):
    """`pool.positions[slot]` is the lane's real KV fill level — prompt
    plus every emitted token except the newest (whose KV lands only when
    it is fed back next step), no decode-block overshoot — and resets to
    0 on release."""
    model, params = gpt_tiny
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=64, decode_block=4, bucket=8,
    ))
    prompts = _prompts(2, seed=7, lo=5, hi=11)
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.step()  # prefill + one block: both lanes mid-stream
    for p, h in zip(prompts, handles):
        if not h.done:
            assert eng.pool.positions[h.slot] == len(p) + len(h.tokens) - 1
    eng.run()
    assert all(h.done for h in handles)
    np.testing.assert_array_equal(eng.pool.positions, 0)


# -------------------------------------------------------------- scheduler


def _req(n=4):
    return Request(prompt=np.arange(n, dtype=np.int32), max_new_tokens=4,
                   eos_id=None)


def test_scheduler_decode_priority_bounds_prefills():
    sched = FIFOScheduler(decode_priority=True, max_prefills_per_step=1)
    for _ in range(3):
        sched.submit(_req())
    # active decodes present: one prefill per iteration
    assert len(sched.pick(n_free=3, n_active=2)) == 1
    # idle pool: fill every free slot at once
    assert len(sched.pick(n_free=3, n_active=0)) == 2


def test_scheduler_wait_budget_overrides_decode_priority():
    sched = FIFOScheduler(decode_priority=True, max_prefills_per_step=1,
                          max_wait_steps=2)
    for _ in range(3):
        sched.submit(_req())
    for _ in range(3):
        sched.tick()
    # head waited past the budget: prefill gets the free slots despite
    # active decodes
    assert len(sched.pick(n_free=2, n_active=4)) == 2


def test_scheduler_admission_control():
    sched = FIFOScheduler(max_waiting=1)
    assert sched.submit(_req())
    overflow = _req()
    assert not sched.submit(overflow)
    assert overflow.state == "rejected"
    assert len(sched) == 1
