"""Fault-tolerance tests (serve/faults.py + the engine's supervised
step boundary in serve/engine.py).

Four contracts under test. Blast-radius isolation: with a seeded fault
plan poisoning K of N concurrent streams (both pools, speculation on
and off), the N-K untouched streams must be TOKEN-EXACT vs a fault-free
run, the poisoned streams finish "error", and `assert_no_leaks` passes
after drain. Systemic recovery: synthetic XlaRuntimeError/OOM trigger
bounded pool-rebuild retries — streams resume by recompute token-exact
— and persistent failure drains to `unhealthy` (/healthz 503) with a
backoff-gated recovery that serves a fresh request token-exactly.
Liveness: injected stalls fire the watchdog, and `ServeEngine.close` /
`force_drain` return within their bound with everything reclaimed.
None-pattern: with `fault_plan=None` the compiled-program inventory is
byte-for-byte the plain engine's (the compile registry proves no scrub
or extra program exists) and streams are untouched — the always-traced
finite-logits guard is a numeric no-op on finite logits.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_no_leaks
from solvingpapers_tpu.infer import generate
from solvingpapers_tpu.serve import (
    DegradationLadder,
    FaultPlan,
    FaultSpec,
    ServeConfig,
    ServeEngine,
)
from solvingpapers_tpu.serve.faults import InjectedFault, classify_failure


def _gpt_tiny():
    from solvingpapers_tpu.models.gpt import GPT, GPTConfig

    model = GPT(GPTConfig(vocab_size=64, block_size=64, dim=32,
                          n_layers=2, n_heads=2, dropout=0.0))
    params = model.init({"params": jax.random.key(0)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


_MODEL = None


def _model():
    global _MODEL
    if _MODEL is None:
        _MODEL = _gpt_tiny()
    return _MODEL


def _ref(model, params, prompt, max_new):
    out = generate(model, params, jnp.asarray(prompt)[None, :],
                   jax.random.key(0), max_new_tokens=max_new)
    return np.asarray(out[0, len(prompt):]).tolist()


def _prompts(n, seed=0, size=8):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, size=size).astype(np.int32)
            for _ in range(n)]


def _cfg(**kw):
    base = dict(n_slots=3, max_len=32, decode_block=4, bucket=8,
                max_prefills_per_step=3)
    base.update(kw)
    return ServeConfig(**base)


# ------------------------------------------------------------- plan units


def test_fault_plan_is_deterministic_and_validates():
    specs = [
        dict(site="decode", kind="nan", visit=3, slot=1),
        dict(site="prefill", kind="oom", visit=0, count=2),
    ]
    a, b = FaultPlan(specs), FaultPlan(specs)
    fired_a = [tuple(s.kind for s in a.poke("decode")) for _ in range(5)]
    fired_b = [tuple(s.kind for s in b.poke("decode")) for _ in range(5)]
    assert fired_a == fired_b == [(), (), (), ("nan",), ()]
    # count=2 fires at consecutive visits
    assert [len(a.poke("prefill")) for _ in range(3)] == [1, 1, 0]
    # from_config on a live plan resets its counters (bench arms reuse
    # one config object across engines)
    fresh = FaultPlan.from_config(a)
    assert fresh.fired == 0 and fresh.poke("prefill")[0].kind == "oom"
    assert FaultPlan.from_config(None) is None
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec(site="nowhere", kind="nan", visit=0)
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec(site="decode", kind="meteor", visit=0)
    with pytest.raises(ValueError, match="stall_s"):
        FaultSpec(site="decode", kind="stall", visit=0)
    with pytest.raises(ValueError, match="sse_write"):
        FaultSpec(site="decode", kind="socket_reset", visit=0)
    with pytest.raises(ValueError, match="poison"):
        FaultSpec(site="scatter", kind="nan", visit=0)


def test_classify_failure_taxonomy():
    assert classify_failure(InjectedFault("oom", "decode")) == "systemic"
    assert classify_failure(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory")) == "systemic"

    class XlaRuntimeError(RuntimeError):
        pass

    assert classify_failure(XlaRuntimeError("boom")) == "systemic"
    assert classify_failure(KeyError("host bug")) == "host"


def test_ladder_hysteresis_and_shed_order():
    lad = DegradationLadder(up_steps=2, down_steps=3)
    assert lad.observe(True) is None          # 1 pressured step: hold
    assert lad.observe(True) == 1             # 2nd: escalate one rung
    assert lad.shed_classes() == ()
    for expect in (2, 3, 4):
        assert lad.observe(True) is None
        assert lad.observe(True) == expect
    assert lad.rung == 4 and lad.shed_classes() == ("batch", "standard")
    assert lad.observe(True) is None          # capped at max rung
    # de-escalation needs down_steps CONSECUTIVE clear evaluations,
    # and a pressured step resets the clear counter (hysteresis)
    assert lad.observe(False) is None
    assert lad.observe(True) is None
    assert [lad.observe(False) for _ in range(3)] == [None, None, 3]
    assert lad.shed_classes() == ("batch",)   # reverse re-arm order
    for expect in (2, 1, 0):
        assert [lad.observe(False) for _ in range(3)][-1] == expect
    assert lad.rung == 0


# ------------------------------------------------- blast-radius isolation


@pytest.mark.parametrize("kind", ["nan", "inf"])
@pytest.mark.parametrize("paged", [False, True])
def test_quarantine_isolates_poisoned_slot(paged, kind):
    """K=1 of N=3 streams poisoned at a decode visit: the poisoned
    stream finishes "error", the other two are token-exact vs the
    fault-free reference, and the drained pool leaks nothing."""
    model, params = _model()
    prompts = _prompts(3, seed=1)
    plan = [dict(site="decode", kind=kind, visit=1, slot=1)]
    kw = dict(paged=True, page_size=4) if paged else {}
    eng = ServeEngine(model, params, _cfg(fault_plan=plan, **kw))
    hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run()
    errs = [h for h in hs if h.finish_reason == "error"]
    assert len(errs) == 1, [h.finish_reason for h in hs]
    for h, p in zip(hs, prompts):
        if h is not errs[0]:
            assert h.tokens == _ref(model, params, p, 10), \
                "an untouched stream diverged — blast radius leaked"
    snap = eng.metrics.snapshot()
    assert snap["serve/fault_quarantined"] == 1.0
    assert snap["serve/finish_error"] == 1.0
    assert_no_leaks(eng)


@pytest.mark.parametrize("paged", [False, True])
def test_quarantine_isolates_with_speculation(paged):
    model, params = _model()
    prompts = _prompts(3, seed=2)
    plan = [dict(site="decode", kind="nan", visit=1, slot=2)]
    kw = dict(paged=True, page_size=4) if paged else {}
    eng = ServeEngine(model, params, _cfg(
        fault_plan=plan, speculative="ngram", spec_k=2, spec_rounds=2,
        **kw,
    ))
    hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run()
    errs = [h for h in hs if h.finish_reason == "error"]
    assert len(errs) == 1
    for h, p in zip(hs, prompts):
        if h is not errs[0]:
            assert h.tokens == _ref(model, params, p, 10)
    assert_no_leaks(eng)


@pytest.mark.parametrize("paged", [False, True])
def test_quarantine_on_quantized_pool_scrubs_scales(paged):
    """Quantized pools: a quarantine must scrub int8 codes AND scale
    rows (a NaN absmax scale would dequantize the whole block to NaN
    for the slot's next occupant), and the exact-lane free list must
    survive the drain."""
    model, params = _model()
    prompts = _prompts(3, seed=21)
    plan = [dict(site="decode", kind="nan", visit=1, slot=0)]
    kw = dict(paged=True, page_size=4) if paged else {}
    eng = ServeEngine(model, params, _cfg(
        fault_plan=plan, kv_quant="int8", kv_quant_block=4,
        kv_exact_lanes=1, **kw))
    hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run()
    assert sum(h.finish_reason == "error" for h in hs) == 1
    # a fresh stream through the scrubbed slot must be clean (int8
    # agreement with the exact reference is gated elsewhere; here the
    # contract is finite, deterministic output)
    h = eng.submit(prompts[0], max_new_tokens=10)
    eng.run()
    assert h.finish_reason == "length" and len(h.tokens) == 10
    assert_no_leaks(eng)


def test_prefill_poison_quarantines_at_admission():
    model, params = _model()
    prompts = _prompts(2, seed=3)
    plan = [dict(site="prefill", kind="nan", visit=0)]
    eng = ServeEngine(model, params, _cfg(fault_plan=plan))
    hs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run()
    assert hs[0].finish_reason == "error" and hs[0].tokens == []
    assert hs[1].tokens == _ref(model, params, prompts[1], 6)
    assert_no_leaks(eng)


def test_scrubbed_lane_cannot_poison_next_occupant():
    """The quarantine scrub contract: after a NaN quarantine, a fresh
    request admitted into the SAME slot must stream token-exactly —
    0 * NaN is NaN, so an unscrubbed lane would contaminate it through
    the masked attention tail."""
    model, params = _model()
    p0, p1 = _prompts(2, seed=4)
    plan = [dict(site="decode", kind="nan", visit=0, slot=0)]
    eng = ServeEngine(model, params, _cfg(n_slots=1,
                                          max_prefills_per_step=1,
                                          fault_plan=plan))
    h0 = eng.submit(p0, max_new_tokens=10)
    eng.run()
    assert h0.finish_reason == "error"
    h1 = eng.submit(p1, max_new_tokens=10)
    eng.run()
    assert h1.tokens == _ref(model, params, p1, 10), \
        "poison leaked into the quarantined slot's next occupant"
    assert_no_leaks(eng)


# ----------------------------------------------------- systemic recovery


@pytest.mark.parametrize("paged", [False, True])
def test_systemic_failure_rebuilds_and_resumes_exactly(paged):
    model, params = _model()
    prompts = _prompts(3, seed=5)
    plan = [dict(site="decode", kind="xla_error", visit=2)]
    kw = dict(paged=True, page_size=4) if paged else {}
    eng = ServeEngine(model, params, _cfg(
        fault_plan=plan, fault_retry_backoff_s=0.001, **kw))
    hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run()
    for h, p in zip(hs, prompts):
        assert h.tokens == _ref(model, params, p, 10), \
            "rebuild-and-recompute broke a stream"
    snap = eng.metrics.snapshot()
    assert snap["serve/fault_retries"] == 1.0
    assert "serve/fault_recovery_s" in snap
    assert eng.health == "healthy"
    assert_no_leaks(eng)


def test_mid_admission_failure_loses_no_picked_request():
    """Regression: `pick` pops a whole admission batch; a fault raised
    mid-batch (the injected prefill OOM) must requeue the not-yet-
    admitted tail, not leak it out of the queue forever."""
    model, params = _model()
    prompts = _prompts(3, seed=6)
    plan = [dict(site="prefill", kind="oom", visit=0)]
    eng = ServeEngine(model, params, _cfg(
        fault_plan=plan, fault_retry_backoff_s=0.001))
    hs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run()
    assert all(h.done for h in hs), [h.state for h in hs]
    for h, p in zip(hs, prompts):
        assert h.tokens == _ref(model, params, p, 6)
    assert_no_leaks(eng)


def test_persistent_failure_drains_unhealthy_then_recovers():
    model, params = _model()
    p0 = _prompts(1, seed=7)[0]
    # exactly one unhealthy episode: max_retries=1 consumes 2 visits
    plan = [dict(site="decode", kind="xla_error", visit=0, count=2)]
    eng = ServeEngine(model, params, _cfg(
        fault_plan=plan, fault_max_retries=1,
        fault_retry_backoff_s=0.001, fault_recover_backoff_s=0.5,
    ))
    h0 = eng.submit(p0, max_new_tokens=10)
    eng.run()
    assert eng.health == "unhealthy"
    assert h0.finish_reason == "error", "unhealthy drain must fail fast"
    # inside the backoff window: submissions reject with the reason
    hr = eng.submit(p0, max_new_tokens=10)
    assert hr.state == "rejected" and hr.reject_reason == "unhealthy"
    time.sleep(0.55)
    h1 = eng.submit(p0, max_new_tokens=10)
    assert h1.state == "waiting"
    eng.run()
    assert eng.health == "healthy"
    assert h1.tokens == _ref(model, params, p0, 10), \
        "recovered engine lost token-exactness"
    snap = eng.metrics.snapshot()
    assert snap["serve/fault_unhealthy"] == 1.0
    assert_no_leaks(eng)


def test_traced_unhealthy_drain_of_mid_admission_request():
    """Regression: a request whose PREFILL keeps failing has no first
    token when the unhealthy drain force-finishes it — with tracing on,
    _finish must close its lifecycle with a zero-width prefill phase
    instead of subtracting None (which killed the engine loop the
    boundary exists to protect)."""
    model, params = _model()
    p0 = _prompts(1, seed=20)[0]
    plan = [dict(site="prefill", kind="oom", visit=0, count=10)]
    eng = ServeEngine(model, params, _cfg(
        fault_plan=plan, fault_max_retries=1,
        fault_retry_backoff_s=0.001, fault_recover_backoff_s=0.5,
        trace=True,
    ))
    h = eng.submit(p0, max_new_tokens=8)
    eng.run()
    assert eng.health == "unhealthy" and h.finish_reason == "error"
    names = {e.name for e in eng.trace.events()}
    assert {"queue", "prefill", "decode", "unhealthy"} <= names, names
    assert_no_leaks(eng)


def test_healthz_flips_503_while_unhealthy_and_back():
    import urllib.error
    import urllib.request

    model, params = _model()
    p0 = _prompts(1, seed=8)[0]
    plan = [dict(site="decode", kind="xla_error", visit=0, count=2)]
    eng = ServeEngine(model, params, _cfg(
        fault_plan=plan, fault_max_retries=1,
        fault_retry_backoff_s=0.001, fault_recover_backoff_s=0.5,
        status_port=0,
    ))
    try:
        url = eng.status.url("/healthz")
        with urllib.request.urlopen(url, timeout=30) as r:
            assert r.status == 200 and r.read() == b"ok\n"
        eng.submit(p0, max_new_tokens=10)
        eng.run()
        assert eng.health == "unhealthy"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url, timeout=30)
        assert ei.value.code == 503
        assert ei.value.read() == b"unhealthy\n"
        doc_health = eng.statusz()["health"]
        assert doc_health["state"] == "unhealthy"
        assert doc_health["unhealthy_episodes"] == 1
        # past the backoff /healthz flips back to 200 on its own
        # (readiness — a load balancer that dropped the replica on 503
        # must be able to see it recover without routing traffic first)
        time.sleep(0.55)
        with urllib.request.urlopen(url, timeout=30) as r:
            assert r.status == 200, \
                "healthz stayed 503 past the recovery backoff"
        h = eng.submit(p0, max_new_tokens=10)
        eng.run()
        assert h.tokens == _ref(model, params, p0, 10)
        with urllib.request.urlopen(url, timeout=30) as r:
            assert r.status == 200, "recovered engine must answer 200"
    finally:
        eng.close()


# ------------------------------------------------------ liveness bounds


def test_watchdog_flags_stalled_step():
    model, params = _model()
    p0 = _prompts(1, seed=9)[0]
    plan = [dict(site="decode", kind="stall", visit=1, stall_s=0.08)]
    # the watchdog times a step's wall clock, a compile inside it included:
    # warm the programs first, or the test counts 2 wherever it is the first
    # of its worker to run these shapes (xdist hands tests out by load)
    warm = ServeEngine(model, params, _cfg())
    warm.submit(p0, max_new_tokens=10)
    warm.run()
    eng = ServeEngine(model, params, _cfg(
        fault_plan=plan, fault_step_deadline_s=0.04))
    h = eng.submit(p0, max_new_tokens=10)
    eng.run()
    snap = eng.metrics.snapshot()
    assert snap["serve/watchdog_stalls"] == 1.0
    assert h.tokens == _ref(model, params, p0, 10), \
        "a stall must delay, never corrupt"
    assert eng.statusz()["health"]["watchdog_stalls"] == 1


def test_bounded_close_force_cancels_wedged_streams():
    """The SIGTERM contract: close(drain_s) must return promptly even
    when every step stalls — leftover streams force-cancel host-side
    and the pool drains leak-free."""
    model, params = _model()
    p0 = _prompts(1, seed=10)[0]
    plan = [dict(site="decode", kind="stall", visit=0, stall_s=0.2,
                 count=1000)]
    eng = ServeEngine(model, params, _cfg(fault_plan=plan))
    h = eng.submit(p0, max_new_tokens=20)
    eng.step()  # admitted and mid-stream
    t0 = time.monotonic()
    eng.close(drain_s=0.25)
    took = time.monotonic() - t0
    assert h.done and h.finish_reason == "cancelled"
    # bound: the drain window plus at most ONE stalled step's overrun
    assert took < 2.0, f"close took {took:.2f}s — not bounded"
    assert_no_leaks(eng)


# ---------------------------------------------------------- None-pattern


def test_disabled_fault_plane_compiles_no_extra_programs():
    """fault_plan=None keeps the compiled inventory byte-for-byte the
    plain engine's: the registry (which records EVERY program the
    engine runs) shows exactly prefill + decode — no scrub, no fault
    branch — and the always-on finite guard never perturbs streams."""
    model, params = _model()
    prompts = _prompts(2, seed=11)
    eng = ServeEngine(model, params, _cfg(xla_obs=True))
    hs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run()
    names = set(eng.registry.snapshot()["programs"])
    assert names == {"prefill_program", "decode_block"}, names
    for h, p in zip(hs, prompts):
        assert h.tokens == _ref(model, params, p, 8)
    assert eng.health == "healthy"
    # fault keys absent from a fault-free snapshot (key-surface contract)
    snap = eng.metrics.snapshot()
    assert not [k for k in snap if "fault" in k or "watchdog" in k], \
        "fault gauges leaked into a fault-free run's key surface"


# --------------------------------------------------- degradation ladder


def _burn_engine(model, params, **kw):
    """An engine whose SLO targets are impossible on this hardware —
    every finish violates, so the burn-rate pressure signal is
    guaranteed to fire without timing games."""
    targets = {
        "interactive": {"ttft_s": 1e-9, "objective": 0.99},
        "standard": {"ttft_s": 1e-9, "objective": 0.99},
        "batch": {"ttft_s": 1e-9, "objective": 0.9},
    }
    return ServeEngine(model, params, _cfg(
        slo_targets=targets, degrade=True, degrade_up_steps=1,
        degrade_down_steps=4, **kw))


def test_ladder_escalates_on_burn_and_sheds_by_class():
    model, params = _model()
    prompts = _prompts(8, seed=12)
    eng = _burn_engine(model, params)
    from solvingpapers_tpu.serve.sampling import SamplingParams

    for p in prompts[:4]:
        eng.submit(p, max_new_tokens=4)
    eng.run()
    # violations filled the burn window; up_steps=1 climbs one rung per
    # evaluation — idle steps keep evaluating while the window still
    # shows the burn, so drive a few to reach the shedding rungs
    for _ in range(4):
        eng.step()
    assert eng.degradation_rung >= 3, eng.degradation_rung
    assert eng.health == "degraded"
    # batch is shed first; interactive is never shed by the ladder
    hb = eng.submit(prompts[4], max_new_tokens=4,
                    params=SamplingParams(slo="batch"))
    assert hb.state == "rejected" and hb.reject_reason == "shed:batch"
    hi = eng.submit(prompts[5], max_new_tokens=4,
                    params=SamplingParams(slo="interactive"))
    assert hi.state == "waiting"
    eng.run()
    assert hi.done
    snap = eng.metrics.snapshot()
    assert snap["serve/shed_batch"] >= 1.0
    assert snap["serve/degradation_rung"] >= 3.0
    assert snap["serve/degrade_transitions"] >= 3.0
    lad = eng.statusz()["health"]["ladder"]
    assert lad["rung"] == eng.degradation_rung
    assert "batch" in lad["shedding"]
    assert_no_leaks(eng)


def test_ladder_deescalates_in_reverse_with_hysteresis():
    model, params = _model()
    prompts = _prompts(2, seed=13)
    eng = _burn_engine(model, params)
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    eng.run()
    rung0 = eng.degradation_rung
    assert rung0 >= 1
    # clear the pressure: rebuild the burn window with attained
    # finishes by relaxing the targets in place (the tracker object is
    # live state — tests may retune it)
    for spec in eng._slo.targets.values():
        spec["ttft_s"] = 1e9
    for st in eng._slo._stats.values():
        st["window"].clear()
    p_new = _prompts(1, seed=14)[0]
    h = eng.submit(p_new, max_new_tokens=20)
    eng.run()
    assert h.done
    assert eng.degradation_rung < rung0, \
        "ladder never de-escalated after the pressure cleared"
    assert_no_leaks(eng)


def test_ladder_holds_speculation_at_rung_two():
    from solvingpapers_tpu.serve.spec import SpecController

    ctl = SpecController(min_rate=1.0, probe_every=4)
    assert ctl.decide() == "probe"
    ctl.hold(3)
    assert [ctl.decide() for _ in range(3)] == ["off"] * 3
    assert ctl.decide() == "probe"  # hold expired; adaptive state intact
    assert ctl.ema is None and ctl.fallback_steps == 3
