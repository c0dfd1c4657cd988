"""What the two serving drivers share: the engine built as `cli serve`
builds it, the warm-up of the cell's shapes, the drive loop's bookkeeping
(the benchmark's own timestamps around `eng.step()`), and the comparison of
served tokens with the plain reference.
"""

from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from benchmarks import harness, trafficgen

now = time.monotonic  # the engine's clock (serve.metrics.now)


@dataclasses.dataclass(eq=False)
class Track:
    """One request as the load generator saw it. Times are seconds on the
    engine's clock, relative to the window's start."""

    req: trafficgen.Req
    due: float
    submit: float | None = None
    handle: object = None
    first_token: float | None = None  # first step after which a token showed
    deliveries: list = dataclasses.field(default_factory=list)  # (t, n_total)
    finish: float | None = None

    @property
    def n_tokens(self) -> int:
        return self.deliveries[-1][1] if self.deliveries else 0


def build_engine(run: harness.Run):
    """(engine, sizes): `ServeEngine` with the traffic file's
    `engine` settings (the parser defaults of `cli serve` otherwise) over
    weights made from the seed."""
    import jax
    import jax.numpy as jnp

    from solvingpapers_tpu.configs import get_config
    from solvingpapers_tpu.configs.factory import build_model
    from solvingpapers_tpu.serve.engine import ServeConfig, ServeEngine

    config, traffic = run.config, run.traffic
    adapter = harness.load_module("adapters", config["adapter"])
    reference = harness.load_module("reference", config["reference"])
    cfg = get_config(config["registry"])
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, **config["model"]))
    model = build_model(cfg)
    sizes = adapter.sizes_of(model.cfg)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 8), jnp.int32)))
    weights = reference.make_weights(run.seed, sizes)
    params = adapter.to_program_tree(weights, shapes["params"])
    extra = {k: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), v)
             for k, v in shapes.items() if k != "params"}
    eng_cfg = dict(traffic["engine"])
    if run.trace:
        # TraceAnnotations around the engine's program calls, without the
        # flight recorder's fences; the window is never opened by the engine
        eng_cfg.update(profile_dir=run.trace_dir,
                       profile_steps=(2**62, 2**62 + 1))
    eng = ServeEngine(model, params, ServeConfig(seed=0, **eng_cfg),
                      extra_variables=extra or None)
    return eng, sizes


def warm_up(eng, traffic: dict, vocab: int) -> None:
    """One request through every prefill bucket the traffic can hit, all in
    flight together so the decode program runs too; counted as set-up."""
    bucket = traffic["engine"]["bucket"]
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    lengths = sorted({min(hi, max(lo, b))
                      for b in range(bucket, hi + bucket, bucket)})
    rng = np.random.default_rng(0)
    handles = [eng.submit(rng.integers(0, vocab, size=n).astype(np.int32),
                          max_new_tokens=traffic["output_len"]["min"])
               for n in lengths]
    eng.run()
    if not all(h.done and h.finish_reason == "length" for h in handles):
        raise harness.BenchFailure("warm-up requests did not finish")


def step_and_record(eng, live: dict, t0: float, occupancy: list) -> None:
    """One `eng.step()`, then the benchmark's own timestamp for whatever
    it brought: tokens that became visible, requests that finished."""
    eng.step()
    t = now() - t0
    occupancy.append(eng.pool.n_active)
    for key in list(live):
        tr = live[key]
        n = len(tr.handle.tokens)
        if n > tr.n_tokens:
            if tr.first_token is None:
                tr.first_token = t
            tr.deliveries.append((t, n))
        if tr.handle.done:
            tr.finish = t
            del live[key]


def submit(eng, tr: Track, t0: float, live: dict) -> None:
    tr.submit = now() - t0
    tr.handle = eng.submit(tr.req.prompt, max_new_tokens=tr.req.max_new)
    if tr.handle.state == "rejected" or tr.handle.done:
        tr.finish = tr.submit
    else:
        live[id(tr)] = tr


def observe(run: harness.Run, eng, tracks: list[Track], t0: float,
            occupancy: list, n_slots: int) -> None:
    """The window's raw observations, for the metric readers."""
    window = run.obs["window_s"]
    sent = [t for t in tracks if t.submit is not None]
    ok = [t for t in sent if t.handle.finish_reason == "length"
          and t.n_tokens == t.req.max_new]
    in_window = [t for t in ok if t.finish <= window]
    run.attempted = len(sent)
    run.failed = sum(1 for t in sent if t.finish is not None and t not in ok)
    inf = math.inf
    run.obs.update(
        ttft_s=[(t.first_token - t.due) if t.first_token is not None else inf
                for t in sent],
        tpot_s=[(t.deliveries[-1][0] - t.first_token) / (t.n_tokens - 1)
                for t in ok if t.n_tokens > 1],
        late_s=[t.submit - t.due for t in sent],
        queue_wait_s=[(t.handle.admit_time - t0 - t.due)
                      if t.handle.admit_time is not None else inf
                      for t in sent],
        decode_gap_max_s=[max((b[0] - a[0] for a, b in
                               zip(t.deliveries, t.deliveries[1:])),
                              default=0.0) for t in ok if t.n_tokens > 1],
        tokens_completed=sum(t.n_tokens for t in in_window),
        requests_completed=len(in_window),
        occupancy=[n / n_slots for n in occupancy],
        prefill_module="jit__prefill_program",
        decode_module="jit__decode_program",
    )
    run.note(phase="window", window_s=window, sent=len(sent),
             completed_in_window=len(in_window), failed=run.failed,
             ttft_p50_ms=1e3 * trafficgen.percentile(run.obs["ttft_s"], 50),
             tpot_p50_ms=1e3 * trafficgen.percentile(run.obs["tpot_s"], 50),
             mean_occupancy=float(np.mean(run.obs["occupancy"] or [0])))


HOST_SPANS = ("prefill*", "decode*", "splice*", "spec*")


def check_served(run: harness.Run, tracks: list[Track], sizes) -> None:
    """After the engine is freed: a sample, drawn from the seed, of the
    requests the window finished, the longest among them; one reference
    pass over each prompt with its served tokens; the widest gap by which a
    served token's logit lies below the reference's best."""
    config = run.config
    reference = harness.load_module("reference", config["reference"])
    done = [t for t in tracks if t.finish is not None and t.n_tokens > 0
            and t.handle.finish_reason == "length"]
    if not done:
        raise harness.BenchFailure("the window finished no request")
    rng = np.random.default_rng(run.seed)
    n = min(run.traffic["check_requests"], len(done))
    longest = max(done, key=lambda t: len(t.req.prompt) + t.n_tokens)
    rest = [t for t in done if t is not longest]
    picks = [longest] + [rest[i] for i in
                         rng.permutation(len(rest))[:n - 1]]
    served = [(t.req.prompt, np.asarray(t.handle.tokens, np.int32))
              for t in picks]
    weights = reference.make_weights(run.seed, sizes)
    worst, n_tokens, exact = 0.0, 0, 0
    for prompt, toks in served:
        gaps = reference.served_gaps(weights, sizes, prompt, toks)["gap"]
        worst = max(worst, float(gaps.max()))
        n_tokens += len(toks)
        exact += int((gaps == 0).sum())
    run.note(phase="reference_numbers", requests=len(served),
             tokens=n_tokens, exact_share=exact / n_tokens)
    run.compare("served_logit_gap", worst,
                config["limits"]["serve"]["served_logit_gap"])


def start(run: harness.Run):
    """Set-up shared by the serving drivers: (engine, sizes), warmed."""
    eng, sizes = build_engine(run)
    run.phase("build_and_weights")
    warm_up(eng, run.traffic, sizes.vocab)
    run.phase("warm_up")
    return eng, sizes


def finish(run: harness.Run, eng, tracks: list[Track], t0: float,
           occupancy: list, live: dict, sizes) -> None:
    """After the window closed: drain what is in flight (outside the
    window, so that every request sent has its times), reduce, free the
    engine, compare with the reference."""
    n_window_steps = len(occupancy)
    while eng.has_work():
        step_and_record(eng, live, t0, occupancy)
    observe(run, eng, tracks, t0, occupancy[:n_window_steps],
            run.traffic["engine"]["n_slots"])
    if run.trace:
        run.reduce_trace(HOST_SPANS, "host_between_programs")
    run.phase("window_and_reduction")
    eng.close()
