"""Continuous-batching serving engine: the long-lived mixed prefill/decode
step over a slot pool.

`infer.decode.generate` is one static batch to completion — a new request
waits for the whole previous batch. `ServeEngine` instead advances a pool
of S independent slots one iteration at a time (Orca-style iteration-level
scheduling): each `step()` admits waiting requests into free lanes
(chunked prefill, same end-aligned attend_len contract as `generate`),
then advances every active slot by a block of single-token steps, emitting
per-request token streams as they materialize. A slot freed by an
early-EOS sequence is re-acquired by the next queued request immediately
— the batch never drains.

Static shapes throughout (XLA requirement): the batch dimension of every
jitted program is the slot count, inactive slots run masked dummy steps
(their writes land in lane slot 0, overwritten by the next prefill;
masked-softmax zeros annihilate stale finite values exactly — see
`serve/kv_pool.py`). Per-slot positions are made possible by `vmap`ping a
batch-1 single-token apply over the slot axis: the models' cached
attention writes at ``positions[0, 0]`` (one scalar per call), and under
vmap that scalar is per-slot — so every decoder family (gpt, llama3,
gemma, deepseekv3) serves unmodified.

Compiled-program inventory (bounded by construction): ONE decode program
(every block runs the full `decode_block`; a slot that hits EOS or its
budget mid-block keeps stepping and the host discards its overshoot —
the wasted writes stay inside that slot's own lane, which the next
prefill overwrites), one prefill program per prompt bucket (prompts pad
right to a multiple of ``bucket``; the pad region is causally invisible
to real tokens and its cache slots are overwritten by the decode stream
before ever being attended).

Paged KV pool (`ServeConfig.paged`, `serve/kv_pool.py PagedKVPool`):
instead of one contiguous `max_len` lane per slot, the cache is a
physical pool of fixed-size KV pages with per-slot page tables; the
jitted programs gather the logical lane view from the page table (which
rides the existing packed control transfer), run the models unmodified,
and scatter back only written pages. HBM is booked per page, slot count
decouples from max_seq, the scheduler admits on a PAGE budget (free
pages must cover prompt + a decode reservation), and a stream that
outgrows the pool is preempted — pages freed, request requeued at the
head, KV recomputed on resume (token streams unchanged). The lane pool
stays the default.

Cross-request prefix reuse (`serve/prefix_cache.py`, opt-in via
`ServeConfig.prefix_cache` — see its docstring for the cost model):
admission first reuses the longest cached page-aligned prompt prefix —
the lane pool splices it into the freed lane (copy-on-acquire — one
fused dynamic_update_slice program per segment), the paged pool appends
the cached PHYSICAL page ids to the slot's page table (a refcount bump:
zero device copies, no program dispatched) — and prefills only the
uncovered suffix from position `matched`, then hands the prompt's
prefix back to the radix tree (snapshot copy vs page-id reference,
respectively). Cached KV at position p depends only on tokens <= p, so
greedy streams are token-exact with the cache on or off.

Speculative decoding (`serve/spec.py`, opt-in via
`ServeConfig.speculative`): the decode block becomes per-slot
draft-and-verify rounds — a drafter (n-gram prompt-lookup over a
history buffer riding the packed control transfer, or the DeepSeek-V3
MTP heads) proposes up to `spec_k` tokens per slot, one chunked
forward evaluates the whole `1 + spec_k` window, and verification
commits a variable number of tokens per round. Greedy slots verify by
exact argmax match (streams stay byte-identical to spec-off serving
and one-shot `generate`); stochastic slots use lossless rejection
sampling against `fused_sample`'s truncated distributions; grammar
slots ride along draft-free. Draft length is traced per slot — mixed
spec/non-spec batches share one compiled decode program — and a
host-side adaptive controller falls back to the plain block while
drafts keep rejecting.

Per-request sampling (`serve/sampling.py`): every request carries
`SamplingParams` (temperature / top-k / top-p / min-p / seed / stop sets /
logprobs). The knobs live in slot-major struct-of-arrays mirrors packed
into the jitted programs as TRACED control operands — one fused
`fused_sample` serves the whole slot axis, so a greedy request and a
temperature-1.2/top-p-0.9 request coexist in one vmapped decode block
with zero extra compiled programs. Greedy slots (temperature 0) are
token-exact vs per-request one-shot greedy `generate`
(tests/test_serve.py, tests/test_prefix_cache.py,
tests/test_serve_sampling.py); a seeded stochastic slot replays the same
stream run-to-run (its rng chain folds only (seed, sample index) into the
engine's base key — never the slot or step counter).

Request lifecycle: `cancel()` and per-request deadlines free the lane at
the next block boundary (finish reasons eos / length / stop / cancelled /
timeout, counted in `ServeMetrics`); stop strings are matched host-side
on the detokenized stream (matches may span block boundaries); stop
token-id sets extend single-id EOS host-side.

Observability (`metrics/trace.py`, opt-in via `ServeConfig.trace`): a
flight recorder captures per-request lifecycle spans, per-step batch
composition, and scheduler/prefix-cache events into a bounded ring;
export to Perfetto with `engine.trace.export_chrome(path)`, rebuild
timelines with `cli trace-summary`, and arm post-mortem anomaly dumps
with `trace_dump_path` — see the ServeConfig docstring and the README's
Observability section.

Compile & memory observatory (`metrics/xla_obs.py`, opt-in via
`ServeConfig.xla_obs`): every jitted program routes through a compile
registry that records each XLA compilation (signature, wall time,
cost_analysis flops/bytes) and flags recompile storms, while an HBM
ledger accounts per-pool live bytes and projected peak vs device
capacity; `ServeConfig.status_port` serves the live /healthz /metrics
/statusz endpoint (`metrics/http.py`).

Fault tolerance (`serve/faults.py`; always on — real NaN forwards and
device runtime errors need no opt-in): every `step()` runs inside a
supervised fault boundary. A traced per-slot finite-logits guard pins
NaN/Inf forwards to their slot, which is QUARANTINED — block output
discarded, lane/pages scrubbed to zero before release (0 * NaN is NaN;
the stale-lane contract only covers finite values), request finished
``"error"``, every other stream byte-identical. Systemic failures
(XlaRuntimeError / OOM / anything escaping a program call) cost a
bounded pool-rebuild retry — active streams requeue and resume by
recompute, token-exactly — then drain the engine to a 503-reporting
`unhealthy` state until a backed-off recovery. `ServeConfig.fault_plan`
arms the deterministic seeded fault-injection plane (None-pattern off),
`fault_step_deadline_s` the stalled-step watchdog, and
`ServeConfig.degrade` the SLO/ledger-driven degradation ladder (shed
prefix leaves -> hold speculation -> load-shed admissions by class with
jittered Retry-After; hysteresis both ways).

Durable serving (`serve/journal.py`, opt-in via
`ServeConfig.journal_path`): a request write-ahead journal records
submit/commit/finish events (commits once per decode-block boundary,
fsync batched once per step) with atomic live-set compaction; on boot,
`ServeEngine.recover()` replays unfinished entries through the
preemption-resume machinery — greedy and seeded plain-path streams
continue TOKEN-EXACT across a process kill — and the HTTP front door
resumes SSE streams from `Last-Event-ID`. Journal I/O failures degrade
to journal-off with one warning (serving outlives its durability
plane) unless `journal_strict` escalates them.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
import uuid
import warnings

import jax
import jax.numpy as jnp
import numpy as np

# imported for the side effect too: buildinfo stamps its process-start
# clock at FIRST import, and /statusz's uptime_s should measure from
# engine-module load (≈ serving-process start), not from whenever the
# first status probe happened to lazily import it
from solvingpapers_tpu import buildinfo
from solvingpapers_tpu.serve import metrics as smetrics
from solvingpapers_tpu.serve.faults import (
    FAULT_INF,
    FAULT_NAN,
    DegradationLadder,
    FaultPlan,
    InjectedFault,
    classify_failure,
)
from solvingpapers_tpu.serve.grammar import encode_allow
from solvingpapers_tpu.serve.journal import Journal, JournalError
from solvingpapers_tpu.serve.kv_pool import (
    TRASH_PAGE,
    KVSlotPool,
    PagedKVPool,
    QuantStore,
    extract_lane,
    gather_lane,
    gather_lanes,
    pad_time,
    quant_gather_lane,
    quant_gather_lanes,
    quant_lane_view,
    quant_lanes_view,
    quant_pool_bytes,
    quant_scatter_lane_pages,
    quant_scatter_window_pages,
    quant_scatter_written_pages,
    quant_store_exact_lanes,
    quant_store_lane,
    quant_store_written,
    scatter_lane_pages,
    scatter_window_pages,
    scatter_written_pages,
    scrub_lane_program,
    scrub_pages_program,
    store_lane,
    strip_time,
)
from solvingpapers_tpu.serve.metrics import ServeMetrics
from solvingpapers_tpu.serve.prefix_cache import PrefixCache
from solvingpapers_tpu.serve.sampling import (
    GREEDY_ROW,
    PackedSampling,
    SamplingParams,
    encode_params,
    fused_sample,
    request_key,
    slot_keys,
)
from solvingpapers_tpu.serve.scheduler import (
    ACTIVE,
    FINISHED,
    REJECTED,
    WAITING,
    FIFOScheduler,
    Request,
)
from solvingpapers_tpu.serve.spec import (
    DRAFTERS,
    SpecController,
    ngram_drafts,
    round_keys,
    spec_verify,
)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine shape/policy knobs.

    `decode_block` amortizes host dispatch: each decode program advances
    all slots `block` tokens in one `lax.scan` before the host looks at
    the stream again (termination granularity = one block; EOS discovered
    mid-block discards the padded tail, matching `generate`'s
    pad-with-EOS semantics). `bucket` quantizes prefill lengths so the
    number of compiled prefill programs stays bounded — use a multiple of
    128 for `use_flash` models (the Pallas q-block constraint).

    Flight recorder (`metrics/trace.py`, opt-in via `trace`): the engine
    records per-request lifecycle spans (queue / prefill / decode, one
    track per KV slot, one flow per request), per-step composition
    (prefills vs decode slots, control-array transfers, device vs host
    time via `block_until_ready` fencing — the fence only exists when
    tracing is on), and scheduler/prefix-cache events into a bounded
    ring (`trace_capacity` events). Export with
    `engine.trace.export_chrome(path)` and open in Perfetto, or rebuild
    timelines with `cli trace-summary`. `trace_dump_path` arms the
    anomaly dumper: timeout/cancelled finishes, `trace_reject_burst`
    consecutive rejections, or a step exceeding `trace_slow_step_factor`
    x the rolling median step time append the last `trace_dump_events`
    events + a `ServeMetrics.snapshot()` to that JSONL file. With
    `trace` off every hook site is one `is None` branch; what it costs
    when on is not measured on the chip.

    Profiler (`profile_dir`): opens a `jax.profiler.trace` window around
    engine steps [`profile_steps[0]`, `profile_steps[1]`) with
    `TraceAnnotation` scopes around the prefill/decode/splice programs,
    so engine phases are visible inside the XLA trace (view in
    TensorBoard / Perfetto).

    Prefix cache (`serve/prefix_cache.py`): with `prefix_cache` on, each
    admitted request splices its longest cached page-aligned prompt
    prefix into the lane and prefills only the uncovered suffix (start
    position = matched length; the suffix pads to `bucket` as before, so
    compiled prefill programs stay bounded by (page multiples x
    buckets)). `prefix_cache_bytes` caps the HBM the radix tree may hold
    (LRU leaf eviction; refcounted nodes are never evicted);
    `prefix_page` is the match/segment granularity. `prefix_sched` makes
    the scheduler prefer waiting requests with the shortest uncovered
    suffix (the existing anti-starvation wait budget still overrides).
    Greedy streams are token-exact with the cache on or off. Opt-in:
    every admission pays a match + snapshot copy and the tree holds up
    to `prefix_cache_bytes` of HBM, which is pure overhead on traffic
    with no shared prefixes (not measured on the chip) — turn it
    on when prompts share stems (system prompts, few-shot, multi-turn).
    """

    n_slots: int = 8
    max_len: int = 512
    decode_block: int = 8
    bucket: int = 64
    # Paged KV pool (serve/kv_pool.py PagedKVPool, vLLM-PagedAttention
    # style): one physical pool of `page_budget` fixed-size KV pages +
    # per-slot page tables instead of contiguous max_len lanes. HBM is
    # booked per PAGE actually needed, so slot count decouples from
    # max_len (more concurrent slots at equal HBM), and the prefix
    # cache shares pages
    # zero-copy by refcount (a full-page hit dispatches NO device
    # program). Admission moves from slot-count to page-budget
    # accounting: a request is admitted while free pages cover its
    # prompt + a decode-block reservation, and a growing stream that
    # exhausts the pool preempts the youngest request
    # (requeue-and-recompute; greedy/seeded streams are unchanged —
    # resume re-prefills prompt + emitted tokens and the rng chain
    # folds only (seed, sample index)).
    #   page_size   tokens per page; defaults to `prefix_page` so tree
    #               edges align with physical pages (required when both
    #               paged and prefix_cache are on — zero-copy sharing
    #               needs the alignment). max_len must be a multiple.
    #   page_budget allocatable pages; None = n_slots * (max_len /
    #               page_size), the lane-pool-equivalent HBM. Shrink it
    #               (or raise n_slots) to trade worst-case headroom for
    #               concurrency — the whole point of paging.
    paged: bool = False
    page_size: int | None = None
    page_budget: int | None = None
    # Quantized KV storage (ops/quant.py + serve/kv_pool.py QuantStore):
    # the pool holds symmetric int8 payload + per-block f32 absmax
    # scales instead of the compute dtype — roughly HALF the resident KV
    # bytes (vs bf16; a quarter vs f32), i.e. ~2x the servable slots or
    # context at the same HBM budget. The jitted programs dequantize on read
    # (gather/extract sites materialize the familiar compute-dtype lane
    # view — models serve unmodified) and quantize on write (store/
    # scatter sites requantize only the blocks/pages the step wrote).
    # Output is close to the exact pool's, not equal: `cli replay
    # --config-overrides kv_quant=int8` scores the greedy-token agreement
    # of recorded streams (not measured on the chip).
    #   kv_quant        None = exact storage (today's pools, untouched
    #                   code paths); "int8" = quantized payload + scale
    #                   sidecar in BOTH pool layouts. The prefix cache
    #                   stores int8 pages/segments + scales (sharing
    #                   stays zero-copy on the paged pool — scales ride
    #                   the page ids). Excludes speculative="mtp" (its
    #                   head-cache lanes are a separate follow-on).
    #   kv_quant_block  lane-pool scale granularity: one f32 absmax
    #                   scale per (slot, kv_quant_block tokens, head)
    #                   — must divide max_len (and prefix_page when the
    #                   lane-pool prefix cache is on). The paged pool
    #                   always scales per (page, head) so scales ride
    #                   the page tables.
    #   kv_exact_lanes  per-request escape hatch capacity: a request
    #                   with SamplingParams.kv_exact serves from one of
    #                   this many full-precision sidecar lanes (plus a
    #                   trash lane), byte-identical to the unquantized
    #                   engine, INSIDE the same compiled programs as
    #                   quantized traffic (the lane index rides the
    #                   packed control rows). 0 (default) books no
    #                   sidecar — pure capacity win — and kv_exact
    #                   submissions are rejected. Exact requests bypass
    #                   the (quantized) prefix cache and never consume
    #                   pages.
    kv_quant: str | None = None
    kv_quant_block: int = 16
    kv_exact_lanes: int = 0
    # Speculative decoding (serve/spec.py): per-slot draft-and-verify
    # inside the decode program. Each decode step runs `spec_rounds`
    # draft-verify rounds: a drafter proposes up to `spec_k` tokens per
    # slot, ONE chunked forward computes the model's distributions over
    # the 1+k-token window, and verification commits 1..k+1 tokens per
    # round — greedy slots by exact argmax match (streams stay
    # byte-identical to spec-off serving and one-shot generate),
    # stochastic slots by rejection sampling against fused_sample's
    # truncated distributions (per-request output distributions provably
    # unchanged), grammar-constrained slots ride along draft-free (one
    # token per step, the stale-mask contract). Draft length is traced
    # per slot, so mixed spec/non-spec batches share ONE compiled decode
    # program.
    #   speculative  None = off; "ngram" = model-free prompt-lookup
    #                self-drafter (device-side lookup over a history
    #                buffer riding the packed control transfer — any
    #                family, either pool); "mtp" = DeepSeek-V3
    #                multi-token-prediction heads (infer/speculative.py
    #                mechanics vmapped over slots; deepseekv3 family,
    #                lane pool, no prefix cache — the head cache has no
    #                hidden states for spliced prefixes)
    #   spec_k       draft tokens per round (chunk width 1 + spec_k);
    #                "mtp" clamps to the model's trained head count
    #   spec_rounds  draft-verify rounds per decode call (None =
    #                decode_block); each call commits between
    #                spec_rounds and spec_rounds * (1 + spec_k) tokens
    #                per slot
    #   spec_ngram   longest tail n-gram the lookup drafter tries
    #                (falls back n, n-1, ..., 1)
    #   spec_min_rate / spec_probe_every  the adaptive controller
    #                (serve/spec.py SpecController): acceptance below
    #                spec_min_rate ACCEPTED DRAFTS PER ROUND drops the
    #                engine to plain blocks for spec_probe_every steps
    #                (doubling on every failed cheap probe, capped), so
    #                zero-acceptance adversarial traffic pays a few
    #                short probes instead of chunked blocks every step.
    #                None scales the threshold with the chunk width
    #                (max(1, spec_k / 4)): each round forwards 1+k
    #                positions, so the acceptance worth paying for
    #                grows with k
    speculative: str | None = None
    spec_k: int = 4
    spec_rounds: int | None = None
    spec_ngram: int = 3
    spec_min_rate: float | None = None
    spec_probe_every: int = 8
    # static support bound for stochastic sampling (clamped to the vocab):
    # fused_sample draws inside the top `sample_cap` logits per step —
    # bounded-support sampling keeps the per-step cost at one top-k
    # selection instead of full-vocab sorts (~100x the forward on
    # XLA:CPU). Requests' top_k must fit under it (submit validates);
    # raise it (up to the vocab size) for exact full-support sampling.
    sample_cap: int = 64
    # SLO accounting (serve/slo.py, opt-in): per-class latency targets,
    # {class: {"ttft_s"/"itl_s"/"e2e_s": seconds, "objective": frac}} —
    # pass `serve.slo.DEFAULT_SLO_TARGETS` for the reference
    # interactive/standard/batch tier set. When set, every finish is
    # accounted under its request's `SamplingParams.slo` class (default
    # "standard", which the dict must define): per-class attainment,
    # error-budget burn rate, and goodput (tokens from SLO-attained
    # requests only) ride the snapshot as slo/* +
    # serve/goodput_tokens[_per_s] gauges and the /statusz `slo`
    # section. None = off: no gauges, and slo-tagged submissions are
    # rejected (the tag would silently account to nothing).
    slo_targets: dict | None = None
    # finishes in the sliding window the burn rate is computed over
    slo_burn_window: int = 256
    # Fault tolerance (serve/faults.py; see that module's docstring for
    # the failure taxonomy). The supervised step loop is ALWAYS on —
    # every step() runs inside a fault boundary that quarantines
    # NaN/Inf-poisoned slots (finish_reason "error", leak-free reclaim,
    # other streams byte-identical) and answers systemic device
    # failures with bounded pool-rebuild retries, then a draining
    # `unhealthy` state /healthz reports as 503 until recovery. The
    # knobs below tune the boundary; `fault_plan` arms the DETERMINISTIC
    # seeded fault-injection plane (None-pattern off, like the tracer):
    #   fault_plan       sequence of serve.faults.FaultSpec (or dicts):
    #                    named sites (prefill/decode/scatter/
    #                    prefix_splice/sse_write) x kinds (nan/inf
    #                    logits poison, synthetic xla_error/oom, stall,
    #                    socket_reset), each firing at an exact visit of
    #                    its site — so every recovery path is testable
    #                    on CPU, bit-reproducibly. None = off: the hot
    #                    path pays one `is not None` branch per site.
    #   fault_max_retries  consecutive pool-rebuild retries a systemic
    #                    failure may consume before the engine drains to
    #                    `unhealthy` (in-flight streams finish "error")
    #   fault_retry_backoff_s  base sleep between rebuild retries
    #                    (doubles per consecutive failure)
    #   fault_recover_backoff_s  how long an unhealthy engine waits
    #                    before accepting work again (doubles across
    #                    repeated unhealthy episodes until a clean step)
    #   fault_step_deadline_s  watchdog: a step exceeding this absolute
    #                    wall deadline is flagged (serve/watchdog_stalls
    #                    counter, trace instant, anomaly dump when the
    #                    dumper is armed). None = off.
    fault_plan: object | None = None
    fault_max_retries: int = 2
    fault_retry_backoff_s: float = 0.05
    fault_recover_backoff_s: float = 0.25
    fault_step_deadline_s: float | None = None
    # Request write-ahead journal (serve/journal.py, opt-in via
    # journal_path — the None-pattern, like the tracer and the fault
    # plane): an fsync'd append-only JSONL journal recording submit
    # (prompt ids + full SamplingParams incl. seed + SLO class +
    # arrival), commit (committed token ids, once per decode-block
    # boundary riding the host-mirror drain — never per token) and
    # finish (reason + usage) events, with atomic tmp+rename live-set
    # compaction so the file stays O(active requests). On boot,
    # `ServeEngine.recover()` replays unfinished entries through the
    # preemption-resume machinery: greedy/seeded recovered streams are
    # TOKEN-EXACT vs an uninterrupted run (seeded chains fold only
    # (seed, sample index)). fsync is batched once per engine step, so
    # a SIGKILL loses at most one step's records.
    #   journal_path     JSONL journal file; an existing file is LOADED
    #                    (the recovery source), then appended. None =
    #                    off: one `is not None` branch per hook.
    #   journal_strict   journal I/O failures (disk full; injected via
    #                    the fault plane's journal_write/io_error site)
    #                    normally degrade to journal-off with a single
    #                    warning and the serve/journal_degraded gauge —
    #                    serving survives, durability is lost and SAYS
    #                    so. strict=True propagates the failure instead
    #                    (a deployment that REQUIRES durability fails
    #                    loudly rather than silently serving without it).
    #   journal_rotate_bytes / journal_rotate_finished   compaction
    #                    triggers: rewrite to the live set once this
    #                    many bytes / finish records accumulate.
    journal_path: str | None = None
    journal_strict: bool = False
    journal_rotate_bytes: int = 4 << 20
    journal_rotate_finished: int = 256
    # Degradation ladder (serve/faults.py DegradationLadder, opt-in):
    # under sustained pressure — paged-pool page exhaustion
    # (pages_free below degrade_free_page_frac of the budget),
    # HBM-projection breach (the xla_obs ledger's projected peak within
    # degrade_headroom_frac of capacity), or SLO error-budget burn
    # (any class's burn rate above degrade_burn_threshold) — the
    # engine climbs one rung at a time: shed prefix-cache leaves ->
    # hold speculation -> load-shed admissions by SLO class (batch
    # first, then standard; shed submissions reject with a jittered
    # Retry-After through the front door). Escalation needs
    # degrade_up_steps consecutive pressured steps, de-escalation
    # degrade_down_steps clear ones (hysteresis — the ladder cannot
    # flap), and recovery re-arms in reverse order. Each rung is the
    # serve/degradation_rung gauge; each transition a trace instant.
    degrade: bool = False
    degrade_up_steps: int = 2
    degrade_down_steps: int = 16
    degrade_free_page_frac: float = 0.125
    degrade_burn_threshold: float = 1.5
    degrade_headroom_frac: float = 0.05
    prefill_chunk: int | None = None
    max_waiting: int = 256
    decode_priority: bool = True
    max_prefills_per_step: int = 1
    max_wait_steps: int = 64
    eos_id: int | None = None  # default per-request EOS (None = run to budget)
    seed: int = 0
    prefix_cache: bool = False
    prefix_page: int = 16
    prefix_cache_bytes: int = 64 << 20
    prefix_sched: bool = False
    # flight recorder (metrics/trace.py); see the class docstring above
    trace: bool = False
    trace_capacity: int = 65536
    trace_dump_path: str | None = None  # anomaly JSONL; requires trace=True
    trace_dump_events: int = 256
    trace_slow_step_factor: float = 10.0
    trace_reject_burst: int = 8
    # rolling in-process time series (metrics/timeseries.py): a fixed-
    # budget ring of periodic metric samples (gauges + per-window
    # counter/histogram deltas), sampled opportunistically from step()
    # — no timer thread. Served as /timeseriesz JSON + /statusz
    # sparklines and attached to every anomaly dump, so a quarantine/
    # degradation/drain artifact carries the preceding N-window of
    # engine state. On by default: capacity x interval bounds memory
    # at O(capacity x n_series) floats (~2 minutes at the defaults).
    timeseries: bool = True
    timeseries_capacity: int = 120
    timeseries_interval_s: float = 1.0
    # jax.profiler window over engine steps [start, stop)
    profile_dir: str | None = None
    profile_steps: tuple[int, int] = (10, 15)
    # compile & memory observatory (metrics/xla_obs.py, opt-in): every
    # jitted program routes through a CompileRegistry (records each XLA
    # compilation's signature, wall time, cost_analysis flops/bytes and
    # memory_analysis temp bytes; flags recompile storms — same program,
    # >= obs_storm_k NEW signatures inside obs_storm_window_s — through
    # the AnomalyMonitor when trace_dump_path is armed) and an HBMLedger
    # tracks per-pool live bytes (params / kv_pool / prefix_cache) plus
    # projected decode-step peak vs device capacity, warning before the
    # projection exceeds it. Gauges ride ServeMetrics.snapshot() as
    # compile/* + mem/* + roofline/* keys. Observability mode: program
    # calls are fenced for device-true run seconds (same contract as
    # `trace`; cost not measured on the chip); off = None registry, one
    # branch per call site.
    # The registry also parses every compiled program's HLO text into
    # the per-op-category anatomy ledger (metrics/hlo_cost.py —
    # gather/scatter/dot/convert/... flops + output-shape bytes, top-k
    # heaviest ops), surfaced as /statusz `compile.programs.<name>.
    # anatomy`, compile-event args on the flight recorder, and the
    # trace-summary "anatomy" section. obs_hlo_dir optionally dumps
    # each TRUE compile's HLO text (atomic tmp+rename, one file per
    # signature, sanitized program names) so anatomy claims can be
    # diffed offline.
    xla_obs: bool = False
    obs_hlo_dir: str | None = None
    obs_storm_k: int = 8
    obs_storm_window_s: float = 60.0
    # device capacity override for the headroom estimate (bytes); None =
    # ask the backend (memory_stats()["bytes_limit"]; CPU reports none,
    # so headroom gauges are simply absent there)
    obs_capacity_bytes: int | None = None
    # live status endpoint (metrics/http.py, opt-in): /healthz, /metrics
    # (Prometheus text of the current snapshot), /statusz (engine + slot
    # occupancy + compile registry + memory ledger JSON) on a daemon
    # thread bound to status_host. Port 0 = ephemeral (published as
    # engine.status.port); None = no server. Close with engine.close().
    status_port: int | None = None
    status_host: str = "127.0.0.1"
    # OpenAI-compatible HTTP front door (serve/api.py — started by `cli
    # serve` or `serve.api.ApiServer`, NEVER by the engine itself: the
    # API server owns the step-loop thread and the shutdown ordering).
    # Knobs live here so ONE config object describes a serving process:
    #   api_port    port for /v1/completions + /v1/chat/completions
    #               (plus /healthz /metrics /statusz on the same
    #               listener); 0 = ephemeral, published as
    #               ApiServer.port
    #   api_host    bind address (loopback by default — an inspection/
    #               demo surface; front with a real proxy to expose it)
    #   api_max_connections  concurrent streaming connections before
    #               the front door answers 503 (per-connection
    #               backpressure AHEAD of the scheduler's bounded
    #               waiting queue, which 503s the overflow after it)
    #   json_mode   accept `response_format {"type": "json_object"}`:
    #               grammar-constrained decoding via the (S, sample_cap)
    #               allow-mask (serve/grammar.py); constrained slots
    #               share the one compiled decode program but advance
    #               ONE token per decode block (the mask rides the
    #               per-call control transfer and is stale after the
    #               first draw), so JSON-mode throughput is ~1/block of
    #               unconstrained — size decode_block accordingly
    #   stream_queue  per-connection pending stream events before
    #               coalescing (events carry counts, not payloads — a
    #               slow SSE reader never blocks the engine thread)
    #   drain_timeout_s  ApiServer.close(): seconds to wait for active
    #               streams to finish before cancelling them (shutdown
    #               order: drain streams -> engine.close() -> HTTP
    #               threads)
    api_port: int | None = None
    api_host: str = "127.0.0.1"
    api_max_connections: int = 64
    json_mode: bool = True
    stream_queue: int = 256
    drain_timeout_s: float = 10.0


_UNSET = object()


def _inject_fault(logits, fault):
    """Apply the fault-injection plane's logits poison (traced): `fault`
    is the i32 code riding the packed control transfer — 0 clean,
    FAULT_NAN / FAULT_INF poison the slot's whole logits row. An
    all-zero fault operand selects `logits` bitwise unchanged, so the
    disabled plane is a numeric no-op (fault-free streams stay
    token-exact) and costs no extra compiled program — the fault row is
    always part of the signature."""
    f = jnp.asarray(fault)
    mask = (f > 0).reshape(f.shape + (1,) * (logits.ndim - f.ndim))
    bad = jnp.where(f == FAULT_NAN, jnp.nan, jnp.inf).astype(logits.dtype)
    bad = bad.reshape(mask.shape)
    return jnp.where(mask, bad, logits)


def _finite_ok(logits):
    """Per-slot finite-logits guard (traced): True iff every logit the
    sampler would draw from is finite. One cheap reduction riding the
    program's existing outputs — the host pins a NaN/Inf forward to its
    slot with zero extra transfers."""
    axes = tuple(range(1, logits.ndim)) or None
    return jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=axes)


def _prefill_lane(model, padded, chunk, start, variables, lane, prompt,
                  length):
    """Shared chunked-prefill core: run `prompt` (right-padded to
    `padded`) through a batch-1 `lane` from position `start`, returning
    the updated lane and the logits row of the LAST REAL token (index
    `length - 1`, gathered from whichever chunk contains it). Both pool
    layouts call this — the lane pool on an extracted lane, the paged
    pool on a gathered page-table view — so the prefill semantics
    (end-aligned attend_len, pad invisibility) cannot drift between
    them."""
    toks = prompt[None, :]
    step = chunk or padded
    last = None
    for cs in range(0, padded, step):
        ce = min(cs + step, padded)
        tok_chunk = jax.lax.slice_in_dim(toks, cs, ce, axis=1)
        positions = jnp.broadcast_to(
            jnp.arange(start + cs, start + ce), (1, ce - cs)
        )
        logits, lane = model.apply(
            variables, tok_chunk, positions=positions, caches=lane,
            deterministic=True, attend_len=start + ce,
        )
        idx = jnp.clip(length - 1 - cs, 0, ce - cs - 1)
        row = jax.lax.dynamic_index_in_dim(logits[0], idx, axis=0,
                                           keepdims=False)
        sel = (length - 1 >= cs) & (length - 1 < ce)
        last = row if last is None else jnp.where(sel, row, last)
    return lane, last


@functools.partial(
    jax.jit,
    static_argnames=("model", "padded", "chunk", "start", "cap"),
    donate_argnames=("caches",),
)
def _prefill_program(model, padded, chunk, start, cap, variables, caches,
                     prompt, ctl, samp, rng):
    """Prefill one request into lane `ctl[0]` and sample its first token.

    `prompt` is (padded,) right-padded; `ctl = [slot, length, step,
    top_k, seed, need_lp, *allow_row]` is the host's packed int control
    word (one transfer instead of many), where `length` is the real token count, so
    one compiled program serves every prompt in the bucket.
    `allow_row` is the (cap,) grammar allow-list for the FIRST sampled
    token (-1-padded; all -1 = unconstrained — see serve/grammar.py). `samp = [temperature, top_p, min_p]` is
    the float half of the request's SamplingParams — every sampling knob
    is a traced operand, so the compiled inventory is untouched by the
    param mix (`cap` = ServeConfig.sample_cap is static but fixed per
    engine).
    `rng` is the engine's base key; the first token is sample index 0 of
    the request's chain (see `serve.sampling.request_key`). Chunks mirror
    `generate`'s static-bound python loop; the logits row for the LAST
    REAL token is gathered from whichever chunk contains it (padding
    makes that not-necessarily-the-last chunk).

    `start` (static) is the prefix-cache match length: `prompt` is the
    UNCOVERED SUFFIX, cache slots [0, start) already hold the spliced
    prefix KV, and positions/attend_len shift by `start` — the same
    end-aligned contract, so chunk i attends causally over every written
    slot [0, start + end_i). `start=0` is a full prefill. Static because
    `attend_len` drives a static slice; start values are page multiples,
    keeping the compiled inventory bounded.

    Quantized pools (`caches` a `QuantStore` — a TRACE-TIME branch, so
    the unquantized program graph is untouched): the lane view is
    dequantized out of the slot's int8 + scale rows (or substituted from
    the exact sidecar for a kv_exact slot — ``ctl[-1]`` carries the
    exact-lane index), and the store requantizes exactly the written
    span [start, start + padded) — spliced prefix blocks below `start`
    keep their producer's bytes.
    """
    slot, length = ctl[0], ctl[1]
    quant = isinstance(caches, QuantStore)
    # fault-plane layout contract: the poison code is ALWAYS the last
    # ctl element; the exact-lane index (quant pools) sits before it
    fault = ctl[-1]
    eidx = ctl[-2] if quant else None
    lane = (quant_lane_view(caches, slot, eidx) if quant
            else extract_lane(caches, slot))
    lane, last = _prefill_lane(model, padded, chunk, start, variables,
                               lane, prompt, length)
    last = _inject_fault(last, fault)
    ok = _finite_ok(last)
    packed = PackedSampling(
        temperature=samp[0:1], top_p=samp[1:2], min_p=samp[2:3],
        top_k=ctl[3:4], need_lp=ctl[5:6],
    )
    key = request_key(rng, step_tag=ctl[2], slot=slot, seed=ctl[4],
                      samp_idx=jnp.int32(0))
    first, logprob = fused_sample(last[None], packed, key[None], cap=cap,
                                  allow=ctl[6:6 + cap][None, :])
    if quant:
        caches = quant_store_lane(caches, lane, slot, eidx, start,
                                  start + padded, hi=start + length)
    else:
        caches = store_lane(caches, lane, slot)
    return caches, first[0], logprob[0], ok


@functools.partial(
    jax.jit,
    static_argnames=("model", "padded", "chunk", "start", "cap"),
    donate_argnames=("phys",),
)
def _paged_prefill_program(model, padded, chunk, start, cap, variables,
                           phys, prompt, ctl, samp, rng):
    """Paged-pool prefill: identical contract to `_prefill_program`, but
    the lane is a GATHERED view of the physical page pool and only the
    pages the prefill may have written go back.

    `ctl = [slot, length, step, top_k, seed, need_lp, *allow_row,
    *page_table_row]` — the slot's (pages_per_lane,) page-table row
    rides the same packed int control transfer as the sampling knobs
    and the (cap,) grammar allow-list, so logical->physical
    translation costs zero extra host->device transfers and the
    compiled-program inventory keys on exactly the lane pool's
    `(padded, chunk, start)` triple. On a prefix hit, pages
    [0, start // page) hold SHARED prefix KV the gather materializes
    into the lane view; the scatter starts at `start // page` (static),
    so shared pages are read, never written — the zero-device-copy hit
    the refcount design exists for.

    Quantized pools: the gather dequantizes int8 pages through their
    per-(page, head) scale rows (both ride the same page-table
    translation), a kv_exact slot's view comes whole from the exact
    sidecar (its table rests at trash — exact streams never own pages),
    and the scatter re-quantizes only the written pages."""
    slot, length = ctl[0], ctl[1]
    quant = isinstance(phys, QuantStore)
    fault = ctl[-1]
    if quant:
        eidx = ctl[-2]
        row = ctl[6 + cap:-2]
        lane = quant_gather_lane(phys, row, eidx)
    else:
        row = ctl[6 + cap:-1]
        lane = gather_lane(phys, row)
    lane, last = _prefill_lane(model, padded, chunk, start, variables,
                               lane, prompt, length)
    last = _inject_fault(last, fault)
    ok = _finite_ok(last)
    packed = PackedSampling(
        temperature=samp[0:1], top_p=samp[1:2], min_p=samp[2:3],
        top_k=ctl[3:4], need_lp=ctl[5:6],
    )
    key = request_key(rng, step_tag=ctl[2], slot=slot, seed=ctl[4],
                      samp_idx=jnp.int32(0))
    first, logprob = fused_sample(last[None], packed, key[None], cap=cap,
                                  allow=ctl[6:6 + cap][None, :])
    if quant:
        page = jax.tree_util.tree_leaves(phys.q)[0].shape[1]
        phys = quant_scatter_lane_pages(phys, lane, row, start // page,
                                        eidx, hi=start + length)
    else:
        page = jax.tree_util.tree_leaves(phys)[0].shape[1]
        phys = scatter_lane_pages(phys, lane, row, start // page)
    return phys, first[0], logprob[0], ok


@functools.partial(
    jax.jit,
    static_argnames=("model", "block", "cap"),
    donate_argnames=("caches",),
)
def _decode_program(model, block, cap, variables, caches, state, samp, rng):
    """Advance every slot `block` tokens; inactive slots run masked.

    `state` is the host's packed (9 + cap, n_slots) int32 control block
    — rows [toks, pos, active, eos, step, top_k, seed, samp_idx,
    need_lp] then the transposed (cap, S) grammar allow-lists (all -1 =
    unconstrained; a constrained slot samples only listed ids and the
    HOST accepts one token per block, the mask being stale after the
    first draw — see serve/grammar.py) — and `samp` the packed
    (3, n_slots) float32 half of every slot's SamplingParams (rows
    [temperature, top_p, min_p]), so each call costs two host->device
    transfers regardless of slot count or param mix; the host keeps
    numpy mirrors and only the emitted streams come back. Every sampling knob is traced, so the compiled decode program
    count is identical to the static-greedy engine's (`cap` =
    ServeConfig.sample_cap is static but fixed per engine). `rng` is the
    engine's base key (a constant buffer); per-slot keys fold in the
    request seed and sample index for seeded slots, or the step counter
    riding row 4 for unseeded ones (`serve.sampling.slot_keys`).

    The per-slot apply is a batch-1 single-token forward vmapped over the
    slot axis — per-slot positions and per-slot cache writes fall out of
    the models' ``positions[0, 0]`` write contract under vmap. EOS
    padding is sticky by induction (an emitted EOS forces every later
    emission to EOS), mirroring `generate`'s done-flag semantics.

    Returns ``(caches, (tokens (block, S) i32, logprobs (block, S)
    f32))`` — the logprob row is the chosen token's log-softmax under the
    raw logits (streamed to requests with ``params.logprobs``).
    """
    toks, pos = state[0], state[1]
    active, eos = state[2].astype(bool), state[3]
    step_tag, seeds = state[4, 0], state[6]
    allow = state[9:9 + cap].T  # (S, cap)
    # fault-plane layout contract: the per-slot poison row is ALWAYS the
    # last state row; the exact-lane index row (quant) sits before it
    fault = state[-1]
    packed = PackedSampling(
        temperature=samp[0], top_p=samp[1], min_p=samp[2], top_k=state[5],
        need_lp=state[8],
    )
    # quantized pools (trace-time branch; the plain graph is untouched):
    # the scan carries the DEQUANTIZED (S, max_len, ...) lane view —
    # within-block reads are full precision, quantization happens at the
    # block boundary — and the store requantizes only the blocks each
    # slot's write window [pos0, pos0 + block) touched. state[-2] is the
    # per-slot exact-lane index row.
    quant = isinstance(caches, QuantStore)
    if quant:
        eidx = state[-2]
        pos0 = pos
        lanes = quant_lanes_view(caches, eidx)
    else:
        lanes = caches

    def one(tok, p, slot_caches):
        lane = jax.tree_util.tree_map(lambda a: a[None], slot_caches)
        logits, lane = model.apply(
            variables, tok[None, None], positions=jnp.reshape(p, (1, 1)),
            caches=lane, deterministic=True,
        )
        return logits[0, 0], jax.tree_util.tree_map(
            lambda a: jnp.squeeze(a, axis=0), lane
        )

    def step(carry, _):
        toks, pos, samp_idx, lanes = carry
        logits, lanes = jax.vmap(one)(toks, pos, lanes)
        logits = _inject_fault(logits, fault)
        ok = _finite_ok(logits)
        keys = slot_keys(rng, step_tag, seeds, samp_idx)
        nxt, logprob = fused_sample(logits, packed, keys, cap=cap,
                                    allow=allow)
        nxt = nxt.astype(toks.dtype)
        hit_eos = (eos >= 0) & (toks == eos)
        nxt = jnp.where(hit_eos, eos.astype(toks.dtype), nxt)
        nxt = jnp.where(active, nxt, toks)
        pos = jnp.where(active, pos + 1, pos)
        return (nxt, pos, samp_idx + 1, lanes), (nxt, logprob, ok)

    (toks, pos, _, lanes), (out, lps, oks) = jax.lax.scan(
        step, (toks, pos, state[7], lanes), None, length=block
    )
    if quant:
        caches = quant_store_written(caches, lanes, pos0, block, eidx)
    else:
        caches = lanes
    return caches, (out, lps, jnp.all(oks, axis=0))


@functools.partial(
    jax.jit,
    static_argnames=("model", "block", "cap"),
    donate_argnames=("phys",),
)
def _paged_decode_program(model, block, cap, variables, phys, state, samp,
                          rng):
    """Paged-pool decode block: `_decode_program`'s semantics over a
    physical page pool.

    `state` is the packed int block grown by the page tables: rows
    [0, 9 + cap) are exactly the lane program's control rows (incl. the
    grammar allow-lists), rows [9 + cap, 9 + cap + pages_per_lane)
    carry `table.T` — per-call page tables ride the ONE existing
    control transfer, so a paged decode call still costs two
    host->device transfers total.

    Translation is hoisted OUT of the scan: every slot's logical lane
    view is gathered from its page table once up front (the same
    (S, max_len, ...) layout the vmapped batch-1 apply already serves —
    the models run unmodified), the block's token loop runs on the
    carried lane views exactly like the lane program, and afterwards
    only the WRITE WINDOW goes back to the pool: the block writes
    positions [pos, pos + block), which spans a static number of pages
    per slot — those pages are gathered per slot and scattered to their
    physical ids. Sound because within one block every page outside a
    slot's own write window is read-only (shared prefix pages always
    PRECEDE the write frontier — see kv_pool.py's immutability
    argument), and pages inside the window are exclusively owned.
    Inactive slots' tables rest at the trash page, so their masked
    dummy writes land there instead of in lane 0; an active slot's
    unallocated tail also resolves to trash, which only discarded
    overshoot (post-EOS / post-budget steps inside the block) can reach
    — the host truncates those tokens anyway."""
    toks, pos = state[0], state[1]
    active, eos = state[2].astype(bool), state[3]
    step_tag, seeds = state[4, 0], state[6]
    allow = state[9:9 + cap].T  # (S, cap)
    fault = state[-1]
    quant = isinstance(phys, QuantStore)
    if quant:
        # the exact-lane index row rides after the page tables, the
        # fault row after it
        table = state[9 + cap:-2].T  # (S, pages_per_lane)
        eidx = state[-2]
        lanes = quant_gather_lanes(phys, table, eidx)
    else:
        table = state[9 + cap:-1].T  # (S, pages_per_lane)
        lanes = gather_lanes(phys, table)
    pos0 = pos
    packed = PackedSampling(
        temperature=samp[0], top_p=samp[1], min_p=samp[2], top_k=state[5],
        need_lp=state[8],
    )

    def one(tok, p, slot_caches):
        lane = jax.tree_util.tree_map(lambda a: a[None], slot_caches)
        logits, lane = model.apply(
            variables, tok[None, None], positions=jnp.reshape(p, (1, 1)),
            caches=lane, deterministic=True,
        )
        return logits[0, 0], jax.tree_util.tree_map(
            lambda a: jnp.squeeze(a, axis=0), lane
        )

    def step(carry, _):
        toks, pos, samp_idx, lanes = carry
        logits, lanes = jax.vmap(one)(toks, pos, lanes)
        logits = _inject_fault(logits, fault)
        ok = _finite_ok(logits)
        keys = slot_keys(rng, step_tag, seeds, samp_idx)
        nxt, logprob = fused_sample(logits, packed, keys, cap=cap,
                                    allow=allow)
        nxt = nxt.astype(toks.dtype)
        hit_eos = (eos >= 0) & (toks == eos)
        nxt = jnp.where(hit_eos, eos.astype(toks.dtype), nxt)
        nxt = jnp.where(active, nxt, toks)
        pos = jnp.where(active, pos + 1, pos)
        return (nxt, pos, samp_idx + 1, lanes), (nxt, logprob, ok)

    (toks, pos, _, lanes), (out, lps, oks) = jax.lax.scan(
        step, (toks, pos, state[7], lanes), None, length=block
    )
    out = (out, lps, jnp.all(oks, axis=0))
    page = jax.tree_util.tree_leaves(phys.q if quant else phys)[0].shape[1]
    # static window bound: positions [p, p + block) touch at most this
    # many pages; windows clipped past the lane end rewrite the last
    # page with its own (final) content — idempotent by construction
    for w in range((block - 1) // page + 2):
        pos_w = jnp.clip(pos0 + w * page, 0, table.shape[1] * page - 1)
        if quant:
            # only [pos0, pos0 + block) came from this block's writes;
            # the rest of each touched page re-encodes from its own f32
            # codes (bf16 lane round-trips would drift committed entries)
            phys = quant_scatter_written_pages(phys, lanes, table, pos_w,
                                               lo=pos0, hi=pos0 + block)
        else:
            phys = scatter_written_pages(phys, lanes, table, pos_w)
    if quant:
        phys = quant_store_exact_lanes(phys, lanes, eidx)
    return phys, out


def _spec_rounds_scan(model, k, rounds, cap, max_len, nmax, variables,
                      lanes, state, samp, rng, hist=None, hlen=None,
                      mtp_lanes=None, drafts0=None):
    """Shared draft-verify scan of the speculative decode programs (all
    three call it, so the commit semantics cannot drift between pools or
    drafters). `lanes` is the PADDED (S, max_len + k + 1, ...) lane view
    (`kv_pool.pad_time` — a chunk write can then never clamp-shift onto
    committed KV); `hist`/`hlen` arm the in-program n-gram drafter,
    `mtp_lanes`/`drafts0` the MTP head chain (exactly one pair is set).

    Each round: draft up to `k` tokens per slot, ONE chunked forward over
    the ``1 + k`` window (the models' cached per-query position masking
    makes the chunk causal, and garbage KV written for rejected drafts is
    overwritten by the next round's chunk before anything attends it —
    the `infer/speculative.py` argument, per slot under vmap), verify
    with `spec_verify`, advance the carry by the committed count. The
    per-slot position freezes at ``max_len - 1`` once a stream overshoots
    its lane (overshoot rounds rewrite slack/garbage only; the host has
    already finished such a stream when it truncates the call's output).

    Returns ``(lanes, mtp_lanes, out (rounds, S, k+1) i32,
    commits (rounds, S), proposed (rounds, S), lps (rounds, S, k+1),
    next_drafts (S, k))`` — the host keeps ``out[r, s, :commits[r, s]]``
    round by round.
    """
    toks, pos = state[0], state[1]
    active = state[2].astype(bool)
    step_tag, seeds, samp0 = state[4, 0], state[6], state[7]
    allow = state[9:9 + cap].T
    fault = state[-1]  # fault-plane poison row (always the last row)
    spec_ok = state[9 + cap].astype(bool)
    packed = PackedSampling(
        temperature=samp[0], top_p=samp[1], min_p=samp[2], top_k=state[5],
        need_lp=state[8],
    )
    mtp = mtp_lanes is not None
    arange_k1 = jnp.arange(k + 1)
    if mtp:
        from solvingpapers_tpu.models.deepseekv3 import mtp_head_apply

        mcfg = model.cfg
        params = variables["params"]
        moe_state = variables.get("moe_state", {})

    def fwd(tok, ds, p, slot_caches):
        lane = jax.tree_util.tree_map(lambda a: a[None], slot_caches)
        chunk = jnp.concatenate([tok[None], ds])[None, :].astype(jnp.int32)
        poss = jnp.minimum(p + arange_k1, max_len - 1)[None, :]
        if mtp:
            (logits, h), lane = model.apply(
                variables, chunk, positions=poss, caches=lane,
                deterministic=True, return_hidden=True,
            )
            out = (logits[0], h[0])
        else:
            logits, lane = model.apply(
                variables, chunk, positions=poss, caches=lane,
                deterministic=True,
            )
            out = logits[0]
        return out, jax.tree_util.tree_map(
            lambda a: jnp.squeeze(a, axis=0), lane
        )

    def rnd(carry, _):
        toks, pos, cnt, hist, hlen, drafts, lanes, mlanes = carry
        if hist is not None:
            ds, avail = jax.vmap(
                lambda h, m: ngram_drafts(h, m, k=k, nmax=nmax)
            )(hist, hlen)
        else:
            ds, avail = drafts, jnp.full(toks.shape, k, jnp.int32)
        avail = jnp.where(spec_ok & active, avail, 0)
        if mtp:
            (logits, hs), lanes = jax.vmap(fwd)(toks, ds, pos, lanes)
        else:
            logits, lanes = jax.vmap(fwd)(toks, ds, pos, lanes)
        logits = _inject_fault(logits, fault)
        ok = _finite_ok(logits)
        keys = round_keys(rng, step_tag, seeds, cnt, k + 1)
        out, commits, lps = spec_verify(
            logits, ds, avail, packed, keys, cap=cap, allow=allow
        )
        commits = jnp.where(active, commits, 0)
        nxt = jnp.take_along_axis(
            out, jnp.maximum(commits - 1, 0)[:, None], axis=1
        )[:, 0]
        toks = jnp.where(active, nxt.astype(toks.dtype), toks)
        if mtp:
            a_cut = jnp.maximum(commits - 1, 0)

            def adv(h_s, out_s, p, a_s, *slot_mtp):
                # the head's next-token stream is the COMMITTED matrix
                # row (garbage columns beyond the cut are overwritten by
                # the next round's advance before they are attended) and
                # the fresh draft reads the newest surviving column —
                # infer/speculative.py's loop body, per slot under vmap
                poss = jnp.minimum(p + arange_k1, max_len - 1)[None, :]
                c1 = jax.tree_util.tree_map(lambda a: a[None], slot_mtp[0])
                g1, y1, c1, _ = mtp_head_apply(
                    mcfg, params, moe_state, h_s[None], out_s[None, :],
                    poss, cache=c1,
                )
                d1 = jnp.argmax(jnp.take(g1[0], a_s, axis=0)).astype(
                    jnp.int32)
                new = [jax.tree_util.tree_map(
                    lambda a: jnp.squeeze(a, axis=0), c1)]
                if k == 2:
                    next2 = jnp.concatenate([out_s[1:], out_s[-1:]])
                    next2 = next2.at[a_s].set(d1)
                    c2 = jax.tree_util.tree_map(
                        lambda a: a[None], slot_mtp[1])
                    g2, _, c2, _ = mtp_head_apply(
                        mcfg, params, moe_state, y1, next2[None, :], poss,
                        cache=c2, head=2,
                    )
                    d2 = jnp.argmax(jnp.take(g2[0], a_s, axis=0)).astype(
                        jnp.int32)
                    new.append(jax.tree_util.tree_map(
                        lambda a: jnp.squeeze(a, axis=0), c2))
                    return (jnp.stack([d1, d2]), *new)
                return (d1[None], *new)

            adv_out = jax.vmap(adv)(hs, out, pos, a_cut, *mlanes)
            drafts, mlanes = adv_out[0], tuple(adv_out[1:])
        if hist is not None:
            hist = jax.vmap(
                lambda h, o, m: jax.lax.dynamic_update_slice(h, o, (m,))
            )(hist, out, hlen)
            hlen = jnp.minimum(hlen + commits, max_len)
        pos = jnp.minimum(pos + commits, max_len - 1)
        cnt = cnt + commits
        carry = (toks, pos, cnt, hist, hlen, drafts, lanes, mlanes)
        return carry, (out, commits, avail, lps, ok)

    if hist is not None:
        # pad so the (k+1)-wide write at hlen <= max_len never shifts
        hist = jnp.concatenate(
            [hist, jnp.zeros((hist.shape[0], k + 1), hist.dtype)], axis=1
        )
    carry0 = (toks, pos, samp0, hist, hlen, drafts0, lanes, mtp_lanes)
    carry, (out, commits, proposed, lps, oks) = jax.lax.scan(
        rnd, carry0, None, length=rounds
    )
    next_drafts = (carry[5] if drafts0 is not None
                   else jnp.zeros((toks.shape[0], k), jnp.int32))
    return (carry[6], carry[7], out, commits, proposed, lps, next_drafts,
            jnp.all(oks, axis=0))


@functools.partial(
    jax.jit,
    static_argnames=("model", "k", "rounds", "cap", "max_len", "nmax"),
    donate_argnames=("caches",),
)
def _spec_decode_program(model, k, rounds, cap, max_len, nmax, variables,
                         caches, state, samp, rng):
    """Lane-pool speculative decode block: `rounds` n-gram draft-verify
    rounds per call. `state` extends the plain decode layout: rows
    [0, 9 + cap) are `_decode_program`'s control rows, row ``9 + cap`` is
    the per-slot spec gate (0 = never draft: grammar-constrained slots
    and free lanes), rows [10 + cap, 10 + cap + max_len) carry each
    slot's token HISTORY transposed (prompt + committed tokens — the
    n-gram drafter's corpus) and the final row its live length. The
    history rides the same packed int transfer, so a speculative decode
    call is still two host->device control arrays. Quantized pools add
    the exact-lane index row LAST: the rounds run over the dequantized
    (padded) lane view and the store requantizes each slot's written
    window — rejected-draft garbage past the committed tail lands in
    blocks that are overwritten before they are ever attended, the same
    stale-lane contract as the plain program."""
    quant = isinstance(caches, QuantStore)
    if quant:
        eidx = state[-2]
        pos0 = state[1]
        views = quant_lanes_view(caches, eidx)
    else:
        views = caches
    lanes = pad_time(views, k + 1)
    hist = state[10 + cap:10 + cap + max_len].T
    hlen = state[10 + cap + max_len]
    lanes, _, out, commits, proposed, lps, _, finite = _spec_rounds_scan(
        model, k, rounds, cap, max_len, nmax, variables, lanes, state,
        samp, rng, hist=hist, hlen=hlen,
    )
    views = strip_time(lanes, k + 1)
    if quant:
        # bound the requantized window by the DEVICE-committed count
        # (mirrors the paged path's `last`): draft positions past it
        # hold rejected draws whose outliers would coarsen the whole
        # block's scale for the committed tokens sharing it
        total = commits.sum(axis=0)
        caches = quant_store_written(caches, views, pos0,
                                     rounds * (k + 1), eidx,
                                     hi=pos0 + jnp.maximum(total, 1),
                                     tail_garbage=True)
    else:
        caches = views
    return caches, (out, commits, proposed, lps, finite)


@functools.partial(
    jax.jit,
    static_argnames=("model", "k", "rounds", "cap", "max_len", "nmax"),
    donate_argnames=("phys",),
)
def _paged_spec_decode_program(model, k, rounds, cap, max_len, nmax,
                               variables, phys, state, samp, rng):
    """Paged-pool speculative decode block: `_spec_decode_program`'s
    semantics over the physical page pool. The page tables ride the
    packed transfer after the history rows; the gathered lane view is
    padded (`pad_time`) so chunk writes never clamp-shift, and only the
    DEVICE-committed window scatters back (`scatter_window_pages`):
    rejected-draft garbage past that window never reaches the physical
    pool, so shared prefix pages and the immutability argument are
    untouched by speculation. NOTE the window is bounded by the device
    commit count, which can exceed what the host keeps (grammar slots
    keep round 0 only; EOS/stop truncate): those tail pages hold
    stale-draw KV that is only sound because it lands strictly after the
    slot's attend window and is rewritten before it is ever attended —
    do NOT share or snapshot pages past a slot's host-accepted length."""
    base = 11 + cap + max_len
    quant = isinstance(phys, QuantStore)
    if quant:
        table = state[base:-2].T  # (S, pages_per_lane)
        eidx = state[-2]
        gathered = quant_gather_lanes(phys, table, eidx)
    else:
        table = state[base:-1].T  # (S, pages_per_lane)
        gathered = gather_lanes(phys, table)
    hist = state[10 + cap:10 + cap + max_len].T
    hlen = state[10 + cap + max_len]
    pos0 = state[1]
    lanes = pad_time(gathered, k + 1)
    lanes, _, out, commits, proposed, lps, _, finite = _spec_rounds_scan(
        model, k, rounds, cap, max_len, nmax, variables, lanes, state,
        samp, rng, hist=hist, hlen=hlen,
    )
    lanes = strip_time(lanes, k + 1)
    total = commits.sum(axis=0)
    last = jnp.minimum(pos0 + jnp.maximum(total, 1) - 1, max_len - 1)
    if quant:
        phys = quant_scatter_window_pages(phys, lanes, table, pos0, last,
                                          rounds * (k + 1))
        phys = quant_store_exact_lanes(phys, lanes, eidx)
    else:
        phys = scatter_window_pages(phys, lanes, table, pos0, last,
                                    rounds * (k + 1))
    return phys, (out, commits, proposed, lps, finite)


@functools.partial(
    jax.jit,
    static_argnames=("model", "k", "rounds", "cap", "max_len"),
    donate_argnames=("caches", "mtp"),
)
def _mtp_spec_decode_program(model, k, rounds, cap, max_len, variables,
                             caches, mtp, state, samp, rng):
    """MTP speculative decode block (deepseekv3, lane pool): the chunk
    forward returns hidden states and the trained MTP head(s) — their
    per-slot latent-cache lanes ride in `mtp`, allocated with the same
    ``k + 1`` slack — redraft the next round's tokens in-program
    (`infer/speculative.py` head chaining, vmapped over slots). Rows
    [10 + cap, 10 + cap + k) of `state` carry the FIRST round's drafts
    (the bootstrap from `_mtp_prefill_program`, or the previous call's
    returned `next_drafts`)."""
    lanes = pad_time(caches, k + 1)
    drafts0 = state[10 + cap:10 + cap + k].T.astype(jnp.int32)
    lanes, mtp, out, commits, proposed, lps, nxt, finite = (
        _spec_rounds_scan(
            model, k, rounds, cap, max_len, 0, variables, lanes, state,
            samp, rng, mtp_lanes=mtp, drafts0=drafts0,
        ))
    return (strip_time(lanes, k + 1), mtp,
            (out, commits, proposed, lps, finite), nxt)


@functools.partial(
    jax.jit,
    static_argnames=("model", "padded", "chunk", "cap", "k"),
    donate_argnames=("caches", "mtp"),
)
def _mtp_prefill_program(model, padded, chunk, cap, k, variables, caches,
                         mtp, prompt, ctl, samp, rng):
    """MTP-engine admission: `_prefill_program`'s contract (lane pool,
    full prefill — the MTP engine excludes the prefix cache: a spliced
    prefix has no hidden states for the head cache) plus the MTP head
    prefill and bootstrap drafts, mirroring `infer/speculative.py`'s
    prefill on a padded prompt: the head's cache is filled over columns
    [0, padded - 1) (columns past ``length - 1`` hold pad garbage that
    the decode rounds overwrite before any real query attends them), and
    the bootstrap advances it at column ``length - 1`` with the first
    sampled token to draft the token after it. Returns ``(caches, mtp,
    first, logprob, drafts (k,))``."""
    from solvingpapers_tpu.models.deepseekv3 import mtp_head_apply

    mcfg = model.cfg
    params = variables["params"]
    moe_state = variables.get("moe_state", {})
    slot, length = ctl[0], ctl[1]
    lane = extract_lane(caches, slot)
    toks = prompt[None, :]
    step = chunk or padded
    hs = []
    last = None
    for cs in range(0, padded, step):
        ce = min(cs + step, padded)
        tok_chunk = jax.lax.slice_in_dim(toks, cs, ce, axis=1)
        positions = jnp.broadcast_to(
            jnp.arange(cs, ce), (1, ce - cs)
        )
        (logits, h), lane = model.apply(
            variables, tok_chunk, positions=positions, caches=lane,
            deterministic=True, attend_len=ce, return_hidden=True,
        )
        hs.append(h)
        idx = jnp.clip(length - 1 - cs, 0, ce - cs - 1)
        row = jax.lax.dynamic_index_in_dim(logits[0], idx, axis=0,
                                           keepdims=False)
        sel = (length - 1 >= cs) & (length - 1 < ce)
        last = row if last is None else jnp.where(sel, row, last)
    h_all = jnp.concatenate(hs, axis=1)  # (1, padded, D)
    caches = store_lane(caches, lane, slot)
    last = _inject_fault(last, ctl[-1])
    ok = _finite_ok(last)
    packed = PackedSampling(
        temperature=samp[0:1], top_p=samp[1:2], min_p=samp[2:3],
        top_k=ctl[3:4], need_lp=ctl[5:6],
    )
    key = request_key(rng, step_tag=ctl[2], slot=slot, seed=ctl[4],
                      samp_idx=jnp.int32(0))
    first, logprob = fused_sample(last[None], packed, key[None], cap=cap,
                                  allow=ctl[6:6 + cap][None, :])
    first32 = first[0].astype(jnp.int32)
    # ---- head 1 prefill over columns [0, padded - 1): the next-token
    # stream there is the prompt itself (pad columns hold garbage the
    # decode rounds overwrite before any real attend — same contract as
    # the main lane's pad region)
    m1 = extract_lane(mtp[0], slot)
    y1s = []
    head_end = max(padded - 1, 1)
    for cs in range(0, head_end, step):
        ce = min(cs + step, head_end)
        nxt = jax.lax.slice_in_dim(toks, cs + 1, ce + 1, axis=1)
        g, y1, m1, _ = mtp_head_apply(
            mcfg, params, moe_state, h_all[:, cs:ce], nxt,
            jnp.broadcast_to(jnp.arange(cs, ce), (1, ce - cs)),
            cache=m1, attend_len=ce,
        )
        y1s.append(y1)
    # bootstrap at column length - 1: h of the last real prompt token +
    # the embedding of the just-sampled first token -> drafts position
    # length + 1
    pos_last = jnp.clip(length - 1, 0, padded - 1)
    h_last = jax.lax.dynamic_slice(
        h_all, (0, pos_last, 0), (1, 1, h_all.shape[2])
    )
    g, y1_last, m1, _ = mtp_head_apply(
        mcfg, params, moe_state, h_last, first32[None, None],
        jnp.reshape(pos_last, (1, 1)), cache=m1,
    )
    d1 = jnp.argmax(g[0, -1]).astype(jnp.int32)
    out_mtp = [store_lane(mtp[0], m1, slot)]
    if k == 2:
        y1_all = jnp.concatenate(y1s, axis=1)  # (1, padded - 1, D)
        m2 = extract_lane(mtp[1], slot)
        head2_end = max(padded - 2, 1)
        for cs in range(0, head2_end, step):
            ce = min(cs + step, head2_end)
            nxt = jax.lax.slice_in_dim(toks, cs + 2, ce + 2, axis=1)
            _, _, m2, _ = mtp_head_apply(
                mcfg, params, moe_state, y1_all[:, cs:ce], nxt,
                jnp.broadcast_to(jnp.arange(cs, ce), (1, ce - cs)),
                cache=m2, attend_len=ce, head=2,
            )
        pos_a = jnp.clip(length - 2, 0, padded - 2)
        y_a = jax.lax.dynamic_slice(
            y1_all, (0, pos_a, 0), (1, 1, y1_all.shape[2])
        )
        y_pair = jnp.concatenate([y_a, y1_last], axis=1)
        nxt_pair = jnp.stack([first32, d1])[None, :]
        poss = jnp.stack([pos_a, pos_a + 1])[None, :]
        g2, _, m2, _ = mtp_head_apply(
            mcfg, params, moe_state, y_pair, nxt_pair, poss, cache=m2,
            head=2,
        )
        d2 = jnp.argmax(g2[0, -1]).astype(jnp.int32)
        out_mtp.append(store_lane(mtp[1], m2, slot))
        drafts = jnp.stack([d1, d2])
    else:
        drafts = d1[None]
    return caches, tuple(out_mtp), first[0], logprob[0], drafts, ok


class ServeEngine:
    """Long-lived continuous-batching engine over one decoder model.

    >>> eng = ServeEngine(model, params, ServeConfig(n_slots=4))
    >>> reqs = [eng.submit(p, max_new_tokens=64) for p in prompts]
    >>> eng.run()              # drain: step() until queue + slots empty
    >>> reqs[0].tokens         # per-request generated ids

    `submit` is non-blocking (admission control may mark the request
    ``rejected``); `step()` is one scheduler iteration and may be driven
    by an external loop that interleaves new submissions — that is the
    point of continuous batching.

    Per-request sampling rides `submit(..., params=SamplingParams(...))`
    (default greedy); there is no engine-wide sampler any more — the mix
    of greedy and stochastic requests shares the same compiled programs.
    `detokenize` (token ids -> text) is only needed when requests use
    stop STRINGS; stop token-id sets and everything else work without it.
    """

    def __init__(
        self,
        model,
        params,
        config: ServeConfig | None = None,
        *,
        extra_variables: dict | None = None,
        metrics_window: int = 4096,
        detokenize=None,
    ):
        cfg = config or ServeConfig()
        limit = getattr(model, "max_positions", None)
        if limit is not None and cfg.max_len > limit:
            raise ValueError(
                f"max_len {cfg.max_len} exceeds the model's max positions "
                f"{limit}"
            )
        self.model = model
        self.config = cfg
        self.detokenize = detokenize
        self.variables = {"params": params, **(extra_variables or {})}
        if cfg.prefix_sched and not cfg.prefix_cache:
            raise ValueError(
                "prefix_sched orders admission by cached-prefix match "
                "length, which needs prefix_cache=True — without the radix "
                "tree the knob would silently degrade to plain FIFO"
            )
        self.metrics = ServeMetrics(window=metrics_window)
        # flight recorder + anomaly monitor (both None when tracing is
        # off: every hot-path hook below is a single `is not None` check).
        # The recorder shares the latency metrics' patchable clock so
        # trace-summary phase sums equal measured TTFT + decode wall.
        self.trace = None
        self._mon = None
        # rolling retrospective (metrics/timeseries.py): sampled from
        # step() when the interval elapses — created BEFORE the anomaly
        # monitor so every dump can carry the preceding window
        self.timeseries = None
        if cfg.timeseries:
            from solvingpapers_tpu.metrics.timeseries import TimeSeriesStore

            self.timeseries = TimeSeriesStore(
                capacity=cfg.timeseries_capacity,
                interval_s=cfg.timeseries_interval_s,
                clock=smetrics.now,
            )
        if cfg.trace:
            from solvingpapers_tpu.metrics.trace import (
                AnomalyMonitor,
                FlightRecorder,
            )

            self.trace = FlightRecorder(
                capacity=cfg.trace_capacity, clock=smetrics.now
            )
            if cfg.trace_dump_path:
                self._mon = AnomalyMonitor(
                    self.trace, cfg.trace_dump_path,
                    snapshot_fn=self.metrics.snapshot,
                    last_n=cfg.trace_dump_events,
                    slow_step_factor=cfg.trace_slow_step_factor,
                    reject_burst=cfg.trace_reject_burst,
                    timeseries_fn=(self.timeseries.doc
                                   if self.timeseries is not None
                                   else None),
                )
        elif cfg.trace_dump_path:
            raise ValueError(
                "trace_dump_path dumps the flight recorder's last events "
                "on anomalies, which needs trace=True — without the ring "
                "a dump would hold nothing"
            )
        # TraceAnnotation scopes label the prefill/decode/splice programs
        # inside XLA profiles AND the flight recorder's own timeline
        self._annotate = cfg.trace or cfg.profile_dir is not None
        self._step_idx = 0
        self._profiling = False
        self._profile_done = cfg.profile_dir is None
        self._paged = cfg.paged
        # quantized KV storage (ops/quant.py; see the ServeConfig knob
        # block): the pool payload becomes int8 + per-block scales, the
        # jitted programs dequantize on read / quantize on write, and
        # kv_exact requests ride full-precision sidecar lanes inside the
        # same compiled programs
        self._quant = cfg.kv_quant is not None
        if cfg.kv_quant not in (None, "int8"):
            raise ValueError(
                f"kv_quant must be 'int8' or None, got {cfg.kv_quant!r}"
            )
        if cfg.kv_quant_block < 1:
            raise ValueError(
                f"kv_quant_block must be >= 1, got {cfg.kv_quant_block}"
            )
        if cfg.kv_exact_lanes < 0:
            raise ValueError(
                f"kv_exact_lanes must be >= 0, got {cfg.kv_exact_lanes}"
            )
        if cfg.kv_exact_lanes and not self._quant:
            raise ValueError(
                "kv_exact_lanes books full-precision sidecar lanes for "
                "kv_exact requests inside a QUANTIZED pool, which needs "
                "kv_quant set — an unquantized pool is exact everywhere "
                "already, so the knob would silently do nothing"
            )
        if self._quant and cfg.speculative == "mtp":
            raise ValueError(
                "kv_quant with speculative='mtp' is unsupported: the MTP "
                "drafter's head-cache lanes are a latent pool of their "
                "own that the quantized store does not cover yet — use "
                "speculative='ngram' (either pool) or drop kv_quant"
            )
        if (self._quant and cfg.prefix_cache and not cfg.paged
                and cfg.prefix_page % cfg.kv_quant_block):
            raise ValueError(
                f"prefix_page {cfg.prefix_page} is not a multiple of "
                f"kv_quant_block {cfg.kv_quant_block}: quantized lane "
                "segments carry whole scale rows, so splice offsets "
                "(page multiples) must be block-aligned"
            )
        # exact-lane sidecar bookkeeping (kv_exact requests): LIFO free
        # list of lane ids [1, kv_exact_lanes]; 0 is the trash lane a
        # quantized slot's exact-side writes fall into
        self._eidx = np.zeros(cfg.n_slots, np.int32)
        self._exact_free = list(range(cfg.kv_exact_lanes, 0, -1))
        if cfg.paged:
            page = cfg.page_size or cfg.prefix_page
            if cfg.prefix_cache and page != cfg.prefix_page:
                raise ValueError(
                    f"page_size {page} != prefix_page {cfg.prefix_page}: "
                    "zero-copy prefix sharing appends PHYSICAL page ids "
                    "to page tables, which needs tree edges and pool "
                    "pages on one granularity — set them equal (or leave "
                    "page_size None to inherit prefix_page)"
                )
            self.pool = PagedKVPool(
                model, cfg.n_slots, cfg.max_len, page,
                page_budget=cfg.page_budget, quant=cfg.kv_quant,
                exact_lanes=cfg.kv_exact_lanes,
            )
        else:
            if cfg.page_size is not None or cfg.page_budget is not None:
                raise ValueError(
                    "page_size/page_budget configure the paged pool and "
                    "need paged=True — on the lane pool they would "
                    "silently do nothing"
                )
            self.pool = KVSlotPool(
                model, cfg.n_slots, cfg.max_len, quant=cfg.kv_quant,
                quant_block=cfg.kv_quant_block,
                exact_lanes=cfg.kv_exact_lanes,
            )
        if self._quant:
            # kv-quant byte gauges ride every snapshot via the provider
            # mechanism — present iff the pool is quantized, the same
            # key-surface discipline as the paged/spec/observatory gauges
            self.metrics.add_gauge_provider(self._kv_quant_gauges)
        # speculative decoding (serve/spec.py; see the ServeConfig knob
        # block): per-slot draft-and-verify rounds inside the decode
        # program, with a host-side adaptive controller that falls back
        # to the plain block while drafts keep rejecting
        self._spec = cfg.speculative
        self._spec_ctl = None
        self._mtp_pool = None
        if cfg.speculative is None:
            if cfg.spec_rounds is not None:
                raise ValueError(
                    "spec_rounds configures the speculative decode block "
                    "and needs speculative set — without a drafter it "
                    "would silently do nothing"
                )
        else:
            if cfg.speculative not in DRAFTERS:
                raise ValueError(
                    f"speculative must be one of {DRAFTERS} (or None), "
                    f"got {cfg.speculative!r}"
                )
            if cfg.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {cfg.spec_k}")
            if cfg.spec_rounds is not None and cfg.spec_rounds < 1:
                raise ValueError(
                    f"spec_rounds must be >= 1, got {cfg.spec_rounds}"
                )
            self._spec_rounds = cfg.spec_rounds or cfg.decode_block
            if cfg.speculative == "mtp":
                heads = getattr(model.cfg, "mtp_heads", 0)
                if heads < 1:
                    raise ValueError(
                        "speculative='mtp' drafts with the model's "
                        "trained multi-token-prediction heads, which "
                        "this model does not have (mtp_heads == 0) — "
                        "use speculative='ngram' for model-free drafting"
                    )
                if cfg.paged:
                    raise ValueError(
                        "speculative='mtp' serves over the lane pool: "
                        "the MTP head cache is a per-slot lane pool of "
                        "its own (paged main-pool support is a "
                        "follow-on) — drop paged or use 'ngram'"
                    )
                if cfg.prefix_cache:
                    raise ValueError(
                        "speculative='mtp' cannot reuse cached prefixes: "
                        "a spliced prefix carries no hidden states for "
                        "the MTP head cache — drop prefix_cache or use "
                        "'ngram'"
                    )
                self._spec_k = min(cfg.spec_k, heads, 2)
                from solvingpapers_tpu.infer.cache import LatentCache

                dim = model.cfg.latent_dim + model.cfg.rope_dim
                # head lanes carry the same k+1 slack the decode
                # programs pad the main lanes with, so chunked head
                # advances never clamp-shift either
                self._mtp_pool = tuple(
                    LatentCache.init(
                        cfg.n_slots, cfg.max_len + self._spec_k + 1, dim,
                        model.cfg.compute_dtype,
                    )
                    for _ in range(self._spec_k)
                )
                self._next_drafts = np.zeros(
                    (cfg.n_slots, self._spec_k), np.int32
                )
            else:
                self._spec_k = cfg.spec_k
                if cfg.spec_ngram < 1:
                    raise ValueError(
                        f"spec_ngram must be >= 1, got {cfg.spec_ngram}"
                    )
            min_rate = cfg.spec_min_rate
            if min_rate is None:
                min_rate = max(1.0, self._spec_k / 4)
            self._spec_ctl = SpecController(
                min_rate=min_rate,
                probe_every=cfg.spec_probe_every,
            )
            self.metrics.add_gauge_provider(self._spec_gauges)
        # SLO accounting (serve/slo.py; see the ServeConfig knob block):
        # host-side per-class attainment/burn/goodput on the finish path,
        # riding the snapshot via the gauge-provider mechanism — present
        # iff slo_targets is configured, None = one branch per finish
        self._slo = None
        if cfg.slo_targets is not None:
            from solvingpapers_tpu.serve.slo import SloTracker

            self._slo = SloTracker(cfg.slo_targets,
                                   burn_window=cfg.slo_burn_window)
            self.metrics.add_gauge_provider(
                lambda: self._slo.gauges(self.metrics.elapsed_s)
            )
        # fault-tolerance layer (serve/faults.py; see the ServeConfig
        # knob block). The supervised step boundary is ALWAYS armed —
        # real NaN forwards and device runtime errors need no opt-in —
        # while the injection plane and the degradation ladder follow
        # the None-pattern.
        if cfg.fault_max_retries < 0:
            raise ValueError(
                f"fault_max_retries must be >= 0, got {cfg.fault_max_retries}"
            )
        if (cfg.fault_step_deadline_s is not None
                and not cfg.fault_step_deadline_s > 0):
            raise ValueError(
                "fault_step_deadline_s must be > 0 (or None to disarm "
                f"the watchdog), got {cfg.fault_step_deadline_s}"
            )
        self._faults = FaultPlan.from_config(cfg.fault_plan)
        # request write-ahead journal (serve/journal.py; see the
        # ServeConfig knob block). None-pattern off; opening an existing
        # path LOADS it — `recover()` is the boot step that replays it.
        if cfg.journal_strict and cfg.journal_path is None:
            raise ValueError(
                "journal_strict escalates journal I/O failures, which "
                "needs journal_path set — without a journal the knob "
                "would silently do nothing"
            )
        self.journal = None
        self._journal_degraded = False
        self._recovered_total = 0
        # trace_id -> live recovered Request: the HTTP front door's
        # Last-Event-ID reconnect surface after a restart (entries drop
        # when the dict is rebuilt on the next recover(); bounded by the
        # live set at recovery time)
        self._recovered: dict[str, Request] = {}
        if cfg.journal_path is not None:
            self.journal = Journal(
                cfg.journal_path,
                rotate_bytes=cfg.journal_rotate_bytes,
                rotate_finished=cfg.journal_rotate_finished,
            )
            self.metrics.add_gauge_provider(self._journal_gauges)
        # per-slot logits-poison row: rides the LAST row/element of every
        # packed control transfer (all-zero = bitwise no-op inside the
        # programs), written by the plan's decode-site pokes and cleared
        # after each dispatch
        self._fault_row = np.zeros(cfg.n_slots, np.int32)
        self._health = "healthy"
        self._consec_failures = 0
        self._failed_since: float | None = None
        self._last_error: str | None = None
        self._recover_at = 0.0
        self._backoff = cfg.fault_recover_backoff_s
        self._ladder = None
        if cfg.degrade:
            for knob in ("degrade_free_page_frac", "degrade_headroom_frac"):
                v = getattr(cfg, knob)
                if not 0.0 < v < 1.0:
                    raise ValueError(f"{knob} must be in (0, 1), got {v}")
            if not cfg.degrade_burn_threshold > 0:
                raise ValueError(
                    "degrade_burn_threshold must be > 0, got "
                    f"{cfg.degrade_burn_threshold}"
                )
            self._ladder = DegradationLadder(
                up_steps=cfg.degrade_up_steps,
                down_steps=cfg.degrade_down_steps,
            )
            self.metrics.add_gauge_provider(
                lambda: {"serve/degradation_rung": float(self._ladder.rung)}
            )
        # delivered-token tick weight for the scheduler's anti-starvation
        # clock: a speculative step can deliver many tokens per slot, so
        # ticking 1 per iteration would make a waiting request's budget
        # worth MORE delivered work under high acceptance — the weight
        # normalizes the wait clock to block-equivalents of delivered
        # tokens (serve/scheduler.py tick)
        self._tick_weight = 1.0
        self.prefix_cache = (
            PrefixCache(page=cfg.prefix_page, max_bytes=cfg.prefix_cache_bytes,
                        trace=self.trace,
                        pool=self.pool if cfg.paged else None)
            if cfg.prefix_cache else None
        )
        if cfg.paged:
            # page-pool occupancy/fragmentation gauges ride every
            # snapshot via the provider mechanism — present iff paged,
            # the same key-surface discipline as the observatory gauges
            self.metrics.add_gauge_provider(self._page_gauges)
        # compile & memory observatory (metrics/xla_obs.py): both None
        # when off, so every program call site is one `is not None`
        # branch — the same discipline as the flight recorder above
        self.registry = None
        self.ledger = None
        if cfg.xla_obs:
            from solvingpapers_tpu.metrics.xla_obs import (
                CompileRegistry,
                HBMLedger,
                pytree_bytes,
            )

            self.registry = CompileRegistry(
                trace=self.trace, monitor=self._mon,
                storm_k=cfg.obs_storm_k,
                storm_window_s=cfg.obs_storm_window_s,
                clock=smetrics.now,
                # the per-op anatomy ledger rides the observatory: the
                # parse is compile-time-only (steady-state cost not
                # measured on the chip)
                anatomy=True,
                hlo_dir=cfg.obs_hlo_dir,
            )
            if not cfg.paged:
                # the lane pool owns jitted splice/extract programs and
                # routes them through the registry; the paged pool has
                # NONE (sharing is host-side bookkeeping — the absence
                # of a splice_program in the registry is the zero-copy
                # acceptance check)
                self.pool.registry = self.registry
            self.ledger = HBMLedger(capacity_bytes=cfg.obs_capacity_bytes)
            # params are fixed for the engine's lifetime: account once
            self.ledger.register("params", pytree_bytes(self.variables))
            self.ledger.register("kv_pool", lambda: self.pool.nbytes)
            if self._mtp_pool is not None:
                # the MTP drafter's head-cache lanes are a real pool of
                # their own (latent_dim+rope_dim per position per head)
                self.ledger.register(
                    "mtp_cache", pytree_bytes(self._mtp_pool)
                )
            if self.prefix_cache is not None and not cfg.paged:
                # paged trees hold REFERENCES into the fixed pool — their
                # bytes are already inside kv_pool; a separate ledger
                # entry would double-count the same HBM
                self.ledger.register(
                    "prefix_cache", lambda: self.prefix_cache.bytes_held
                )
            self.ledger.temp_fn = self.registry.max_temp_bytes
            self.metrics.add_gauge_provider(self.registry.gauges)
            self.metrics.add_gauge_provider(self.ledger.gauges)
        self.status = None
        self.scheduler = FIFOScheduler(
            max_waiting=cfg.max_waiting,
            decode_priority=cfg.decode_priority,
            max_prefills_per_step=cfg.max_prefills_per_step,
            max_wait_steps=cfg.max_wait_steps,
            prefer_cached=cfg.prefix_sched,
            prefix_lookup=self._match_len if self.prefix_cache else None,
            can_admit=(self._can_admit
                       if cfg.paged or self._exact_free else None),
            trace=self.trace,
        )
        self._slot_req: list[Request | None] = [None] * cfg.n_slots
        # host-side numpy mirrors of per-slot decode state: shipped to the
        # device as ONE packed array per jitted call — eager .at[].set
        # bookkeeping was half the drain time on small models
        self._toks = np.zeros(cfg.n_slots, np.int32)
        self._pos = np.zeros(cfg.n_slots, np.int32)
        # slot-major SamplingParams mirrors, packed into the jitted calls
        # as traced control arrays (serve/sampling.py). Free lanes rest at
        # the greedy row so an all-greedy batch rides fused_sample's
        # sort-free fast path.
        self._samp_f = np.tile(
            np.asarray(GREEDY_ROW, np.float32)[:, None], (1, cfg.n_slots)
        )
        # grammar allow-lists, slot-major (-1 = unconstrained): refreshed
        # from each constrained request's stepper before every program
        # call, riding the packed int control transfers
        self._allow = np.full((cfg.n_slots, cfg.sample_cap), -1, np.int32)
        self._top_k = np.zeros(cfg.n_slots, np.int32)
        self._seed = np.full(cfg.n_slots, -1, np.int32)
        self._need_lp = np.zeros(cfg.n_slots, np.int32)
        self._rng = jax.random.key(cfg.seed)  # base key; folded per call
        self._rng_step = 0
        self._last_emit = np.zeros(cfg.n_slots)  # per-slot last emit time
        # deadline-bearing requests currently in the waiting queue: step()
        # only scans the queue for expiries when this is nonzero, so
        # deadline-free traffic pays nothing on the dispatch-bound host
        # loop (updated at submit / admit / cancel / purge)
        self._waiting_deadlines = 0
        # live status endpoint LAST: its handler threads read scheduler /
        # slot state, so serving must not start until every piece of
        # engine state above exists (a probe hitting the construction
        # window would 500). Useful with or without the observatory —
        # /statusz simply omits the compile/mem sections when it's off.
        if cfg.status_port is not None:
            from solvingpapers_tpu.metrics.http import StatusServer

            self.status = StatusServer(
                self.statusz,
                # prom_snapshot: the pull path renders the latency
                # histograms as native _bucket/_sum/_count series
                lambda: (self._step_idx, self.metrics.prom_snapshot()),
                host=cfg.status_host, port=cfg.status_port,
                # /healthz answers 503 while the engine is unhealthy
                health_fn=lambda: self.health,
                timeseries_fn=(self.timeseries.doc
                               if self.timeseries is not None else None),
            )

    # ------------------------------------------------------------- submit

    def submit(
        self,
        prompt,
        max_new_tokens: int = 64,
        eos_id=_UNSET,
        params: SamplingParams | None = None,
        deadline_s: float | None = None,
        grammar=None,
        stream_cb=None,
        trace_id: str | None = None,
    ) -> Request:
        """Enqueue one request; returns its live handle immediately.

        `params` attaches per-request SamplingParams (default greedy;
        ``params.max_tokens`` overrides `max_new_tokens` when set).
        `deadline_s` is a relative deadline: a request still waiting or
        decoding `deadline_s` seconds after submit finishes "timeout" at
        the next scheduler iteration / block boundary.

        `grammar` constrains decoding to a formal grammar (one
        `serve.grammar.JsonStepper` per request — it is stateful): every
        draw is restricted to the stepper's allowed-token list via the
        traced allow-mask, and the stream finishes ("stop") when the
        grammar accepts a complete document. EOS is not meaningful
        mid-document, so a grammar request must not also carry an
        `eos_id` (the engine default is ignored; an explicit one
        raises). `stream_cb(request, n_new, finished)` is called on the
        engine thread after every token append and at finish — the
        HTTP front door's streaming hook (see `Request.stream_cb`).

        Bad inputs raise `ValueError` HERE, host-side — never inside a
        traced program: non-integer or non-1-D prompts, empty prompts,
        budgets < 1, prompts beyond the engine capacity, non-positive
        deadlines, stop strings without a `detokenize` callable, a
        grammar alongside an explicit eos_id, and a budget too small
        for the grammar's shortest complete document.

        `trace_id` is the request's durable identity (the HTTP front
        door passes its X-Request-Id): it keys the write-ahead journal
        record and the Last-Event-ID resume surface. With the journal
        on and no id supplied, one is minted — a journaled request must
        always be addressable after a restart.
        """
        arr = np.asarray(prompt)
        # size first: np.asarray([]) defaults to float64, and leading with
        # the dtype check would blame "float" ids on a prompt with no ids
        if arr.size < 1:
            raise ValueError("prompt must have at least one token")
        if arr.dtype.kind not in "iu":
            raise ValueError(
                f"prompt must be integer token ids, got dtype {arr.dtype} "
                "(cast explicitly if the values really are ids)"
            )
        if arr.ndim != 1:
            raise ValueError(
                f"prompt must be 1-D (one request's token ids), got shape "
                f"{arr.shape} — batch by submitting one request per row"
            )
        prompt = arr.astype(np.int32)
        params = params or SamplingParams()
        if params.max_tokens is not None:
            max_new_tokens = params.max_tokens
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        if params.stop and self.detokenize is None:
            raise ValueError(
                "params.stop (stop strings) needs the engine constructed "
                "with a `detokenize` callable (token ids -> text); "
                "stop_token_ids work without one"
            )
        if params.top_k > self.config.sample_cap:
            raise ValueError(
                f"top_k {params.top_k} exceeds ServeConfig.sample_cap "
                f"{self.config.sample_cap} — the engine samples inside the "
                "top sample_cap logits; raise the cap (costlier decode "
                "steps) or lower top_k"
            )
        if (params.kv_exact and self._quant
                and not self.config.kv_exact_lanes):
            raise ValueError(
                "kv_exact requests need full-precision sidecar lanes on a "
                "quantized pool — construct the engine with "
                "ServeConfig.kv_exact_lanes >= 1 (on an unquantized "
                "engine kv_exact is a no-op and always accepted)"
            )
        if params.slo is not None:
            if self._slo is None:
                raise ValueError(
                    "params.slo tags the request's SLO class, which needs "
                    "ServeConfig.slo_targets configured — without the "
                    "tracker the tag would silently account to nothing"
                )
            if params.slo not in self._slo.targets:
                raise ValueError(
                    f"unknown SLO class {params.slo!r}: "
                    f"ServeConfig.slo_targets defines "
                    f"{sorted(self._slo.targets)}"
                )
        total = prompt.size + max_new_tokens
        limit = getattr(self.model, "max_positions", None)
        cap = min(self.config.max_len, limit or self.config.max_len)
        if total > cap:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"= {total} exceeds the engine capacity {cap} "
                "(min of ServeConfig.max_len and the model's max positions)"
            )
        if grammar is not None:
            if eos_id is not _UNSET and eos_id is not None:
                raise ValueError(
                    "a grammar-constrained request cannot carry an eos_id: "
                    "EOS is only legal at a complete document, where the "
                    "grammar finishes the stream itself"
                )
            eos_id = None  # the engine default must not leak in either
            min_close = getattr(grammar, "min_close", 0)
            if max_new_tokens < min_close:
                raise ValueError(
                    f"max_new_tokens {max_new_tokens} cannot complete the "
                    f"grammar's shortest document ({min_close} tokens) — "
                    "the constrained stream would be cut mid-structure"
                )
        req = Request(
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            eos_id=self.config.eos_id if eos_id is _UNSET else eos_id,
            params=params,
            grammar=grammar,
            stream_cb=stream_cb,
        )
        req.trace_id = trace_id
        if deadline_s is not None:
            req.deadline = req.submit_time + deadline_s
        # fault boundary: an unhealthy engine is draining — it must not
        # book slots it cannot serve. Past the recovery backoff the next
        # submission re-arms it (the pool was rebuilt at the unhealthy
        # transition, so recovery is a host-side state flip).
        if self._health == "unhealthy":
            if smetrics.now() >= self._recover_at:
                self._recover()
            else:
                req.state = REJECTED
                req.reject_reason = "unhealthy"
                self.metrics.record_reject()
                if self.trace is not None:
                    self.trace.instant("reject", "request", "queue",
                                       req=req.id, ts=req.submit_time,
                                       reason="unhealthy")
                return req
        # degradation ladder: load-shed admissions by SLO class (batch
        # first) — the front door maps the shed to 503 with a jittered
        # Retry-After and the current rung header
        if self._ladder is not None:
            cls = req.params.slo or "standard"
            if cls in self._ladder.shed_classes():
                req.state = REJECTED
                req.reject_reason = f"shed:{cls}"
                self.metrics.record_reject()
                self.metrics.record_shed(cls)
                if self.trace is not None:
                    self.trace.instant("shed", "engine", "queue",
                                       req=req.id, ts=req.submit_time,
                                       slo=cls, rung=self._ladder.rung)
                return req
        if not self.scheduler.submit(req):
            self.metrics.record_reject()
            if self.trace is not None:
                self.trace.instant("reject", "request", "queue", req=req.id,
                                   ts=req.submit_time, prompt_len=prompt.size)
                if self._mon is not None:
                    self._mon.observe_reject()
        else:
            if req.deadline is not None:
                self._waiting_deadlines += 1
            # journal AFTER acceptance: a rejected request has no
            # durable life to replay (the write-ahead contract is
            # "accepted work survives", not "every knock on the door")
            self._journal_submit(req)
            if self.trace is not None:
                # rid: the client trace id rides the submit instant so
                # the stitched fleet export can join this replica's
                # per-request flow to the router's route/migrate spans
                # (absent on direct submits — no key, not a null)
                rid_arg = ({"rid": req.trace_id}
                           if req.trace_id is not None else {})
                self.trace.instant("submit", "request", "queue", req=req.id,
                                   ts=req.submit_time, prompt_len=prompt.size,
                                   **rid_arg)
                if self._mon is not None:
                    self._mon.observe_accept()
        return req

    def replay_submit(self, prompt, max_new_tokens: int = 64, *,
                      eos_id=_UNSET, params: SamplingParams | None = None,
                      committed=()) -> Request:
        """Shadow-traffic submission for the replay harness
        (serve/replay.py): `submit`'s full validation and admission
        with the side effects a re-serve must not have stripped out —
        no deadline is armed, no WAL records are written (the engine
        must be journal-off: shadow traffic written into a live
        journal would replay itself on the next recovery), the
        recorded `params.max_tokens` never overrides the harness's
        explicit budget (replay budgets to the RECORDED stream length
        so comparisons stay prefix-aligned), and an SLO tag this
        engine does not track is dropped instead of rejected
        (`_entry_request`'s rule: the class is accounting, not
        semantics).

        `committed` pre-loads the request with recorded tokens,
        pinning the recorded seed chain through the preemption-resume
        machinery: admission re-prefills prompt + committed[:-1],
        discards the resampled token, and the next draw lands at
        sample index ``len(committed)``. With ``max_new_tokens =
        len(committed) + 1`` the engine produces exactly ONE token,
        directly comparable to the recorded token at that offset —
        the teacher-forced cut-replay primitive. Host-side only: the
        resume path is the one recover()/adopt() already exercise, so
        a replay-less engine compiles nothing new."""
        if self.journal is not None:
            raise ValueError(
                "replay_submit needs a journal-off engine: shadow "
                "traffic must not write WAL records (build the replay "
                "engine from serve.replay.sanitize_config)"
            )
        params = params or SamplingParams()
        if params.max_tokens is not None:
            params = dataclasses.replace(params, max_tokens=None)
        if params.slo is not None and (
                self._slo is None or params.slo not in self._slo.targets):
            params = dataclasses.replace(params, slo=None)
        committed = [int(t) for t in committed]
        if committed and len(committed) >= max_new_tokens:
            raise ValueError(
                f"committed prefix ({len(committed)} tokens) must leave "
                f"budget to generate (max_new_tokens {max_new_tokens})"
            )
        req = self.submit(prompt, max_new_tokens, eos_id=eos_id,
                          params=params)
        if req.state == REJECTED:
            raise ValueError(
                "replay submission rejected "
                f"({req.reject_reason or 'queue full'}) — size the "
                "replay config's max_waiting to the corpus"
            )
        if committed:
            # pre-step is the safe window: the request is queued but
            # cannot be admitted until the owner's next step()
            req.tokens = committed
        return req

    def cancel(self, req: Request) -> None:
        """Cancel a request: a WAITING one leaves the queue and finishes
        "cancelled" immediately; an ACTIVE one keeps its lane until the
        next block boundary, where the engine discards that block's
        output, finishes it "cancelled", and frees the lane for the next
        queued request. Finished/rejected requests are a no-op."""
        if req.state == WAITING:
            if self.scheduler.remove(req):
                if req.deadline is not None:
                    self._waiting_deadlines -= 1
                self._finish_unadmitted(req, "cancelled", smetrics.now())
        elif req.state == ACTIVE:
            req.cancelled = True

    # --------------------------------------------------------------- step

    def has_work(self) -> bool:
        return bool(len(self.scheduler)) or self.pool.n_active > 0

    def step(self) -> list[Request]:
        """One engine iteration: admit + prefill, then one decode block.

        Returns the requests that FINISHED this iteration.

        Supervised (the fault boundary): any exception escaping the
        iteration — a real `XlaRuntimeError`, a device OOM, or an
        injected fault — is classified (`serve.faults.classify_failure`)
        and answered with a bounded pool-rebuild retry (active streams
        requeue and resume by recompute, token-exactly — the
        preemption argument); after `fault_max_retries` consecutive
        failures the engine drains to `unhealthy` (every in-flight
        stream finishes "error" with its terminal client envelope,
        /healthz flips to 503) and re-arms after a backoff. NaN/Inf
        forwards never raise: the traced finite-logits guard pins them
        to a slot, which `_quarantine` contains below the step
        boundary. A watchdog flags steps exceeding
        `fault_step_deadline_s`; the degradation ladder (if armed)
        re-evaluates its pressure signals after every step.
        """
        if self._health == "unhealthy":
            now = smetrics.now()
            if now < self._recover_at:
                # draining: no device work until the backoff elapses (a
                # tight external drive loop must not busy-spin)
                time.sleep(min(0.005, self._recover_at - now))
                self._step_idx += 1
                self._timeseries_tick()
                return []
            self._recover()
        t0 = smetrics.now()
        try:
            finished = self._step_inner()
        except Exception as exc:  # noqa: BLE001 — the fault boundary
            # no watchdog check on this path: the boundary's own
            # recovery work (pool rebuild + backoff sleep) is not a
            # wedged step — the incident is already accounted as
            # serve/fault_retries, and double-reporting it as a stall
            # would page operators twice for one failure
            finished = self._systemic_failure(exc)
        else:
            ddl = self.config.fault_step_deadline_s
            if ddl is not None:
                dur = smetrics.now() - t0
                if dur > ddl:
                    self._watchdog_fire(dur)
            if self._failed_since is not None:
                # first clean step after a failure episode
                self._note_recovery()
        if self._ladder is not None:
            self._ladder_step()
        self._timeseries_tick()
        return finished

    def _timeseries_tick(self) -> None:
        """Opportunistic rolling-retrospective sample: append one
        window of load gauges + per-window counter/histogram deltas
        when `timeseries_interval_s` has elapsed. Rides step() (no
        timer thread), so an idle engine stops producing windows —
        the gap in the ring IS the record of the idle stretch."""
        ts = self.timeseries
        if ts is None or not ts.due():
            return
        snap = self.metrics.snapshot()
        gauges = {
            "occupancy": round(self.pool.occupancy, 4),
            "queue_depth": float(len(self.scheduler)),
            "n_free": float(self.pool.n_free),
        }
        if getattr(self.pool, "page_budget", 0):
            gauges["pages_free"] = float(self.pool.pages_free)
        cumulative = {
            k: float(snap[k]) for k in (
                "serve/tokens_out", "serve/tokens_prefilled",
                "serve/requests_finished", "serve/requests_rejected",
                "serve/steps",
            ) if k in snap
        }
        # histogram deltas: count/sum increments per window — enough
        # to recover windowed mean latency without O(n) percentiles
        for name, h in self.metrics._latency_hists():
            cumulative[f"serve/{name}_count"] = float(h.count)
            cumulative[f"serve/{name}_sum"] = float(h.sum)
        ts.sample(gauges, cumulative)

    def _step_inner(self) -> list[Request]:
        if not self._profile_done:
            self._profile_tick()
        tr = self.trace
        t_step = smetrics.now() if tr is not None else 0.0
        finished: list[Request] = []
        now = smetrics.now()
        if self._waiting_deadlines > 0:
            expired = [r for r in self.scheduler.queue
                       if r.deadline is not None and now >= r.deadline]
            for req in expired:
                self.scheduler.remove(req)
                self._waiting_deadlines -= 1
                self._finish_unadmitted(req, "timeout", now)
                finished.append(req)
        n_admitted = 0
        if self._paged:
            self._unblock_head()
        picked = self.scheduler.pick(self.pool.n_free, self.pool.n_active)
        at = -1
        try:
            for at, req in enumerate(picked):
                if req.deadline is not None:
                    self._waiting_deadlines -= 1  # left the queue via pick
                n_admitted += 1
                if self._admit(req):
                    finished.append(req)  # prefill-only finish (eos/budget 1)
        except BaseException:
            # failure-safe admission: `pick` already popped this
            # iteration's batch off the queue, so a program failure mid
            # loop would silently LOSE the not-yet-admitted tail (the
            # raising request itself is registered in _slot_req before
            # any dispatch and the fault boundary's rebuild requeues it
            # from there). Put the tail back at the head, order
            # preserved, before the boundary sees the exception. No
            # _waiting_deadlines adjustment: the tail never reached its
            # per-request decrement above, so the counter still counts
            # it — incrementing here would double-count forever.
            for r in reversed(picked[at + 1:]):
                self.scheduler.requeue_front(r)
            raise
        decode_slots = self.pool.n_active
        if decode_slots > 0:
            finished.extend(self._decode_block())
        # anti-starvation clock in DELIVERED-TOKEN units: a speculative
        # step that committed several blocks' worth of tokens ages the
        # waiting queue proportionally (weight = max per-slot delivered /
        # decode_block, floored at 1), so a high-acceptance batch cannot
        # starve the wait budget — plain blocks keep weight 1 exactly
        self.scheduler.tick(self._tick_weight)
        self._tick_weight = 1.0
        self.metrics.record_step(self.pool.occupancy)
        # only steps that did work are traced/monitored: an external
        # serving loop may poll step() while idle, and feeding those
        # ~microsecond no-ops into the ring (spam) and the anomaly
        # monitor's rolling median would make the FIRST real step look
        # like a slow-step anomaly and dump on every step after it
        if tr is not None and (n_admitted or decode_slots or finished):
            now = smetrics.now()
            dur = now - t_step
            tr.complete(
                "step", "engine", "engine", ts=t_step, dur=dur,
                prefills=n_admitted, decode_slots=decode_slots,
                # host->device control transfers: 3 per prefill (prompt +
                # int ctl + float samp), 2 per decode call (packed state +
                # samp block) — the dispatch cost the packed mirrors bound
                transfers=3 * n_admitted + (2 if decode_slots else 0),
                device_s=round(self._dev_s, 6),
            )
            tr.counter("queue_depth", "engine", "engine", ts=now,
                       depth=len(self.scheduler))
            tr.counter("active_slots", "engine", "engine", ts=now,
                       active=self.pool.n_active)
            self._dev_s = 0.0
            # the monitor's rolling median sees only steps that ran a
            # program: purge-only steps (deadline expiries) are traced
            # above but, like idle polls, complete in ~microseconds and
            # would collapse the median until every real step looks slow
            if self._mon is not None and (n_admitted or decode_slots):
                self._mon.observe_step(dur)
        # the journal's batched durability point: ONE fsync per step
        # covering every record the step appended (submit records ride
        # the next step's sync — a kill loses at most one step's worth,
        # the same boundary tokens commit to streams at). Gated on
        # dirty so idle polls never touch the fault-plane visit counter.
        if self.journal is not None and self.journal.dirty:
            self._journal_op(self.journal.sync)
        self._step_idx += 1
        return finished

    # accumulated device time (block_until_ready-fenced program calls)
    # within the current step; only maintained while tracing
    _dev_s = 0.0

    def _profile_tick(self) -> None:
        """Open/close the jax.profiler window around engine steps
        [profile_steps[0], profile_steps[1]) — same stop-before-start
        ordering as the train loop so a window never opens empty."""
        cfg = self.config
        if self._profiling and self._step_idx >= cfg.profile_steps[1]:
            jax.profiler.stop_trace()
            self._profiling = False
            self._profile_done = True
        if (not self._profiling and not self._profile_done
                and self._step_idx >= cfg.profile_steps[0]):
            jax.profiler.start_trace(cfg.profile_dir)
            self._profiling = True

    def _scope(self, name: str):
        """TraceAnnotation around a jitted-program call when tracing or
        profiling is on (labels the program inside XLA traces), a shared
        nullcontext otherwise — ONE call site per program, so operand
        changes cannot silently diverge an annotated copy."""
        if self._annotate:
            return jax.profiler.TraceAnnotation(name)
        return self._null_scope

    _null_scope = contextlib.nullcontext()

    def stop_profile(self) -> None:
        """Close a still-open profiler window (external step() drivers
        that stop before `profile_steps[1]`); run() calls this on drain."""
        if self._profiling:
            jax.profiler.stop_trace()
            self._profiling = False
            self._profile_done = True

    # ------------------------------------------------ fault boundary

    @property
    def health(self) -> str:
        """The /healthz state machine: ``"healthy"`` -> ``"degraded"``
        (the ladder is on a rung > 0 — still serving, a load balancer
        should keep it) -> ``"unhealthy"`` (draining after persistent
        systemic failures; /healthz answers 503 until recovery).
        Reports readiness, not the raw internal flag: once the recovery
        backoff elapses the engine IS ready (the pool was rebuilt at the
        unhealthy transition; the next submission flips the flag), so
        /healthz must return to 200 then — a load balancer that dropped
        the replica on 503 routes no traffic, and a health view gated
        on traffic arriving would keep it out of rotation forever."""
        if (self._health == "unhealthy"
                and smetrics.now() < self._recover_at):
            return "unhealthy"
        if self._ladder is not None and self._ladder.rung > 0:
            return "degraded"
        return "healthy"

    @property
    def degradation_rung(self) -> int:
        """Current ladder rung (0 = normal; 0 when the ladder is off)."""
        return self._ladder.rung if self._ladder is not None else 0

    def _poke_site(self, site: str) -> int:
        """Fault-plane hook at a named hot-path site (one `is None`
        branch when disarmed). Applies host-side effects — ``stall``
        sleeps here, ``xla_error``/``oom`` raise a synthetic
        `InjectedFault` the step boundary classifies like the real
        thing — and routes logits poison: returned as the ctl code for
        prefill sites, written to the per-slot fault row for decode
        sites (cleared after the dispatch it rides)."""
        if self._faults is None:
            return 0
        code = 0
        for spec in self._faults.poke(site):
            self.metrics.record_fault_injected()
            if self.trace is not None:
                self.trace.instant("fault_injected", "engine", "engine",
                                   site=site, kind=spec.kind,
                                   slot=spec.slot)
            if spec.kind == "stall":
                time.sleep(spec.stall_s)
            elif spec.kind in ("xla_error", "oom", "io_error"):
                raise InjectedFault(spec.kind, site)
            elif spec.kind in ("nan", "inf"):
                k = FAULT_NAN if spec.kind == "nan" else FAULT_INF
                if site == "prefill":
                    code = k
                else:
                    self._fault_row[spec.slot % self.config.n_slots] = k
            # socket_reset belongs to the front door's sse_write site
        return code

    def _systemic_failure(self, exc: Exception) -> list[Request]:
        """A step escaped with an exception: the in-flight program's
        donated pool buffers are unusable, so the remedy is rebuild —
        bounded retries first (streams requeue and resume by recompute,
        token-exactly), then the draining `unhealthy` state."""
        kind = classify_failure(exc)
        err = f"{type(exc).__name__}: {exc}"
        now = smetrics.now()
        self._consec_failures += 1
        self._last_error = err
        if self._failed_since is None:
            self._failed_since = now
        if self._consec_failures <= self.config.fault_max_retries:
            # counted only when a rebuild retry is actually granted —
            # the failure that EXHAUSTS the budget is accounted as the
            # unhealthy transition below, not as a retry
            self.metrics.record_engine_retry()
        if self.trace is not None:
            self.trace.instant("engine_fault", "engine", "engine", ts=now,
                               kind=kind, error=err[:200],
                               failures=self._consec_failures)
            if self._mon is not None:
                self._mon.dump("engine_fault", failure_kind=kind,
                               error=err[:500],
                               consecutive=self._consec_failures)
        if self._consec_failures > self.config.fault_max_retries:
            return self._go_unhealthy(err)
        self._rebuild_pool(requeue=True)
        time.sleep(min(
            self.config.fault_retry_backoff_s
            * (2 ** (self._consec_failures - 1)), 2.0,
        ))
        return []

    def _go_unhealthy(self, err: str) -> list[Request]:
        """Retries exhausted: drain — every in-flight and queued request
        finishes "error" host-side (each client gets its terminal
        envelope; slots/pages/exact lanes reclaim leak-free), the pool
        rebuilds so recovery starts from fresh fully-owned buffers, and
        /healthz reports 503 until the recovery backoff elapses."""
        self._health = "unhealthy"
        now = smetrics.now()
        self._recover_at = now + self._backoff
        # doubles across consecutive unhealthy episodes; a clean step
        # (via _note_recovery) resets it
        self._backoff = min(self._backoff * 2, 30.0)
        self.metrics.record_engine_unhealthy()
        if self.trace is not None:
            self.trace.instant("unhealthy", "engine", "engine", ts=now,
                               error=err[:200],
                               recover_after_s=round(
                                   self._recover_at - now, 3))
        finished = self.force_drain("error")
        self._rebuild_pool(requeue=False)
        return finished

    def _recover(self) -> None:
        """Re-arm an unhealthy engine (the pool was rebuilt at the
        unhealthy transition, so this is a host-side state flip)."""
        self._health = "healthy"
        self._consec_failures = 0
        if self.trace is not None:
            self.trace.instant("recovered", "engine", "engine",
                               ts=smetrics.now())

    def _note_recovery(self) -> None:
        """First clean step after a failure episode: stamp the
        wall-clock recovery time (first failure -> first clean step)."""
        now = smetrics.now()
        if self._failed_since is not None:
            self.metrics.record_recovery(now - self._failed_since)
            if self.trace is not None:
                self.trace.instant(
                    "fault_recovered", "engine", "engine", ts=now,
                    recovery_s=round(now - self._failed_since, 4),
                )
        self._failed_since = None
        self._consec_failures = 0
        self._backoff = self.config.fault_recover_backoff_s

    # ----------------------------------------------- write-ahead journal

    def _journal_op(self, fn, *args) -> None:
        """Run one journal operation inside the durability-failure
        boundary: the fault plane's ``journal_write`` site pokes first
        (an ``io_error`` spec raises here, exactly where a real disk
        failure would), and any I/O failure degrades the engine to
        journal-off with ONE warning and the serve/journal_degraded
        gauge — serving must survive losing its journal — unless
        `journal_strict` deliberately lets the failure propagate."""
        if self.journal is None or self._journal_degraded:
            return
        try:
            self._poke_site("journal_write")
            fn(*args)
        except (JournalError, OSError, InjectedFault) as exc:
            if isinstance(exc, InjectedFault) and exc.kind != "io_error":
                raise
            if self.config.journal_strict:
                raise
            self._journal_degraded = True
            warnings.warn(
                f"write-ahead journal failed ({type(exc).__name__}: "
                f"{exc}) — degrading to journal-off: serving continues, "
                "crash recovery and stream resumption are LOST from "
                "here (set ServeConfig.journal_strict to fail loudly "
                "instead)",
                stacklevel=2,
            )
            if self.trace is not None:
                self.trace.instant("journal_degraded", "engine", "engine",
                                   error=str(exc)[:200])

    def _journal_submit(self, req: Request) -> None:
        if self.journal is None:
            return
        if req.trace_id is None or self.journal.is_live(req.trace_id):
            # a journaled request must be addressable after a restart —
            # and a client RE-USING a still-live X-Request-Id must not
            # merge two streams' commits into one journal record (the
            # in-memory registry keeps its documented last-wins
            # behavior; the duplicate gets a fresh durable id)
            req.trace_id = uuid.uuid4().hex
        # grammar steppers are host state the journal cannot replay:
        # such a request is journaled for INSPECTION but flagged, and
        # recovery finishes it "error" instead of resuming it
        self._journal_op(
            self.journal.append_submit, req.trace_id, req.prompt,
            req.max_new_tokens, req.eos_id,
            dataclasses.asdict(req.params), req.submit_time,
            req.grammar is not None,
            None if req.deadline is None
            else max(req.deadline - req.submit_time, 1e-3),
        )

    def _journal_commit(self, req: Request, tokens) -> None:
        if self.journal is not None and len(tokens):
            self._journal_op(self.journal.append_commit, req.trace_id,
                             tokens)

    def _journal_finish(self, req: Request) -> None:
        if self.journal is not None:
            self._journal_op(self.journal.append_finish, req.trace_id,
                             req.finish_reason or "unknown", {
                                 "prompt_tokens": int(req.prompt.size),
                                 "completion_tokens": len(req.tokens),
                             })

    def _journal_gauges(self) -> dict[str, float]:
        """Journal gauges riding every metrics snapshot (registered iff
        `journal_path` — the present-iff-enabled key-surface contract
        of the paged/spec/observatory gauges)."""
        s = self.journal.stats()
        return {
            "serve/journal_records": float(s["records"]),
            "serve/journal_bytes": float(s["bytes_written"]),
            "serve/journal_rotations": float(s["rotations"]),
            "serve/journal_fsync_s": s["fsync_s"],
            "serve/journal_live": float(s["live"]),
            "serve/journal_degraded": float(self._journal_degraded),
            "serve/recovered_requests": float(self._recovered_total),
        }

    def _entry_request(self, e) -> tuple[Request | None, str | None]:
        """Validate + materialize one live journal entry as a resumable
        `Request` carrying its committed tokens — the shared core of
        `recover()` (crash restart) and `adopt()` (fleet migration).

        Returns ``(request, None)`` for an entry this engine can honor:
        the request is WAITING with its deadline re-armed RELATIVE from
        now (absolute deadlines cannot cross a process/replica boundary
        — monotonic clocks differ), or already FINISHED with its stop
        reason when the committed stream satisfies a finish condition
        (the crash/drain landed between the final commit and its finish
        record). Returns ``(None, reason)`` for an entry this engine
        cannot resume token-exactly: grammar requests (host stepper
        state), an unparseable params record, a prompt beyond this
        engine's capacity, stop strings without `detokenize`, or
        kv_exact without sidecar lanes. An SLO class this engine does
        not track is dropped, not fatal — the class is accounting, not
        semantics."""
        limit = getattr(self.model, "max_positions", None)
        cap = min(self.config.max_len, limit or self.config.max_len)
        err = None
        params = None
        if e.grammar:
            err = "grammar stepper state is not journaled"
        else:
            try:
                p = dict(e.params)
                p["stop_token_ids"] = tuple(
                    p.get("stop_token_ids") or ())
                p["stop"] = tuple(p.get("stop") or ())
                params = SamplingParams(**p)
            except (TypeError, ValueError) as exc:
                err = f"unreplayable params: {exc}"
        if err is None:
            if len(e.prompt) < 1 or \
                    len(e.prompt) + e.max_new_tokens > cap:
                err = f"beyond this engine's capacity {cap}"
            elif params.stop and self.detokenize is None:
                err = "stop strings need a detokenize callable"
            elif (params.kv_exact and self._quant
                  and not self.config.kv_exact_lanes):
                err = "kv_exact needs exact sidecar lanes"
            elif params.slo is not None and (
                self._slo is None or params.slo not in self._slo.targets
            ):
                # the SLO class is accounting, not semantics: keep
                # the stream, drop the untracked tag
                params = dataclasses.replace(params, slo=None)
        if err is not None:
            return None, err
        req = Request(
            prompt=np.asarray(e.prompt, np.int32),
            max_new_tokens=e.max_new_tokens,
            eos_id=e.eos_id, params=params,
        )
        req.trace_id = e.rid
        req.tokens = [int(t) for t in e.tokens]
        if e.deadline_s is not None:
            # absolute deadlines cannot cross a restart (monotonic
            # clocks reset), so the recovered request re-arms its
            # ORIGINAL relative budget from now — bounded again,
            # not unbounded
            req.deadline = req.submit_time + e.deadline_s
        reason = (self._stop_reason(req, req.tokens[-1])
                  if req.tokens else None)
        if (reason is None and req.tokens and params.stop
                and self._stop_string_at(req, 0) is not None):
            # commits are written AFTER stop-string truncation, so
            # a committed stream never extends past a match — any
            # match here means the stream was complete at the crash
            reason = "stop"
        if reason is not None:
            req.state = FINISHED
            req.finish_reason = reason
            req.finish_time = smetrics.now()
        return req, None

    def adopt(self, entry) -> Request:
        """Adopt a live journal entry from ANOTHER replica's journal —
        the fleet router's stream-migration primitive (serve/fleet.py
        `FleetRouter.drain`). The drained replica force-finishes the
        stream ``"migrated"``; this engine continues it through the same
        preemption-resume machinery `recover()` uses (re-prefill prompt
        + committed tokens, discard the resampled token — TOKEN-EXACT
        for greedy and seeded plain-decode streams, the journal
        contract). Call with this engine's step lock held (the
        EngineLoop lock): adoption touches the scheduler queue and the
        journal the engine thread also owns.

        The adopted request is journaled into THIS engine's journal
        when it has one (submit + committed prefix — a crash after the
        migration recovers the stream HERE), registered in the
        recovered set so Last-Event-ID reconnects resolve through the
        same path as a crash restart, and requeued at the FRONT of the
        queue (it predates everything waiting — the same FIFO-survives
        rule as `recover()`; when migrating several entries, adopt them
        newest-first so the oldest ends at the head). Note the journal
        submit re-keys `trace_id` if this engine already has a live
        journal entry under the same id — read the id back from the
        returned request. An entry whose committed stream already
        satisfies a finish condition comes back FINISHED (journaled
        through to its finish record) instead of requeued.

        Raises ValueError for an entry this engine cannot resume
        token-exactly (see `_entry_request`) — the caller decides how
        to surface the failed migration; nothing is enqueued."""
        req, err = self._entry_request(entry)
        if err is not None:
            raise ValueError(
                f"journal entry {entry.rid} cannot be adopted ({err})")
        self._journal_submit(req)
        self._journal_commit(req, req.tokens)
        if req.done:
            self._journal_finish(req)
        else:
            # bypasses max_waiting like requeue_front's preemption case:
            # the stream was already admitted once, on the drained peer
            self.scheduler.requeue_front(req)
            if req.deadline is not None:
                self._waiting_deadlines += 1
        self._recovered[req.trace_id] = req
        self._recovered_total += 1
        if self.trace is not None:
            self.trace.instant(
                "journal_adopt", "engine", "engine", rid=req.trace_id,
                committed=len(req.tokens), done=req.done,
            )
        return req

    def recover(self) -> list[Request]:
        """Replay the journal's unfinished entries through the
        preemption-resume machinery: each live entry becomes a WAITING
        request carrying its committed tokens; admission re-prefills
        prompt + committed[:-1], discards the resampled token and
        continues decoding — TOKEN-EXACT vs an uninterrupted run for
        greedy streams (any configuration) and seeded stochastic
        streams on the plain decode path (seeded chains fold only
        (seed, sample index); tests/test_journal.py pins it across
        pools and kv_quant; under speculation stochastic streams are
        distribution-exact, the live-preemption contract). Call ONCE
        at boot, before the first step.

        Entries the new engine cannot honor resume-exactly — grammar
        requests (host stepper state), stop-string requests on an
        engine without `detokenize`, kv_exact without sidecar lanes, a
        prompt beyond this engine's capacity, or an unparseable params
        record — finish ``"error"`` in the journal instead of being
        silently dropped. An entry whose committed stream already
        satisfies a stop condition (the crash landed between its final
        commit and its finish record) is finished with that reason.
        Returns the requests actually requeued (oldest first); the
        journal is compacted to exactly that live set."""
        if self.journal is None:
            raise ValueError(
                "recover() replays the write-ahead journal, which needs "
                "ServeConfig.journal_path set"
            )
        resumed: list[Request] = []
        for e in self.journal.live_entries():
            usage = {"prompt_tokens": len(e.prompt),
                     "completion_tokens": len(e.tokens)}
            req, err = self._entry_request(e)
            if err is not None:
                warnings.warn(
                    f"journal entry {e.rid} cannot be recovered ({err}) "
                    "— finishing it \"error\"", stacklevel=2,
                )
                self._journal_op(self.journal.append_finish, e.rid,
                                 "error", usage)
                continue
            if req.done:
                # the crash landed between the final commit and its
                # finish record: the stream is already complete
                self._journal_op(self.journal.append_finish, e.rid,
                                 req.finish_reason, usage)
                continue
            resumed.append(req)
        # oldest ends at the queue head: FIFO order survives the crash
        for req in reversed(resumed):
            self.scheduler.requeue_front(req)
            if req.deadline is not None:
                self._waiting_deadlines += 1
        self._recovered = {r.trace_id: r for r in resumed}
        self._recovered_total = len(resumed)
        # compact to exactly the live set (and make it durable): a
        # recovered journal starts O(active), not O(crash history)
        self._journal_op(self.journal.compact)
        self._journal_op(self.journal.sync)
        if self.trace is not None:
            self.trace.instant("journal_recover", "engine", "engine",
                               resumed=len(resumed))
        return resumed

    def _rebuild_pool(self, requeue: bool) -> None:
        """Replace the device pool with fresh buffers after a systemic
        failure (a raising jitted call may have consumed its donated
        inputs — the old pytree cannot be trusted). With `requeue`,
        every active stream returns to the queue head ordered oldest-
        first and resumes by recompute: cached KV depends only on token
        ids and seeded chains fold only (seed, sample index), so
        resumed streams are token-exact (the preemption argument). The
        prefix cache is dropped wholesale — lane segments may alias
        rebuilt state and paged trees hold page ids into the dead pool."""
        cfg = self.config
        if requeue:
            active = [r for r in self._slot_req if r is not None]
            # youngest requeued first so the OLDEST ends at the head
            active.sort(key=lambda r: r.admit_time or 0.0, reverse=True)
            for req in active:
                if self._paged and req.slot is not None:
                    req.pages_held = max(
                        req.pages_held, int(self.pool.n_alloc[req.slot])
                    )
                req.slot = None
                self.scheduler.requeue_front(req)
                if req.deadline is not None:
                    self._waiting_deadlines += 1
        self._slot_req = [None] * cfg.n_slots
        self._toks[:] = 0
        self._pos[:] = 0
        self._samp_f[:] = np.asarray(GREEDY_ROW, np.float32)[:, None]
        self._allow[:] = -1
        self._top_k[:] = 0
        self._seed[:] = -1
        self._need_lp[:] = 0
        self._fault_row[:] = 0
        self._eidx[:] = 0
        self._exact_free = list(range(cfg.kv_exact_lanes, 0, -1))
        if self._paged:
            page = cfg.page_size or cfg.prefix_page
            self.pool = PagedKVPool(
                self.model, cfg.n_slots, cfg.max_len, page,
                page_budget=cfg.page_budget, quant=cfg.kv_quant,
                exact_lanes=cfg.kv_exact_lanes,
            )
        else:
            self.pool = KVSlotPool(
                self.model, cfg.n_slots, cfg.max_len, quant=cfg.kv_quant,
                quant_block=cfg.kv_quant_block,
                exact_lanes=cfg.kv_exact_lanes,
            )
            if self.registry is not None:
                self.pool.registry = self.registry
        if self._mtp_pool is not None:
            from solvingpapers_tpu.infer.cache import LatentCache

            dim = self.model.cfg.latent_dim + self.model.cfg.rope_dim
            self._mtp_pool = tuple(
                LatentCache.init(
                    cfg.n_slots, cfg.max_len + self._spec_k + 1, dim,
                    self.model.cfg.compute_dtype,
                )
                for _ in range(self._spec_k)
            )
            self._next_drafts[:] = 0
        if self.prefix_cache is not None:
            self.prefix_cache = PrefixCache(
                page=cfg.prefix_page, max_bytes=cfg.prefix_cache_bytes,
                trace=self.trace,
                pool=self.pool if cfg.paged else None,
            )
            self.metrics.record_prefix_state(0, self.prefix_cache.evictions)

    def _watchdog_fire(self, dur_s: float) -> None:
        """A step exceeded the absolute deadline: count it, stamp a
        trace instant, and (when the anomaly dumper is armed) dump the
        flight-recorder tail for the post-mortem."""
        self.metrics.record_watchdog_stall(dur_s)
        if self.trace is not None:
            self.trace.instant(
                "watchdog_stall", "engine", "engine",
                step_s=round(dur_s, 4),
                deadline_s=self.config.fault_step_deadline_s,
            )
            if self._mon is not None:
                self._mon.dump(
                    "watchdog_stall", step_s=round(dur_s, 4),
                    deadline_s=self.config.fault_step_deadline_s,
                )

    def _quarantine(self, req: Request, now: float) -> Request:
        """Blast-radius containment for a NaN/Inf-poisoned slot: the
        block's tokens are discarded (drawn from non-finite logits), the
        slot's lane/pages are SCRUBBED to zero before release (masked
        attention annihilates finite stale values exactly, but
        ``0 * NaN`` is NaN — an unscrubbed poisoned lane would leak into
        its next occupant), and the request finishes "error". Every
        other stream — computed in the same program call from its own
        per-slot lane — continues byte-identically."""
        slot = req.slot
        self.metrics.record_quarantine()
        # a prefill-poisoned request has no first token: _finish closes
        # its lifecycle spans with a zero-width prefill phase
        if self.trace is not None:
            self.trace.instant("quarantine", "engine", f"slot{slot}",
                               req=req.id, ts=now, tokens=len(req.tokens))
            if self._mon is not None:
                self._mon.dump("quarantine", req=req.id, slot=slot)
        self._scrub_slot(slot)
        self._finish(req, "error", now)
        self._notify(req, 0)
        return req

    def _scrub_slot(self, slot: int) -> None:
        """Zero a poisoned slot's device state before its storage is
        reused (see `_quarantine`). Paged pools scrub only the slot's
        exclusively-owned pages — shared prefix pages hold KV written
        strictly before the poisoned step and other holders still read
        them — plus the trash page, where the poisoned slot's masked
        overshoot writes land."""
        eidx = jnp.int32(int(self._eidx[slot]) if self._quant else 0)
        if self._paged:
            n = int(self.pool.n_alloc[slot])
            own = [int(p) for p in self.pool.table[slot, :n]
                   if self.pool.refcount[p] == 1]
            row = np.full(self.pool.pages_per_lane + 1, TRASH_PAGE,
                          np.int32)
            row[:len(own)] = own
            self.pool.phys = scrub_pages_program(
                self.pool.phys, jnp.asarray(row), eidx
            )
        else:
            self.pool.caches = scrub_lane_program(
                self.pool.caches, jnp.int32(slot), eidx
            )
            if self._mtp_pool is not None:
                self._mtp_pool = tuple(
                    scrub_lane_program(c, jnp.int32(slot), jnp.int32(0))
                    for c in self._mtp_pool
                )

    def force_drain(self, reason: str = "cancelled") -> list[Request]:
        """Finish every in-flight and queued request host-side — no
        device work, so it cannot hang on a wedged program. The
        bounded-shutdown backstop (`close`) and the unhealthy drain
        (`reason="error"`); slots, pages and exact lanes reclaim through
        the ordinary finish paths, so the pool drains leak-free."""
        now = smetrics.now()
        finished: list[Request] = []
        for req in [r for r in self._slot_req if r is not None]:
            self._finish(req, reason, now)
            self._notify(req, 0)
            finished.append(req)
        for req in list(self.scheduler.queue):
            self.scheduler.remove(req)
            self._finish_unadmitted(req, reason, now)
            finished.append(req)
        self._waiting_deadlines = 0
        return finished

    def _ladder_step(self) -> None:
        """One degradation-ladder evaluation (per engine step): gather
        the pressure signals, move at most one rung (hysteresis lives in
        the ladder), and apply the current rung's effects. Rung 1 sheds
        a few prefix-cache leaves per step (gradual — a short spike must
        not destroy the whole cache); rung 2 additionally holds
        speculation; rungs 3/4 shed admissions in `submit`."""
        cfg = self.config
        reasons = []
        if self._paged and (self.pool.pages_free
                            < cfg.degrade_free_page_frac
                            * self.pool.page_budget):
            reasons.append("pages")
        if self.ledger is not None and self.ledger.capacity_bytes:
            peak = self.ledger.projected_peak_bytes()
            if peak > (1.0 - cfg.degrade_headroom_frac) \
                    * self.ledger.capacity_bytes:
                reasons.append("hbm")
        if self._slo is not None:
            for cls in self._slo.targets:
                if self._slo.burn_rate(cls) > cfg.degrade_burn_threshold:
                    reasons.append(f"burn:{cls}")
                    break
        new = self._ladder.observe(bool(reasons), reasons)
        if new is not None:
            self.metrics.record_degrade_transition()
            if self.trace is not None:
                self.trace.instant(
                    "degrade", "engine", "engine", rung=new,
                    name=self._ladder.name,
                    reasons=",".join(reasons) or "clear",
                )
        rung = self._ladder.rung
        if rung >= 1 and self.prefix_cache is not None:
            shed = 0
            while shed < 4 and self.prefix_cache.evict_one():
                shed += 1
            if shed:
                self.metrics.record_prefix_state(
                    self.prefix_cache.bytes_held,
                    self.prefix_cache.evictions,
                )
        if rung >= 2 and self._spec_ctl is not None:
            self._spec_ctl.hold(2)

    def statusz(self) -> dict:
        """The /statusz document: live engine state assembled from
        host-side mirrors only (safe to call from the status server's
        request threads while the engine steps)."""
        d = {
            # build identity FIRST: a scraped replica must be
            # identifiable (which build, which jax, how long up) before
            # any of its numbers are aggregated — ROADMAP item 2's
            # per-replica prerequisite
            "build": buildinfo.build_info(),
            "engine": {
                "n_slots": self.config.n_slots,
                "n_free": self.pool.n_free,
                "occupancy": self.pool.occupancy,
                "queue_depth": len(self.scheduler),
                "step": self._step_idx,
                "max_len": self.config.max_len,
                "decode_block": self.config.decode_block,
            },
            "slots": [
                {
                    "slot": i,
                    "req": None if r is None else r.id,
                    "position": int(self.pool.positions[i]),
                }
                for i, r in enumerate(self._slot_req)
            ],
            "metrics": self.metrics.snapshot(),
        }
        m = self.metrics
        d["health"] = {
            "state": self.health,
            "consecutive_failures": self._consec_failures,
            "last_error": self._last_error,
            "quarantines": m.quarantines,
            "retries": m.engine_retries,
            "unhealthy_episodes": m.engine_unhealthy,
            "watchdog_stalls": m.watchdog_stalls,
        }
        if self._faults is not None:
            d["health"]["fault_plan"] = self._faults.stats()
        if self._ladder is not None:
            d["health"]["ladder"] = self._ladder.stats()
        if self.journal is not None:
            d["journal"] = {
                **self.journal.stats(),
                "strict": self.config.journal_strict,
                "degraded": self._journal_degraded,
                "recovered_requests": self._recovered_total,
            }
        if self._paged:
            d["kv_pages"] = {
                "page_size": self.pool.page_size,
                "page_budget": self.pool.page_budget,
                "pages_free": self.pool.pages_free,
                "pages_active": self.pool.pages_active,
                "fragmentation": self.pool.fragmentation,
                "per_slot_pages": self.pool.n_alloc.tolist(),
            }
        if self._quant:
            pool = self.pool
            store = pool.phys if self._paged else pool.caches
            pool_bytes, scale_bytes, exact_bytes, base_bytes = \
                quant_pool_bytes(store)
            d["kv_quant"] = {
                "mode": self.config.kv_quant,
                "quant_block": pool.quant_block,
                "kv_pool_bytes": pool_bytes + exact_bytes,
                "quant_bytes": pool_bytes,
                "scale_bytes": scale_bytes,
                "exact_bytes": exact_bytes,
                "baseline_bytes": base_bytes,
                "bytes_ratio": round(pool_bytes / base_bytes, 4),
                "exact_lanes": pool.exact_lanes,
                "exact_lanes_free": len(self._exact_free),
                "exact_slots": [
                    i for i, e in enumerate(self._eidx) if e
                ],
            }
        if self._spec is not None:
            m = self.metrics
            d["spec"] = {
                "drafter": self._spec,
                "k": self._spec_k,
                "rounds": self._spec_rounds,
                "steps": m.spec_steps,
                "drafts_proposed": m.spec_proposed,
                "drafts_accepted": m.spec_accepted,
                "acceptance_rate": round(
                    m.spec_accepted / m.spec_proposed, 4
                ) if m.spec_proposed else 0.0,
                "tokens_per_step": round(
                    m.spec_tokens / m.spec_steps, 2
                ) if m.spec_steps else 0.0,
                **self._spec_ctl.stats(),
            }
        if self._slo is not None:
            d["slo"] = self._slo.statusz()
        if self.prefix_cache is not None:
            d["prefix_cache"] = self.prefix_cache.stats()
        if self.registry is not None:
            d["compile"] = self.registry.snapshot()
        if self.ledger is not None:
            d["mem"] = self.ledger.snapshot()
        if self.timeseries is not None and len(self.timeseries):
            # the human rendering of the rolling retrospective: one
            # sparkline per series (right edge = now); the raw rows
            # live on /timeseriesz
            d["timeseries"] = {
                "interval_s": self.timeseries.interval_s,
                "windows": len(self.timeseries),
                "sparklines": self.timeseries.sparklines(),
            }
        return d

    def close(self, drain_s: float = 0.0) -> None:
        """Bounded shutdown: drive step() for up to `drain_s` seconds of
        graceful drain, then FORCE-CANCEL whatever is still in flight
        host-side (`force_drain`) — so SIGTERM can never hang on a
        wedged request (the deadline is checked before every step; a
        single stalled step can overrun it by at most its own duration,
        after which no further device work is dispatched). Releases
        external resources (status endpoint, profiler window).
        Idempotent; the engine itself stays usable."""
        deadline = smetrics.now() + drain_s
        while (self.has_work() and self._health != "unhealthy"
               and smetrics.now() < deadline):
            self.step()
        if self.has_work():
            self.force_drain("cancelled")
        if self.journal is not None and self.journal.dirty:
            # make the drain's finish records durable before the
            # process goes away (the journal stays open — the engine
            # itself stays usable after close())
            self._journal_op(self.journal.sync)
        self.stop_profile()
        if self.status is not None:
            self.status.close()
            self.status = None

    def run(self, max_steps: int | None = None) -> None:
        """Drive step() until queue and slots drain (or `max_steps`)."""
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                return
        self.stop_profile()

    # ------------------------------------------------------------ private

    def _bucketed(self, length: int, start: int = 0) -> int:
        b = self.config.bucket
        padded = -(-length // b) * b
        limit = getattr(self.model, "max_positions", None)
        cap = min(self.config.max_len, limit or self.config.max_len) - start
        return max(length, min(padded, cap))

    def _notify(self, req: Request, n_new: int) -> None:
        """Fire the request's streaming hook (engine thread): `n_new`
        tokens were appended (0 for a tokenless finish boundary —
        cancel/timeout), `finished` mirrors the lifecycle state."""
        cb = req.stream_cb
        if cb is not None:
            cb(req, n_new, req.state == FINISHED)

    def _grammar_allow(self, req: Request) -> np.ndarray:
        """The request's current allowed-token list packed into a
        (sample_cap,) allow row. The grammar contract says the list is
        never empty before the document completes (and a completed
        document finishes the request immediately), so emptiness here
        is a stepper bug — failing loudly beats silently decoding
        unconstrained."""
        ids = req.grammar.allowed(req.remaining)
        if not ids:
            raise RuntimeError(
                f"grammar for request {req.id} returned an empty "
                f"allow-list mid-generation (budget {req.remaining}) — "
                "the mask-never-empty contract is broken"
            )
        return encode_allow(ids, self.config.sample_cap)

    def _match_len(self, prompt: np.ndarray) -> int:
        """Cached page-aligned prefix length for `prompt` (read-only; the
        scheduler's admission lookup). Capped at len-1: the suffix prefill
        must produce at least one logits row to sample from."""
        if self.prefix_cache is None or prompt.size < 2:
            return 0
        return self.prefix_cache.peek(prompt[: prompt.size - 1])

    # -------------------------------------------------- paged-pool policy

    def _page_gauges(self) -> dict[str, float]:
        """Page-pool occupancy gauges riding every metrics snapshot
        (registered iff `paged` — the present-iff-enabled key-surface
        contract the observatory gauges set)."""
        pool = self.pool
        return {
            "serve/pages_free": float(pool.pages_free),
            "serve/pages_active": float(pool.pages_active),
            "serve/page_fragmentation": float(pool.fragmentation),
        }

    def _kv_quant_gauges(self) -> dict[str, float]:
        """Quantized-pool byte gauges riding every metrics snapshot
        (registered iff `kv_quant` — the present-iff-enabled key-surface
        contract of the paged/spec/observatory gauges). Byte math is
        analytic (host-side shape sums), never a device read."""
        pool = self.pool
        store = pool.caches if not self._paged else pool.phys
        pool_bytes, scale_bytes, exact_bytes, base_bytes = \
            quant_pool_bytes(store)
        out = {
            # resident KV bytes per bookable token slot: the capacity
            # price of one context token under this pool (int8 payload
            # + scale sidecar; exact lanes are a fixed surcharge the
            # *_exact_* gauges expose separately)
            "serve/kv_bytes_per_token": pool_bytes / pool.token_capacity,
            "serve/kv_quant_scale_bytes": float(scale_bytes),
            # what the same payload would hold at the compute dtype,
            # minus int8 + scales — the ledger-visible capacity win (the
            # exact-lane sidecar is a separately-disclosed surcharge)
            "serve/kv_quant_bytes_saved": float(base_bytes - pool_bytes),
        }
        if pool.exact_lanes:
            out["serve/kv_quant_exact_lanes_free"] = float(
                len(self._exact_free)
            )
            out["serve/kv_quant_exact_active"] = float(
                pool.exact_lanes - len(self._exact_free)
            )
        return out

    def _page_need(self, req: Request) -> int:
        """Pages a waiting request needs to start: prefill coverage of
        its (resume-aware) sequence net of the cached-prefix hint, plus
        one decode block's reservation. Deliberately an ESTIMATE — the
        hint can go stale between gate and admit, and several admissions
        in one iteration share the same free count; `_ensure_pages`'
        reclaim path absorbs any over-admission."""
        pool = self.pool
        if req.tokens:
            seq = np.concatenate(
                [req.prompt, np.asarray(req.tokens[:-1], np.int32)]
            )
        else:
            seq = req.prompt
        matched = 0
        if self.prefix_cache is not None and seq.size > 1:
            matched = self.prefix_cache.peek(seq[: seq.size - 1])
        suffix = int(seq.size) - matched
        padded = self._bucketed(suffix, start=matched)
        need = min(matched + padded + self.config.decode_block,
                   self.config.max_len)
        return pool.pages_for(need) - matched // pool.page_size

    def _can_admit(self, req: Request) -> bool:
        """The scheduler's capacity gate beyond free slots: paged pools
        admit while free pages cover the request's prompt + a decode
        reservation (free SLOTS alone no longer imply capacity — that is
        what decouples slot count from max_seq); kv_exact requests on a
        quantized pool instead need a free full-precision sidecar lane
        (they never consume pages). Estimates can go stale across one
        iteration's picks — `_admit`'s bail paths absorb over-admission."""
        if self._quant and req.params.kv_exact:
            return bool(self._exact_free)
        if self._paged:
            return self.pool.pages_free >= self._page_need(req)
        return True

    def _unblock_head(self) -> None:
        """Shed prefix-tree page references for a page-starved queue
        head BEFORE the scheduler picks. Without this the engine can
        livelock: the tree's references persist after every stream
        drains (that is the cache working as designed), but reclaim
        otherwise only runs inside `_admit`/`_cover_decode` — which a
        blocked `can_admit` gate prevents from ever running again.
        Runs only with the pool fully IDLE: while streams are active,
        their ordinary finish-and-release is what unblocks the head
        (transient backpressure — shedding the tree then would destroy
        the cache for nothing), and active streams are never preempted
        for a WAITING request. Once they all drain, either the head
        fits or only the tree still holds pages — and with the tree
        spent, `page_budget >= pages_per_lane` guarantees any single
        request fits."""
        if (not self.scheduler.queue or self.pool.n_active > 0
                or self.prefix_cache is None):
            return
        head = self.scheduler.queue[0]
        if self._quant and head.params.kv_exact:
            return  # blocked on exact lanes, not pages: the tree can't help
        shed = False
        while (not self._can_admit(head)
               and self.prefix_cache.evict_one()):
            shed = True
        if shed:
            self.metrics.record_prefix_state(
                self.prefix_cache.bytes_held, self.prefix_cache.evictions
            )

    def _ensure_pages(self, slot: int, n_tokens: int) -> bool:
        """Grow `slot`'s page table to cover `n_tokens`, reclaiming
        under pressure: shed prefix-tree references first (cheap — the
        cache is advisory), then preempt the youngest other stream
        (requeue-and-recompute). False only when the pool cannot cover
        this slot even with everything else evicted."""
        while not self.pool.ensure(slot, n_tokens):
            if not self._reclaim_one(protect={slot}):
                return False
        return True

    def _reclaim_one(self, protect: set) -> bool:
        """Free page capacity by one unit: evict one prefix-tree leaf
        (preferred — dropping cache never hurts correctness) or, with
        the tree spent, preempt the YOUNGEST active request not in
        `protect` (latest-admitted loses: it has the least sunk prefill
        work and the oldest streams keep their latency contract). False
        when nothing reclaimable remains. A tree eviction may free zero
        pages (a slot still shares them) — callers loop, and each call
        removes a node or a stream, so the loop terminates."""
        pc = self.prefix_cache
        if pc is not None and pc.evict_one():
            self.metrics.record_prefix_state(pc.bytes_held, pc.evictions)
            return True
        victim = None
        for r in self._slot_req:
            if r is None or r.slot in protect:
                continue
            if self._quant and r.params.kv_exact:
                continue  # exact streams hold no pages: nothing to free
            if victim is None or r.admit_time > victim.admit_time:
                victim = r
        if victim is None:
            return False
        self._preempt(victim)
        return True

    def _preempt(self, req: Request) -> None:
        """Evict an ACTIVE stream on page exhaustion: its pages free
        immediately (shared ones survive under the tree's references —
        often making its own resume a prefix HIT), the request returns
        to the HEAD of the queue, and `_admit`'s resume path recomputes
        its KV when pages free up. Runs only at block boundaries, so no
        in-flight program output is lost."""
        slot = req.slot
        self.metrics.record_preemption()
        req.pages_held = max(req.pages_held, int(self.pool.n_alloc[slot]))
        if self.trace is not None:
            self.trace.instant("preempt", "engine", f"slot{slot}",
                               req=req.id, tokens=len(req.tokens))
        self._slot_req[slot] = None
        self._toks[slot] = 0
        self._pos[slot] = 0
        self._samp_f[:, slot] = GREEDY_ROW
        self._allow[slot] = -1
        self._top_k[slot] = 0
        self._seed[slot] = -1
        self._need_lp[slot] = 0
        if self._eidx[slot]:
            self._exact_free.append(int(self._eidx[slot]))
            self._eidx[slot] = 0
        self.pool.release(slot)
        req.slot = None
        self.scheduler.requeue_front(req)
        if req.deadline is not None:
            self._waiting_deadlines += 1

    def _cover_decode(self, block: int) -> None:
        """Page-budget guard before a decode block: every surviving slot
        must own pages for its next `block` writes (a slot that hits
        EOS/budget mid-block keeps stepping — overshoot beyond coverage
        lands in the trash page and is discarded host-side, but REAL
        tokens' writes must be owned). Oldest streams are covered first;
        reclaim preempts youngest-first, so under exhaustion the pool
        degrades to fewer, older streams instead of corrupting any."""
        active = [r for r in self._slot_req if r is not None]
        active.sort(key=lambda r: r.admit_time)
        covered: set[int] = set()
        for req in active:
            if req.slot is None:
                continue  # preempted by an earlier slot's reclaim
            slot = req.slot
            covered.add(slot)
            if self._quant and req.params.kv_exact:
                continue  # exact streams write sidecar lanes, not pages
            target = min(int(self._pos[slot]) + block, self.config.max_len)
            ok = self.pool.ensure(slot, target)
            while not ok:
                if not self._reclaim_one(protect=covered):
                    break
                ok = self.pool.ensure(slot, target)
            if not ok:
                # nothing reclaimable left: this stream yields too
                self._preempt(req)
                covered.discard(slot)

    def _admit(self, req: Request) -> bool:
        """Prefill `req` into a free lane; True if it finished already.

        With the prefix cache on: reuse the longest cached page-aligned
        prompt prefix — the lane pool SPLICES it into the lane
        (copy-on-acquire, one fused device program), the paged pool
        APPENDS the cached physical page ids to the slot's page table
        (refcount bump, zero device copies) — prefill only the uncovered
        suffix from position `matched`, then hand the prompt's
        page-aligned prefix back to the tree (snapshot copy vs page-id
        reference, same split).

        A request with tokens already emitted is a PREEMPTED one being
        resumed (paged pool only): the prefill recomputes KV for prompt
        + emitted-so-far (minus the newest token, whose KV is written
        when it is fed back), the program's sampled token is discarded
        (the stream already holds it), and decode continues where it
        stopped — token streams are unchanged because cached KV depends
        only on the token ids, and seeded sampling chains fold only
        (seed, sample index).
        """
        slot = self.pool.acquire()
        assert slot is not None, "scheduler admitted beyond free slots"
        tr = self.trace
        now = smetrics.now()
        resumed = bool(req.tokens)
        req.state = ACTIVE
        req.slot = slot
        req.admit_time = now
        # registered BEFORE any device dispatch: if a program call below
        # raises, the fault boundary's rebuild scans _slot_req to
        # requeue in-flight work — a mid-admission request must not slip
        # through the scan and get lost (the bail paths clear it)
        self._slot_req[slot] = req

        if resumed:
            seq = np.concatenate(
                [req.prompt, np.asarray(req.tokens[:-1], np.int32)]
            )
        else:
            seq = req.prompt
        length = int(seq.size)
        matched = 0
        # kv_exact streams bypass the (quantized) prefix cache entirely:
        # a spliced int8 prefix would break their byte-exactness, and
        # their sidecar lanes own no pages/segments the tree could share
        exact = self._quant and req.params.kv_exact
        use_pc = self.prefix_cache is not None and not exact
        if use_pc and length > 1:
            match = self.prefix_cache.match(seq[: length - 1])
            matched = match.length
            if matched:
                # fault-plane site: the prefix-cache reuse path (splice
                # program / zero-copy page append)
                self._poke_site("prefix_splice")
                # pin across the reuse. In today's single-threaded engine
                # nothing can evict between match and splice (eviction only
                # runs inside insert, below) — the pin is the invariant a
                # future async/threaded admission path must keep, kept live
                # here so the refcount machinery stays exercised.
                self.prefix_cache.pin(match)
                if self._paged:
                    # zero-copy hit: the matched nodes' PHYSICAL page ids
                    # go straight into the slot's page table (host-side
                    # incref) — no device program is dispatched at all,
                    # which the compile registry can prove (no
                    # splice_program entry ever appears)
                    for node in match.nodes:
                        self.pool.append_shared(slot, node.pages)
                    self.prefix_cache.unpin(match)
                    if tr is not None:
                        tr.instant(
                            "share", "prefix", f"slot{slot}", req=req.id,
                            matched=matched,
                            pages=matched // self.prefix_cache.page,
                        )
                else:
                    t_sp = smetrics.now() if tr is not None else 0.0
                    offset = 0
                    for node in match.nodes:
                        self.pool.splice_prefix(slot, node.segment, offset)
                        offset += node.length
                    self.prefix_cache.unpin(match)
                    if tr is not None:
                        # fence: the splice programs run async; without
                        # the wait the span would record dispatch, not
                        # the copy
                        jax.block_until_ready(self.pool.caches)
                        t_sp1 = smetrics.now()
                        self._dev_s += t_sp1 - t_sp
                        tr.complete("splice", "prefix", f"slot{slot}",
                                    ts=t_sp, dur=t_sp1 - t_sp, req=req.id,
                                    matched=matched,
                                    pages=matched // self.prefix_cache.page)

        suffix = length - matched
        padded = self._bucketed(suffix, start=matched)
        if (self._paged and not exact
                and not self._ensure_pages(slot, matched + padded)):
            # pathological: even after shedding the whole tree and every
            # other stream the pool cannot cover this prefill. Hand the
            # pages and slot back and retry next iteration.
            self._slot_req[slot] = None
            self.pool.release(slot)
            req.slot = None
            self.scheduler.requeue_front(req)
            if req.deadline is not None:
                self._waiting_deadlines += 1
            return False
        eidx = 0
        if exact:
            if not self._exact_free:
                # the admission gate's estimate went stale (several exact
                # picks in one iteration): requeue and retry when a
                # sidecar lane frees — the paged bail path's discipline
                self._slot_req[slot] = None
                self.pool.release(slot)
                req.slot = None
                self.scheduler.requeue_front(req)
                if req.deadline is not None:
                    self._waiting_deadlines += 1
                return False
            eidx = self._exact_free.pop()
            self._eidx[slot] = eidx
        # admission metrics AFTER the bail points above: a requeued-and-
        # retried admission must not add a second queue-wait sample or
        # count its prefix lookup twice
        if not resumed:
            self.metrics.record_admit(req, now)
        if use_pc and length > 1:
            self.metrics.record_prefix_lookup(matched)
        chunk = self.config.prefill_chunk
        if chunk is None and padded > 4096:
            chunk = 2048  # same auto-chunk threshold as infer.decode.generate
        if chunk is not None and chunk >= padded:
            chunk = None
        prompt_padded = np.zeros(padded, np.int32)
        prompt_padded[:suffix] = seq[matched:]
        samp_row, top_k, seed = encode_params(req.params)
        need_lp = int(req.params.logprobs)
        self._samp_f[:, slot] = samp_row
        self._top_k[slot] = top_k
        self._seed[slot] = seed
        self._need_lp[slot] = need_lp
        head = np.asarray(
            [slot, suffix, self._rng_step, top_k, seed, need_lp], np.int32
        )
        # grammar allow-list for the FIRST sampled token (resumed
        # requests discard that sample, but the mask must still be
        # well-formed); free/unconstrained lanes rest at -1
        self._allow[slot] = (self._grammar_allow(req)
                             if req.grammar is not None else -1)
        # fault-plane site: the prefill dispatch (stall/synthetic-error
        # effects apply here; a nan/inf spec poisons THIS prefill's
        # sampled-token logits through the ctl code below)
        pf_fault = self._poke_site("prefill")
        # the paged program reads the slot's page-table row off the SAME
        # packed int transfer as the allow-list (logical->physical
        # translation with zero extra host->device traffic); the
        # fault-plane poison code is ALWAYS the last element, the
        # exact-lane index (quant pools) second-to-last
        ctl = np.concatenate(
            [head, self._allow[slot]]
            + ([self.pool.table[slot]] if self._paged else [])
            + ([np.asarray([eidx], np.int32)] if self._quant else [])
            + [np.asarray([pf_fault], np.int32)]
        )
        self._rng_step += 1
        t_pf = smetrics.now() if tr is not None else 0.0
        if self._spec == "mtp":
            # admission doubles as the MTP bootstrap: the head cache is
            # prefilled alongside the main lane and the first round's
            # drafts come back with the first token (matched is always 0
            # — the MTP engine excludes the prefix cache)
            pf_args = (
                self.model, padded, chunk, self.config.sample_cap,
                self._spec_k, self.variables, self.pool.caches,
                self._mtp_pool, jnp.asarray(prompt_padded),
                jnp.asarray(ctl), jnp.asarray(samp_row, np.float32),
                self._rng,
            )
            with self._scope("serve/prefill"):
                if self.registry is not None:
                    (pool_tree, self._mtp_pool, first, logprob, drafts,
                     ok) = self.registry.call(
                        "mtp_prefill_program", (padded, chunk),
                        _mtp_prefill_program, pf_args,
                        static_argnums=(0, 1, 2, 3, 4),
                    )
                else:
                    (pool_tree, self._mtp_pool, first, logprob, drafts,
                     ok) = _mtp_prefill_program(*pf_args)
            self.pool.caches = pool_tree
            self._next_drafts[slot] = np.asarray(drafts)
        else:
            prog = (_paged_prefill_program if self._paged
                    else _prefill_program)
            pool_tree = self.pool.phys if self._paged else self.pool.caches
            pf_args = (
                self.model, padded, chunk, matched, self.config.sample_cap,
                self.variables, pool_tree, jnp.asarray(prompt_padded),
                jnp.asarray(ctl), jnp.asarray(samp_row, np.float32),
                self._rng,
            )
            with self._scope("serve/prefill"):
                if self.registry is not None:
                    # signature = the static shape triple; everything else
                    # (params, caches, control arrays) is fixed per engine
                    pool_tree, first, logprob, ok = self.registry.call(
                        "prefill_program", (padded, chunk, matched),
                        prog, pf_args, static_argnums=(0, 1, 2, 3, 4),
                    )
                else:
                    pool_tree, first, logprob, ok = prog(*pf_args)
            if self._paged:
                self.pool.phys = pool_tree
            else:
                self.pool.caches = pool_tree
        first = int(first)  # blocks on the program — t_pf1 is device-true
        if tr is not None:
            t_pf1 = smetrics.now()
            self._dev_s += t_pf1 - t_pf
            tr.complete("prefill_program", "engine", f"slot{slot}", ts=t_pf,
                        dur=t_pf1 - t_pf, req=req.id, padded=padded,
                        suffix=suffix, chunk=chunk or 0)
        if not bool(np.asarray(ok)):
            # poisoned prefill: quarantine BEFORE the prefix-cache
            # insert below — a non-finite lane must never be snapshotted
            # or page-shared into the radix tree
            self._quarantine(req, smetrics.now())
            return True
        if use_pc:
            # hand the prefilled span to the tree while [0, length) is
            # pristine (an active lane's decode writes land at positions
            # >= length, and dummy writes only hit FREED lanes' slot 0 /
            # the trash page)
            page = self.prefix_cache.page
            aligned = (length - 1) // page * page
            # aligned == matched on a full hit: nothing new to cache, and
            # insert's internal re-match would re-walk the whole prefix on
            # the dispatch-bound host hot path for nothing
            if aligned > matched:
                if self._paged:
                    # reference, not copy: the tree increfs the slot's own
                    # fully-filled pages (only a trailing PARTIAL page
                    # would need a snapshot, and insert never takes one —
                    # aligned is a page multiple)
                    self.prefix_cache.insert(
                        seq[:aligned],
                        lambda off, n: self.pool.share_range(slot, off, n),
                    )
                else:
                    self.prefix_cache.insert(
                        seq[:aligned],
                        lambda off, n: self.pool.extract_prefix(slot, off, n),
                    )
            self.metrics.record_prefix_state(
                self.prefix_cache.bytes_held, self.prefix_cache.evictions
            )
        if self.ledger is not None:
            # live bytes only grow at admission (prefix snapshots) and
            # program temp only at new compiles (just above) — one
            # projected-peak check per admitted request, never per token
            self.ledger.check()
        now = smetrics.now()
        if resumed:
            # recompute complete: the sampled token is discarded (the
            # stream already holds every emitted id) and decode resumes
            # at the preempted position
            self.metrics.record_recompute_tokens(suffix)
            self._last_emit[slot] = now
            self.pool.positions[slot] = length
            self._toks[slot] = req.tokens[-1]
            self._pos[slot] = length
            # _slot_req[slot] was registered before the dispatch (the
            # fault boundary's rebuild scans it) — nothing to set here
            if tr is not None:
                tr.instant("resume", "request", f"slot{slot}", req=req.id,
                           ts=now, recomputed=suffix,
                           tokens=len(req.tokens))
            return False
        req.first_token_time = now
        req.tokens.append(first)
        if req.grammar is not None:
            req.grammar.advance(first)
        if req.params.logprobs:
            req.logprobs.append(float(logprob))
        # the first token is a one-token commit at the admission
        # boundary (decode blocks commit the rest block-by-block)
        self._journal_commit(req, (first,))
        self.metrics.record_first_token(req, now, prefilled=suffix)
        if tr is not None:
            # lifecycle spans stamped from the request's OWN timestamps:
            # queue + prefill partition TTFT exactly (submit -> admit ->
            # first token), which is what lets trace-summary's phase sums
            # reproduce the measured latencies instead of approximating
            # them from instrumentation spans
            tr.complete("queue", "request", "queue", ts=req.submit_time,
                        dur=req.admit_time - req.submit_time, req=req.id)
            tr.complete("prefill", "request", f"slot{slot}",
                        ts=req.admit_time, dur=now - req.admit_time,
                        req=req.id, prefilled=suffix, matched=matched)
        self._last_emit[slot] = now
        self.pool.positions[slot] = length
        self._toks[slot] = first
        self._pos[slot] = length
        reason = self._stop_reason(req, first)
        if req.grammar is not None and req.grammar.done:
            reason = "stop"  # complete document beats a length finish
        if reason != "eos" and self._stop_string_at(req, 0) is not None:
            reason = "stop"  # the first token alone completed a match
        if reason is None:
            self._notify(req, 1)
            return False
        self._finish(req, reason, now)
        self._notify(req, 1)
        return True

    def _stop_reason(self, req: Request, tok: int) -> str | None:
        """Why the just-appended token `tok` ends `req`'s stream — "eos",
        "stop" (stop token-id set), "length", or None (keep decoding).
        Token-level checks only; stop STRINGS are matched once per block
        by `_stop_string_at` (a per-token full-stream decode would make
        the dispatch-bound host loop O(n^2) in stream length)."""
        if req.eos_id is not None and tok == req.eos_id:
            return "eos"
        if req.params.stop_token_ids and tok in req.params.stop_token_ids:
            return "stop"
        if req.remaining == 0:
            return "length"
        return None

    def _stop_string_at(self, req: Request, start: int) -> int | None:
        """Earliest token index >= `start` whose appended text completes a
        stop-string match over the decoded stream, or None. ONE full
        decode per block (matches may span block boundaries because the
        whole generated stream is searched); the per-prefix walk to
        locate the completing token runs only on a hit — at most once in
        a request's lifetime, since a hit finishes it.

        Deliberately NOT a bounded tail-window re-decode (the vLLM
        trick): `detokenize` is caller-supplied and need not be
        prefix-stable — merge-y tokenizers can rewrite text at token
        boundaries and tokens may decode to empty strings, so a
        fixed-token window can miss or misplace a cross-boundary match.
        The full re-decode is exact for ANY detokenizer at one O(stream)
        host call per block, bounded by max_len."""
        if not req.params.stop:
            return None
        text = self.detokenize(req.tokens)
        if not any(s in text for s in req.params.stop):
            return None
        for k in range(start, len(req.tokens)):
            prefix = self.detokenize(req.tokens[: k + 1])
            if any(s in prefix for s in req.params.stop):
                return k
        return len(req.tokens) - 1  # decode-boundary quirk: match only
        # materializes with the full stream; attribute it to the last token

    def _spec_gauges(self) -> dict[str, float]:
        """Speculation gauges riding every metrics snapshot (registered
        iff `speculative` — the present-iff-enabled key-surface contract
        of the paged/observatory gauges)."""
        m = self.metrics
        rate = (m.spec_accepted / m.spec_proposed) if m.spec_proposed else 0.0
        per_step = (m.spec_tokens / m.spec_steps) if m.spec_steps else 0.0
        return {
            "serve/spec_acceptance_rate": rate,
            "serve/spec_tokens_per_step": per_step,
            "serve/spec_drafts_rejected": float(
                m.spec_proposed - m.spec_accepted
            ),
        }

    def _spec_block(self, probe: bool = False) -> list[Request]:
        """One speculative decode step: `spec_rounds` draft-verify rounds
        in ONE program call, committing a variable number of tokens per
        slot. The host walk mirrors `_decode_block`'s exactly — per
        committed token: append, grammar advance, logprobs, stop checks —
        so every lifecycle behavior (EOS/budget/stop-string/cancel/
        timeout, overshoot discard) is identical; only the token source
        changed. Grammar-constrained slots keep ONE token per step (their
        allow-mask is stale after the first draw): they ride the same
        program draft-free and the host takes round 0's first commit.

        `probe` runs the controller's short measurement block (a couple
        of rounds) instead of the full one — cheap acceptance evidence
        after a hold, so adversarial traffic pays a fraction of a block,
        not a full chunked block, per probe."""
        cfg = self.config
        k = self._spec_k
        rounds = min(2, self._spec_rounds) if probe else self._spec_rounds
        mtp = self._spec == "mtp"
        if self._paged:
            # cover the worst-case committed window (every round sweeps);
            # reclaim preempts youngest-first under pressure as usual
            self._cover_decode(min(rounds * (k + 1), cfg.max_len))
            if self.pool.n_active == 0:
                return []
        # fault-plane site: the speculative block IS the decode dispatch
        self._poke_site("decode")
        acap = cfg.sample_cap
        if mtp:
            rows = 10 + acap + k + 1
        else:
            rows = (11 + acap + cfg.max_len
                    + (self.pool.pages_per_lane if self._paged else 0)
                    + (1 if self._quant else 0) + 1)
        state = np.zeros((rows, cfg.n_slots), np.int32)
        state[0] = self._toks
        state[1] = self._pos
        state[3] = -1
        for slot, r in enumerate(self._slot_req):
            if r is None:
                continue
            state[2, slot] = 1
            if r.eos_id is not None:
                state[3, slot] = r.eos_id
            state[7, slot] = len(r.tokens)
            if r.grammar is not None:
                # constrained slots never draft (spec gate stays 0) and
                # refresh their allow row exactly like the plain block
                self._allow[slot] = self._grammar_allow(r)
            else:
                state[9 + acap, slot] = 1
            if not mtp:
                # the slot's token history — the n-gram drafter's corpus
                # — rides the packed transfer, one column per slot
                seq = np.concatenate(
                    [r.prompt, np.asarray(r.tokens, np.int32)]
                )
                m = min(int(seq.size), cfg.max_len)
                state[10 + acap:10 + acap + m, slot] = seq[:m]
                state[10 + acap + cfg.max_len, slot] = m
        state[4] = self._rng_step
        state[5] = self._top_k
        state[6] = self._seed
        state[8] = self._need_lp
        state[9:9 + acap] = self._allow.T
        if mtp:
            state[10 + acap:10 + acap + k] = self._next_drafts.T
        elif self._paged:
            base = 11 + acap + cfg.max_len
            state[base:base + self.pool.pages_per_lane] = self.pool.table.T
        if self._quant:
            state[-2] = self._eidx
        # fault-plane poison row, always last; one-shot per dispatch
        state[-1] = self._fault_row
        self._fault_row[:] = 0
        self._rng_step += 1
        tr = self.trace
        t_dec = smetrics.now() if tr is not None else 0.0
        if mtp:
            prog = _mtp_spec_decode_program
            args = (self.model, k, rounds, acap, cfg.max_len,
                    self.variables, self.pool.caches, self._mtp_pool,
                    jnp.asarray(state), jnp.asarray(self._samp_f),
                    self._rng)
            statics = (0, 1, 2, 3, 4)
        else:
            prog = (_paged_spec_decode_program if self._paged
                    else _spec_decode_program)
            args = (self.model, k, rounds, acap, cfg.max_len,
                    cfg.spec_ngram, self.variables,
                    self.pool.phys if self._paged else self.pool.caches,
                    jnp.asarray(state), jnp.asarray(self._samp_f),
                    self._rng)
            statics = (0, 1, 2, 3, 4, 5)
        with self._scope("serve/spec_block"):
            if self.registry is not None:
                # one speculative decode shape per engine, exactly like
                # decode_block — a second signature IS the anomaly
                res = self.registry.call(
                    "spec_block", (rounds, k), prog, args,
                    static_argnums=statics,
                )
            else:
                res = prog(*args)
        if mtp:
            self.pool.caches, self._mtp_pool, outs, nxt = res
            # np.array, not asarray: the device view is read-only and
            # the next admission writes its bootstrap drafts in place
            self._next_drafts = np.array(nxt)
        elif self._paged:
            self.pool.phys, outs = res
        else:
            self.pool.caches, outs = res
        out, commits, proposed, lps, finite = outs
        # fault-plane site: post-block output fetch / paged scatter
        self._poke_site("scatter")
        t_dev = 0.0
        if tr is not None:
            jax.block_until_ready(out)
            t_dev = smetrics.now()
            self._dev_s += t_dev - t_dec
        out = np.asarray(out)          # (rounds, S, k+1)
        commits = np.asarray(commits)  # (rounds, S)
        proposed = np.asarray(proposed)
        lps = np.asarray(lps)
        finite = np.asarray(finite)    # (S,) — the per-slot guard
        now = smetrics.now()
        finished: list[Request] = []
        tot_prop = tot_acc = tot_rounds = 0
        delivered = 0
        max_appended = 0
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            if tr is not None:
                tr.complete("spec_block", "engine", f"slot{slot}",
                            ts=t_dec, dur=t_dev - t_dec, req=req.id,
                            rounds=rounds, k=k)
            if not finite[slot]:
                finished.append(self._quarantine(req, now))
                continue
            if req.cancelled:
                self._finish(req, "cancelled", now)
                finished.append(req)
                self._notify(req, 0)
                continue
            if req.deadline is not None and now >= req.deadline:
                self._finish(req, "timeout", now)
                finished.append(req)
                self._notify(req, 0)
                continue
            appended = 0
            reason = None
            base = len(req.tokens)
            grammar1 = req.grammar is not None
            for r in range(rounds):
                n = int(commits[r, slot])
                if not grammar1:
                    tot_prop += int(proposed[r, slot])
                    tot_acc += max(n - 1, 0)
                    tot_rounds += 1
                    # request-scoped acceptance fact (debug timeline):
                    # engine-wide rates hide a single adversarial stream
                    req.spec_proposed += int(proposed[r, slot])
                    req.spec_accepted += max(n - 1, 0)
                # a grammar slot accepts only round 0's first commit —
                # later rounds drew through a stale mask (overshoot,
                # discarded exactly like the plain block's tail)
                take = n if not grammar1 else (1 if r == 0 else 0)
                for j in range(take):
                    t = int(out[r, slot, j])
                    req.tokens.append(t)
                    if grammar1:
                        req.grammar.advance(t)
                    if req.params.logprobs:
                        req.logprobs.append(float(lps[r, slot, j]))
                    appended += 1
                    reason = self._stop_reason(req, t)
                    if grammar1 and req.grammar.done:
                        reason = "stop"
                    if reason is not None:
                        break
                if reason is not None:
                    break
            kk = self._stop_string_at(req, base)
            if kk is not None:
                last = len(req.tokens) - 1
                if reason is None or kk < last or reason == "length":
                    del req.tokens[kk + 1:]
                    if req.params.logprobs:
                        del req.logprobs[kk + 1:]
                    appended -= last - kk
                    reason = "stop"
            # one commit per request per speculative block (same
            # boundary as the plain block's — the drafts' variable
            # commit counts are invisible to the journal)
            self._journal_commit(req, req.tokens[base:])
            self.metrics.record_tokens(
                req, appended, now - self._last_emit[slot], now
            )
            self._last_emit[slot] = now
            self.pool.positions[slot] += appended
            delivered += appended
            max_appended = max(max_appended, appended)
            if reason is not None:
                self._finish(req, reason, now)
                finished.append(req)
            else:
                # an unfinished slot kept every commit, so the host
                # mirrors track the device carry exactly (the device's
                # internal position is rebuilt from these next call)
                self._toks[slot] = req.tokens[-1]
                self._pos[slot] += appended
            self._notify(req, appended)
        self.metrics.record_spec_step(tot_prop, tot_acc, delivered)
        if tot_rounds:
            self._spec_ctl.observe(tot_acc, tot_rounds)
        self._tick_weight = max(1.0, max_appended / cfg.decode_block)
        return finished

    def _decode_block(self) -> list[Request]:
        if self._spec is not None:
            decision = self._spec_ctl.decide()
            if decision != "off":
                return self._spec_block(probe=decision == "probe")
        cfg = self.config
        block = cfg.decode_block
        if self._paged:
            self._cover_decode(block)
            if self.pool.n_active == 0:
                return []  # exhaustion preempted every stream this block
        # fault-plane site: the decode-block dispatch (stall/synthetic
        # errors apply here; nan/inf pokes write the per-slot fault row
        # packed into THIS call's control transfer)
        self._poke_site("decode")
        acap = cfg.sample_cap
        rows = (9 + acap + (self.pool.pages_per_lane if self._paged else 0)
                + (1 if self._quant else 0) + 1)
        state = np.zeros((rows, cfg.n_slots), np.int32)
        state[0] = self._toks
        state[1] = self._pos
        state[3] = -1
        for slot, r in enumerate(self._slot_req):
            if r is not None:
                state[2, slot] = 1
                if r.eos_id is not None:
                    state[3, slot] = r.eos_id
                # sample index of this block's first draw: the request
                # has emitted len(tokens) so far (index 0 was prefill's)
                state[7, slot] = len(r.tokens)
                if r.grammar is not None:
                    # the stepper advanced with last block's accepted
                    # token: refresh this slot's allow-list (only the
                    # FIRST draw of the block is accepted — see below)
                    self._allow[slot] = self._grammar_allow(r)
        state[4] = self._rng_step
        state[5] = self._top_k
        state[6] = self._seed
        state[8] = self._need_lp
        state[9:9 + acap] = self._allow.T
        if self._paged:
            # the page tables ride the SAME packed transfer: still two
            # host->device control arrays per decode call
            state[9 + acap:9 + acap + self.pool.pages_per_lane] = \
                self.pool.table.T
        if self._quant:
            # exact-lane indices ride second-to-last (0 = quantized/trash)
            state[-2] = self._eidx
        # the fault-plane poison row is ALWAYS the last row (all-zero =
        # bitwise no-op in the program); one-shot per dispatch
        state[-1] = self._fault_row
        self._fault_row[:] = 0
        self._rng_step += 1
        tr = self.trace
        t_dec = smetrics.now() if tr is not None else 0.0
        prog = _paged_decode_program if self._paged else _decode_program
        dec_args = (
            self.model, block, self.config.sample_cap, self.variables,
            self.pool.phys if self._paged else self.pool.caches,
            jnp.asarray(state), jnp.asarray(self._samp_f), self._rng,
        )
        with self._scope("serve/decode_block"):
            if self.registry is not None:
                # one decode shape per engine — a second signature here
                # IS the anomaly the registry exists to catch. Named
                # after the trace span ("decode_block") so the offline
                # roofline join in summarize_trace matches.
                pool_tree, (out, lps, finite) = self.registry.call(
                    "decode_block", (block,), prog, dec_args,
                    static_argnums=(0, 1, 2),
                )
            else:
                pool_tree, (out, lps, finite) = prog(*dec_args)
        if self._paged:
            self.pool.phys = pool_tree
        else:
            self.pool.caches = pool_tree
        # fault-plane site: the post-block output fetch / paged scatter
        # boundary (where async XLA runtime errors actually surface)
        self._poke_site("scatter")
        t_dev = 0.0
        if tr is not None:
            # fence so the span is device wall time, not dispatch time;
            # the np.asarray below would block anyway, so the fence costs
            # nothing extra — it just moves the wait to a measured point
            jax.block_until_ready(out)
            t_dev = smetrics.now()
            self._dev_s += t_dev - t_dec
        out = np.asarray(out)  # (block, n_slots); overshoot truncated below
        lps = np.asarray(lps)
        finite = np.asarray(finite)  # (n_slots,) — the per-slot guard
        now = smetrics.now()
        finished: list[Request] = []
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            if tr is not None:
                # one fused program advances every lane together: each
                # active slot's block span shares the program's wall time
                tr.complete("decode_block", "engine", f"slot{slot}",
                            ts=t_dec, dur=t_dev - t_dec, req=req.id,
                            block=block)
            if not finite[slot]:
                # the guard pinned a NaN/Inf forward to this slot: its
                # block output is garbage — contain it; every other
                # slot's walk below proceeds untouched
                finished.append(self._quarantine(req, now))
                continue
            if req.cancelled:
                # lifecycle kill at the block boundary: this block's
                # output is discarded, the lane frees for the next pick
                self._finish(req, "cancelled", now)
                finished.append(req)
                self._notify(req, 0)
                continue
            if req.deadline is not None and now >= req.deadline:
                self._finish(req, "timeout", now)
                finished.append(req)
                self._notify(req, 0)
                continue
            appended = 0
            reason = None
            base = len(req.tokens)
            # a grammar-constrained slot accepts only the block's FIRST
            # draw: the allow-mask rode this call's control transfer and
            # is stale after one advance — the tail is discarded exactly
            # like post-EOS overshoot (stale writes in the slot's own
            # lane are overwritten before they are ever attended)
            span = 1 if req.grammar is not None else block
            for t, lp in zip(out[:span, slot], lps[:span, slot]):
                req.tokens.append(int(t))
                if req.grammar is not None:
                    req.grammar.advance(int(t))
                if req.params.logprobs:
                    req.logprobs.append(float(lp))
                appended += 1
                reason = self._stop_reason(req, int(t))
                if req.grammar is not None and req.grammar.done:
                    reason = "stop"  # complete document ends the stream
                if reason is not None:
                    break  # the tail of the block is discarded overshoot
            k = self._stop_string_at(req, base)
            if k is not None:
                # a stop string completed at token k; it wins over a
                # token-level reason that fired LATER in the block (and
                # over "length" at the same token — the old per-token
                # check order), truncating the overshoot
                last = len(req.tokens) - 1
                if reason is None or k < last or reason == "length":
                    del req.tokens[k + 1:]
                    if req.params.logprobs:
                        del req.logprobs[k + 1:]
                    appended -= last - k
                    reason = "stop"
            # ONE commit record per request per block, riding the same
            # host-mirror drain that appended the tokens (the journal's
            # granularity is the engine's — never per token)
            self._journal_commit(req, req.tokens[base:])
            self.metrics.record_tokens(
                req, appended, now - self._last_emit[slot], now
            )
            self._last_emit[slot] = now
            self.pool.positions[slot] += appended
            if reason is not None:
                self._finish(req, reason, now)
                finished.append(req)
            elif req.grammar is not None:
                # the mirror advances by the ONE accepted token; the
                # device's remaining writes land beyond the mirror
                # position and are overwritten by the next block
                self._toks[slot] = out[0, slot]
                self._pos[slot] += 1
            else:
                # mirror the device carry: the slot ran the full block
                self._toks[slot] = out[-1, slot]
                self._pos[slot] += block
            self._notify(req, appended)
        return finished

    def _finish(self, req: Request, reason: str, now: float) -> None:
        req.state = FINISHED
        req.finish_reason = reason
        req.finish_time = now
        if req.first_token_time is None:
            # finished before its first token ever landed (a quarantined
            # or force-drained mid-admission request): close the
            # lifecycle with a zero-width prefill phase so the traced
            # three-span partition below never subtracts None
            req.first_token_time = now
            if self.trace is not None:
                self.trace.complete("queue", "request", "queue",
                                    ts=req.submit_time,
                                    dur=(req.admit_time or now)
                                    - req.submit_time, req=req.id)
                self.trace.complete("prefill", "request",
                                    f"slot{req.slot}",
                                    ts=req.admit_time or now,
                                    dur=now - (req.admit_time or now),
                                    req=req.id)
        if self._paged and req.slot is not None:
            # page-usage fact for the request's debug timeline, stamped
            # before release frees the table (streams only grow, so the
            # finish-boundary count IS the peak)
            req.pages_held = max(req.pages_held,
                                 int(self.pool.n_alloc[req.slot]))
        self._journal_finish(req)
        self.metrics.record_finish(req, now)
        if self._slo is not None:
            req.slo_result = self._slo.observe(req, now)
        if self.trace is not None:
            # lifecycle decode phase: first token -> finish (0 for
            # prefill-only finishes) — with queue + prefill above, the
            # three spans partition finish_time - submit_time exactly
            self.trace.complete(
                "decode", "request", f"slot{req.slot}",
                ts=req.first_token_time, dur=now - req.first_token_time,
                req=req.id, tokens=len(req.tokens),
            )
            self.trace.instant("finish", "request", f"slot{req.slot}",
                               req=req.id, ts=now, reason=reason)
            if self._mon is not None:
                self._mon.observe_finish(reason)
        slot = req.slot
        self._slot_req[slot] = None
        # park the idle lane at position 0 with greedy sampling rows: the
        # masked dummy writes land in slot 0 (overwritten by the next
        # prefill), and an all-greedy, unconstrained resting state keeps
        # idle batches on fused_sample's sort-free fast path
        self._toks[slot] = 0
        self._pos[slot] = 0
        self._samp_f[:, slot] = GREEDY_ROW
        self._allow[slot] = -1
        self._top_k[slot] = 0
        self._seed[slot] = -1
        self._need_lp[slot] = 0
        if self._eidx[slot]:
            # hand the exact sidecar lane back (stale data contract as
            # the pools': the next exact prefill overwrites before read)
            self._exact_free.append(int(self._eidx[slot]))
            self._eidx[slot] = 0
        self.pool.release(slot)

    def _finish_unadmitted(self, req: Request, reason: str,
                           now: float) -> None:
        """Finish a request cancelled or timed out while in the waiting
        queue — either never admitted, or a PREEMPTED stream waiting to
        resume (paged pool; it already has tokens and stamped queue +
        prefill spans at its original admission)."""
        req.state = FINISHED
        req.finish_reason = reason
        req.finish_time = now
        self._journal_finish(req)
        self.metrics.record_finish(req, now)
        if self._slo is not None:
            req.slo_result = self._slo.observe(req, now)
        if self.trace is not None:
            if req.first_token_time is None:
                # its whole life was queue time; no prefill/decode phases
                self.trace.complete("queue", "request", "queue",
                                    ts=req.submit_time,
                                    dur=now - req.submit_time, req=req.id)
            else:
                # preempted mid-stream: queue/prefill spans exist from
                # the original admission — close the lifecycle with the
                # decode phase (first token -> finish) instead of a
                # second full-life queue span, keeping the three-phase
                # partition of finish - submit intact
                self.trace.complete(
                    "decode", "request", "queue",
                    ts=req.first_token_time,
                    dur=now - req.first_token_time,
                    req=req.id, tokens=len(req.tokens),
                )
            self.trace.instant("finish", "request", "queue", req=req.id,
                               ts=now, reason=reason)
            if self._mon is not None:
                self._mon.observe_finish(reason)
        self._notify(req, 0)
