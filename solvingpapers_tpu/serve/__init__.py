"""Continuous-batching serving: slot/paged KV pools + FIFO scheduler +
mixed prefill/decode engine + radix-tree prefix cache (zero-copy
refcounted page sharing on the paged pool) + per-request sampling
(SamplingParams / fused_sample) + speculative decoding (serve/spec.py:
n-gram/MTP draft-and-verify with lossless rejection sampling) +
grammar-constrained JSON decoding (JsonStepper) + OpenAI-compatible
HTTP front door (ApiServer) + latency metrics + fault tolerance
(serve/faults.py: seeded fault injection, supervised step loop with
per-request blast-radius isolation, SLO-driven degradation ladder) +
durable serving (serve/journal.py: request write-ahead journal,
crash-safe warm restart via ServeEngine.recover, SSE stream
resumption over Last-Event-ID) + fleet serving (serve/fleet.py:
multi-replica FleetRouter with prefix-affinity + SLO-aware routing,
merged fleet metrics, journal-backed zero-drop stream migration via
FleetRouter.drain) + replay observatory (serve/replay.py: journal-
backed shadow-traffic replay against a candidate config, byte-level
stream diffing + teacher-forced agreement scoring, the config-canary
divergence gate)."""

from solvingpapers_tpu.metrics.trace import begin as _begin

_imported = _begin("import:serve")
from solvingpapers_tpu.serve.api import ApiServer, EngineLoop, serve_api
from solvingpapers_tpu.serve.engine import ServeConfig, ServeEngine
from solvingpapers_tpu.serve.fleet import (
    FleetRouter,
    MigrationReport,
    Replica,
)
from solvingpapers_tpu.serve.faults import (
    DegradationLadder,
    FaultPlan,
    FaultSpec,
    InjectedFault,
)
from solvingpapers_tpu.serve.grammar import JsonStepper
from solvingpapers_tpu.serve.journal import (
    Journal,
    JournalEntry,
    JournalError,
    read_entries,
)
from solvingpapers_tpu.serve.kv_pool import (
    KVSlotPool,
    PagedKVPool,
    extract_lane,
    store_lane,
)
from solvingpapers_tpu.serve.metrics import ServeMetrics
from solvingpapers_tpu.serve.prefix_cache import PrefixCache, PrefixMatch
from solvingpapers_tpu.serve.replay import ReplayHarness
from solvingpapers_tpu.serve.sampling import SamplingParams, fused_sample
from solvingpapers_tpu.serve.scheduler import FIFOScheduler, Request
from solvingpapers_tpu.serve.slo import DEFAULT_SLO_TARGETS, SloTracker
from solvingpapers_tpu.serve.spec import SpecController

__all__ = [
    "ApiServer",
    "DegradationLadder",
    "EngineLoop",
    "FaultPlan",
    "FaultSpec",
    "FleetRouter",
    "InjectedFault",
    "MigrationReport",
    "Replica",
    "JsonStepper",
    "Journal",
    "JournalEntry",
    "JournalError",
    "read_entries",
    "ReplayHarness",
    "serve_api",
    "ServeConfig",
    "ServeEngine",
    "KVSlotPool",
    "PagedKVPool",
    "extract_lane",
    "store_lane",
    "ServeMetrics",
    "PrefixCache",
    "PrefixMatch",
    "SamplingParams",
    "fused_sample",
    "FIFOScheduler",
    "Request",
    "DEFAULT_SLO_TARGETS",
    "SloTracker",
    "SpecController",
]

_imported()
