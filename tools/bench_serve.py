"""Serving-throughput benchmark -> BENCH_serve.json.

Thin wrapper over `python -m solvingpapers_tpu.cli serve-bench` (one
parser, one call site — the two entry points cannot drift) that defaults
--config to llama3_shakespeare and --out to BENCH_serve.json, keeping the
artifact in the same {metric, value, unit, vs_baseline, detail} shape as
the training scorecard so the serving trajectory stays comparable
across rounds.

Usage: python tools/bench_serve.py [--config llama3_shakespeare]
       [--requests 32] [--slots 8] [--out BENCH_serve.json]
       (any `cli serve-bench` flag passes through)

BENCH_serve.json is JSON-lines, one entry per workload. The default run
overwrites it with the Poisson entry; re-run with
`--shared-prefix --append` to add the prefix-cache workload entry
(cache-on vs cache-off TTFT over K shared system prompts), with
`--sampling --append` for the per-request-sampling workload (mixed
temperature/top-p/top-k/min-p vs all-greedy on the same trace), and
with `--paged --append` for the paged-KV-pool workload (ABBA-paired
paged vs lane throughput, equal-HBM capacity arm, zero-copy
shared-prefix TTFT), with `--http --append` for the HTTP soak
(the Poisson trace as N concurrent SSE clients through the OpenAI
front door, ABBA-paired against direct engine.submit: req/s,
client-side TTFT/p99 ITL, http_overhead_pct, stream_token_exact), and
with `--speculative --append` for the speculative-decoding workload
(spec-on vs spec-off delivered tokens/sec on a briefly-trained model,
greedy token-exactness, acceptance rate, and the temperature-2.0
zero-acceptance adversarial overhead), and with `--kv-quant int8
--append` for the quantized-KV workload (teacher-forced greedy-token
agreement vs the exact pool on a briefly-trained model, ABBA-paired
like-for-like Poisson overhead, and an equal-HBM capacity arm booking
int8+scale slots at the f32 paged pool's resident byte budget).

and with `--slo --append` for the SLO-observatory workload (per-request
SLO classes — interactive/standard/batch — through an slo_targets
engine, ABBA-paired against the plain engine: slo_overhead_pct,
per-class attainment/burn, and goodput_tokens_per_s, the tokens
delivered inside their latency targets),

and with `--chaos --append` for the fault-tolerance soak (one seeded
fault schedule — NaN/Inf slot poisons, synthetic XlaRuntimeError + OOM,
a step stall, a journal_write io_error — through a fault-free
reference, a ladder-off chaos arm and a degradation-ladder arm:
streams_survived, survivor token-exactness, fault_recovery_s, the
zero-leak drain invariant, goodput ladder-on vs ladder-off, the
degraded-journal path, and the ABBA-paired armed-but-quiet
fault_overhead_pct),

and with `--journal --append` for the durability workload (ABBA-paired
journal-on vs journal-off req/s — journal_overhead_pct, fsync batched
per step — plus a kill-and-recover arm: abandon a journaled engine
mid-decode, replay the journal through a fresh one, and record
recovery_wall_s / recovered_requests / recovered_token_exact with the
zero-leak drain invariant),

and with `--replay --append` for the replay-observatory workload
(journal a seeded greedy+stochastic workload on a briefly-trained
model, replay it through serve/replay.py against the identical config
on BOTH pool layouts — replay_byte_exact, the never-flip gate — and
against an int8-kv candidate — replay_agreement_rate, the graded
teacher-forced score held to the same >= 0.99 band as --kv-quant,
with quant_byte_exact_rate / replay_first_divergence_p50 disclosing
how fast byte exactness decays under the lossy candidate),

and with `--fleet --append` for the fleet-serving workload (ABBA-paired
1-replica FleetRouter vs bare engine req/s — router_overhead_pct, the
pure routing tax — plus a drain-migration arm: a journaled 2-replica
fleet mid-decode drain of r0, peers adopting its live streams through
the recover() path, recording migration_wall_s / migrated_streams /
migrated_token_exact / fleet_token_exact with zero-leak on BOTH
replicas).

Every entry records the `kv_dtype` / `kv_pool_bytes` /
`greedy_agreement_rate` triple (exact pools report their compute dtype
and 1.0) so the trajectory stays comparable across quantized rounds,
plus (schema v2) a provenance stamp — git sha, timestamp, jax/jaxlib,
host device — that `tools/bench_check.py` keys its regression gate on.

Add `--trace` to any workload to run one extra flight-recorded arm: the
entry gains `trace_overhead_pct` (tracing-on vs tracing-off req/s on the
same arrival trace — the tracer's < 2% budget), and `--trace-out` gets
the Chrome trace-event JSON for Perfetto / `cli trace-summary`.
"""

from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main() -> int:
    from solvingpapers_tpu.cli import main as cli_main

    argv = list(sys.argv[1:])
    if not any(a == "--config" or a.startswith("--config=") for a in argv):
        # shared-prefix needs prefill compute to dominate dispatch overhead:
        # gpt_shakespeare's 8-layer / 256-position config shows the cache's
        # effect honestly on CPU; llama3_shakespeare (128 positions) stays
        # the Poisson-throughput default for cross-round comparability
        # --paged shares --shared-prefix's reasoning for its prefix
        # sub-arm: the 256-position config's long stems are the regime
        # where the hit-TTFT claim is measured
        # --speculative trains the model briefly before benching (draft
        # quality is the mechanism) — gpt_tiny fits a few hundred steps
        # in seconds
        def flagged(name):
            # value-taking flags also spell --flag=value
            return any(a == name or a.startswith(name + "=") for a in argv)

        if (flagged("--speculative") or flagged("--kv-quant")
                or "--replay" in argv):
            default = "gpt_tiny_long"
        elif "--shared-prefix" in argv or "--paged" in argv:
            default = "gpt_shakespeare"
        else:
            default = "llama3_shakespeare"
        argv += ["--config", default]
    if not any(a == "--out" or a.startswith("--out=") for a in argv):
        argv += ["--out", "BENCH_serve.json"]
    return cli_main(["serve-bench", *argv])


if __name__ == "__main__":
    sys.exit(main())
