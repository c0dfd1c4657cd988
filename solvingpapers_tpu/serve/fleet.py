"""Fleet serving: N independent `ServeEngine` replicas behind one
router — the "millions of users" layer (ROADMAP item 2) between the
HTTP front door (serve/api.py) and the engines.

PRs 11–14 shipped the three prerequisites without cashing them in:
exactly-mergeable per-replica latency histograms (metrics/hist.py's
merge-of-shards == shard-of-merged contract), a readiness-aware
`/healthz` state machine built for a load balancer, and a write-ahead
journal that makes any stream resumable on any process that can read it
(serve/journal.py + `ServeEngine.recover`). `FleetRouter` composes
them: each replica is a full engine with its own `EngineLoop`, KV pool,
journal file, and metrics — no shared device state, so a replica's
fault blast radius stays its own (the vLLM-style replication shape, as
opposed to DistServe-style role splitting, which this layer does not
attempt).

Routing composes three signals, in order:

    health     a replica that is draining, whose loop thread died, or
               whose fault-plane health says "unhealthy" receives no
               new admissions — the same gate its own /healthz exposes
               to an external balancer, applied internally.
    SLO burn   the request's SLO class avoids replicas whose windowed
               error-budget burn rate for that class exceeds
               `burn_threshold` (serve/slo.py `SloTracker.burn_rate`),
               unless every candidate is burning — interactive traffic
               steers around a replica that is missing its latency
               targets while batch traffic keeps it busy.
    affinity   the replica whose prefix-cache radix tree covers the
               longest page-aligned prompt prefix wins (the host-side
               `PrefixCache.peek` via `ServeEngine._match_len`, taken
               under that replica's step lock — the tree mutates on its
               engine thread). A cache hit is a host-side page-table
               append instead of a device prefill, so affinity is the
               difference between O(prompt) and O(suffix) admission
               cost; least-loaded (free fraction of the scarcest
               resource: pages on a paged pool, slots otherwise, then
               queue room, then replica id) breaks ties and decides
               when no replica covers any prefix.

`submit` walks the ranked candidates: a replica whose waiting queue is
full rejects host-side and the router retries the next candidate
instead of bouncing the client — the fleet-wide fix for single-replica
503s (serve/api.py consults `FleetRouter.capacity_left`, the SUM of
admitting replicas' queue room, before burning a submission).

Observability rides the existing primitives: `prom_sets()` feeds
`PrometheusTextWriter.render_sets` one UNLABELED merged set (fleet
gauges + the exact `LogHistogram` merge of every replica's latency
histograms, taken under each replica's step lock — so
`histogram_quantile` over the merged series equals the quantile over
the union of observations) plus one ``replica="rN"``-labeled set per
replica; `statusz()` is the `/statusz` ``fleet`` section with
per-replica occupancy/health/rung and the routing counters.

The headline capability is journal-backed zero-drop stream migration:
`drain(replica)` generalizes PR 14's crash-restart to a LIVE rolling
upgrade. Under the drained replica's step lock, its journal is synced
and the live entries snapshotted, then every in-flight request is
force-finished host-side with reason ``"migrated"`` (slots, pages and
lanes reclaim through the ordinary finish paths — the drained replica
passes the zero-leak invariant). Each snapshotted entry is adopted by
the best admitting peer (`ServeEngine.adopt`: journaled into the peer,
requeued through the `recover()` preemption-resume path — token-exact
for greedy and seeded plain-decode streams). The SSE side: the front
door closes a ``"migrated"`` stream WITHOUT a terminal chunk, the
client reconnects with its Last-Event-ID cursor, and the cursor
resolves on the peer through the same recovered-set path a crash
restart uses — zero dropped streams, byte-identical transcripts
(pinned in tests/test_fleet.py). ``"migrated"`` is excluded
from SLO accounting on the drained replica (serve/slo.py) — the
adopting replica owns the latency outcome.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from collections import OrderedDict

import numpy as np

from solvingpapers_tpu.metrics.hist import LogHistogram
from solvingpapers_tpu.metrics.trace import (FlightRecorder,
                                             fleet_events_to_chrome)
from solvingpapers_tpu.serve import metrics as smetrics
from solvingpapers_tpu.serve.api import EngineLoop

__all__ = ["FleetRouter", "MigrationReport", "Replica"]


class Replica:
    """One engine + its driver loop under a fleet id ("r0", "r1", ...).

    Thin by design: the engine keeps owning its pool/journal/metrics
    and the loop keeps owning the step thread; the replica adds only
    the fleet-facing facts (id, draining flag, admission gate, the
    locked prefix probe)."""

    def __init__(self, rid: str, engine, loop=None, start: bool = True):
        self.rid = rid
        self.engine = engine
        self.loop = loop if loop is not None else EngineLoop(
            engine, start=start)
        # drain() sets this before touching the journal: the admission
        # gate must close FIRST so no new stream lands between the
        # snapshot and the force-drain (undrain() reopens it)
        self.draining = False

    @property
    def admitting(self) -> bool:
        """May this replica receive NEW admissions? Draining replicas,
        replicas whose loop thread died, and replicas whose fault-plane
        health machine says "unhealthy" are out — the same signals the
        replica's own /healthz would serve an external balancer."""
        return (not self.draining and self.loop.error is None
                and getattr(self.engine, "health", "healthy")
                != "unhealthy")

    def free_fraction(self) -> float:
        """Free fraction of the SCARCEST pool resource — pages on a
        paged pool (slots stop being the binding constraint there),
        slots otherwise. Host-mirror reads, safe without the lock."""
        pool = self.engine.pool
        budget = getattr(pool, "page_budget", 0)
        if budget:
            return pool.pages_free / budget
        return pool.n_free / max(pool.n_slots, 1)

    def probe(self, prompt: np.ndarray) -> int:
        """Cached-prefix match length for `prompt` on THIS replica,
        under its step lock (the radix tree mutates on the engine
        thread; `PrefixCache.peek` is read-only — no LRU touch, so
        routing probes cannot evict what they are looking for)."""
        eng = self.engine
        if getattr(eng, "prefix_cache", None) is None:
            return 0
        return self.loop._locked(lambda: eng._match_len(prompt))


@dataclasses.dataclass
class MigrationReport:
    """What one `FleetRouter.drain` did: which streams moved where.

    `targets` maps each migrated journal id to ``(peer_rid, new_rid)``
    — `new_rid` differs from the original only when the peer's journal
    already had a live entry under that id (the adopt re-key rule).
    `errors` holds ``(rid, reason)`` for entries no peer could adopt
    (they finished "migrated" on the drained replica and their journal
    record is the only trace — honest loss accounting, never silent)."""

    replica: str
    entries: int
    migrated: list
    targets: dict
    errors: list
    wall_s: float


class FleetRouter:
    """N `ServeEngine` replicas behind one submit surface (module
    docstring has the policy). Construct with the engines (each gets a
    `Replica` + started `EngineLoop`; pass ``start=False`` for
    manually-stepped benches/tests) and hand the router to `ApiServer`
    — the front door keeps its single-engine API surface and routes
    through here when a router is present."""

    # bounded like the front door's timelines registry: the owner map
    # only accelerates cancel/resume lookups — an evicted id falls back
    # to scanning the replicas' recovered sets and journals
    owner_cap = 4096

    def __init__(self, engines, *, replica_ids=None,
                 burn_threshold: float = 1.0, start: bool = True,
                 stale_shard_cutoff_s: float = 300.0):
        engines = list(engines)
        if not engines:
            raise ValueError("FleetRouter needs at least one engine")
        paths = [getattr(e.config, "journal_path", None) for e in engines]
        dup = {p for p in paths if p is not None and paths.count(p) > 1}
        if dup:
            raise ValueError(
                f"replicas share a journal file ({sorted(dup)}): each "
                "replica needs its OWN journal — interleaved writers "
                "would corrupt recovery and drain migration"
            )
        ids = (list(replica_ids) if replica_ids is not None
               else [f"r{i}" for i in range(len(engines))])
        if len(ids) != len(engines) or len(set(ids)) != len(ids):
            raise ValueError(
                "replica_ids must be unique, one per engine")
        self.replicas = [Replica(rid, eng, start=start)
                         for rid, eng in zip(ids, engines)]
        self._by_id = {r.rid: r for r in self.replicas}
        # burn rate above which a replica stops receiving traffic of
        # the burning class (1.0 = the error budget is fully consumed
        # over the window); >= everything disables the gate
        self.burn_threshold = burn_threshold
        # a non-admitting replica whose metrics shard has seen no
        # traffic for longer than this is EXCLUDED from the /metrics
        # N-way histogram merge (its numbers describe a rotation it is
        # no longer part of); the labeled per-replica set still serves
        # the shard, tagged with serve/shard_age_s + serve/shard_stale
        self.stale_shard_cutoff_s = stale_shard_cutoff_s
        self._lock = threading.Lock()
        self._owners: OrderedDict[str, Replica] = OrderedDict()
        self.stats = {
            "routed": 0, "affinity_hits": 0, "burn_avoided": 0,
            "rerouted_full": 0, "drains": 0, "migrated_streams": 0,
            "migration_errors": 0,
        }
        # the router's own flight recorder: route-decision spans with
        # per-candidate scores, reroute attempts, drain/migration hops
        # — created iff any replica records (same opt-in as the
        # engines', on the SAME patchable clock, so the stitched fleet
        # export aligns router and replica timelines on one time base)
        self.trace: FlightRecorder | None = None
        traced = [e for e in engines
                  if getattr(e, "trace", None) is not None]
        if traced:
            self.trace = FlightRecorder(
                capacity=getattr(traced[0].config, "trace_capacity",
                                 65536),
                clock=smetrics.now,
            )

    # ------------------------------------------------------------ routing

    def replica(self, rid: str) -> Replica:
        try:
            return self._by_id[rid]
        except KeyError:
            raise KeyError(
                f"unknown replica {rid!r} (have "
                f"{sorted(self._by_id)})") from None

    def _rank(self, prompt: np.ndarray, slo: str | None
              ) -> tuple[list[Replica], list[dict]]:
        """Admitting replicas, best first: health gate -> per-class
        burn gate -> prefix affinity -> least-loaded (free fraction of
        the scarcest resource, then queue room, then replica id).
        Returns ``(ranked, scores)``: one score row per replica (the
        route-decision evidence the router's trace span records) —
        ranked candidates carry the signals the sort used, excluded
        replicas carry the gate that dropped them."""
        excluded: dict[str, str] = {
            r.rid: "not_admitting"
            for r in self.replicas if not r.admitting
        }
        cands = [r for r in self.replicas if r.admitting]
        if cands and slo is not None and len(cands) > 1:
            cool = [
                r for r in cands
                if r.engine._slo is None
                or slo not in r.engine._slo.targets
                or r.engine._slo.burn_rate(slo) <= self.burn_threshold
            ]
            if cool and len(cool) < len(cands):
                with self._lock:
                    self.stats["burn_avoided"] += 1
                for r in cands:
                    if r not in cool:
                        excluded[r.rid] = "burn"
                cands = cool
        matches = {r.rid: r.probe(prompt) for r in cands}
        best = max(matches.values(), default=0)
        if best > 0:
            with self._lock:
                self.stats["affinity_hits"] += 1

        def key(r: Replica):
            # longest cached prefix first; then emptiest, then roomiest
            # queue; replica id last so ranking is deterministic
            return (-matches[r.rid], -r.free_fraction(),
                    -r.engine.scheduler.capacity_left, r.rid)

        ranked = sorted(cands, key=key)
        scores = [
            {"replica": r.rid, "match": matches[r.rid],
             "free": round(r.free_fraction(), 4),
             "queue_room": r.engine.scheduler.capacity_left}
            for r in ranked
        ]
        scores += [{"replica": rid, "excluded": why}
                   for rid, why in sorted(excluded.items())]
        return ranked, scores

    def route(self, prompt, slo: str | None = None) -> Replica | None:
        """The admission replica for `prompt` (None when nothing
        admits); `submit` is the same ranking with full-queue retry."""
        ranked, _ = self._rank(
            np.asarray(prompt, np.int32).reshape(-1), slo)
        return ranked[0] if ranked else None

    def submit(self, prompt, *, max_new_tokens: int = 64, params=None,
               deadline_s=None, grammar=None, stream_cb=None,
               trace_id=None):
        """Route + submit through the chosen replica's loop. Returns
        ``(replica, request)``; ``(None, None)`` when no replica admits
        (the front door 503s with the fleet Retry-After). A replica
        that rejects host-side (queue full, shed, or a health flip that
        raced the ranking) does NOT bounce the client while a peer has
        room: the router retries down the ranked list and only surfaces
        the LAST rejection when every candidate refused — the
        fleet-wide 503 fix. ValueError (a malformed request) propagates
        immediately: it would fail identically everywhere.

        The accepted request carries the routing outcome as plain
        attributes — ``fleet_reroutes`` (how many ranked peers refused
        before this one took it; the ``X-Fleet-Reroutes`` header) and
        ``fleet_route_s`` (ranking + retry wall, the trail's "route"
        phase) — so the front door's request trail works with tracing
        OFF; with the router recorder on, the same decision lands as a
        ``route`` span (per-candidate scores in args) plus one
        ``reroute`` instant per refusing peer."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        slo = getattr(params, "slo", None) if params is not None else None
        t0 = smetrics.now()
        ranked, scores = self._rank(prompt, slo)
        if not ranked:
            return None, None
        last = None
        refusals: list[tuple[float, str]] = []  # (ts, rid) per refusal
        for i, rep in enumerate(ranked):
            try:
                req = rep.loop.submit(
                    prompt, max_new_tokens=max_new_tokens, params=params,
                    deadline_s=deadline_s, grammar=grammar,
                    stream_cb=stream_cb, trace_id=trace_id,
                )
            except RuntimeError:
                # the loop died between the ranking and the submit:
                # treat like any other per-replica refusal
                refusals.append((smetrics.now(), rep.rid))
                continue
            if req.state != "rejected":
                with self._lock:
                    self.stats["routed"] += 1
                    if i:
                        self.stats["rerouted_full"] += 1
                self._remember(req.trace_id, rep)
                dur = max(smetrics.now() - t0, 0.0)
                req.fleet_reroutes = i
                req.fleet_route_s = dur
                if self.trace is not None:
                    for ts, frm in refusals:
                        self.trace.instant(
                            "reroute", "fleet", "router", req=req.id,
                            ts=ts, rid=req.trace_id, rejected_by=frm)
                    self.trace.complete(
                        "route", "fleet", "router", ts=t0, dur=dur,
                        req=req.id, rid=req.trace_id, replica=rep.rid,
                        attempts=i + 1, scores=scores)
                return rep, req
            refusals.append((smetrics.now(), rep.rid))
            last = (rep, req)
        if self.trace is not None:
            self.trace.instant(
                "route_failed", "fleet", "router", ts=smetrics.now(),
                attempts=len(ranked), scores=scores)
        if last is None:
            return None, None
        return last

    def _remember(self, rid, rep: Replica) -> None:
        if rid is None:
            return
        with self._lock:
            self._owners[rid] = rep
            self._owners.move_to_end(rid)
            while len(self._owners) > self.owner_cap:
                self._owners.popitem(last=False)

    def owner(self, rid) -> Replica | None:
        """Which replica currently owns the stream `rid` — the routed
        (or post-migration adopting) replica; falls back to scanning
        the recovered sets when the bounded owner map evicted it."""
        if rid is None:
            return None
        with self._lock:
            rep = self._owners.get(rid)
        if rep is not None:
            return rep
        for r in self.replicas:
            if rid in getattr(r.engine, "_recovered", {}):
                return r
        return None

    def owner_loop(self, req) -> EngineLoop:
        """The loop that owns `req` (for cancel) — replica 0's loop
        when the owner is unknown (cancel on the wrong engine is a
        no-op: `engine.cancel` matches by identity)."""
        rep = self.owner(getattr(req, "trace_id", None))
        return rep.loop if rep is not None else self.replicas[0].loop

    # ------------------------------------------------------- fleet views

    @property
    def capacity_left(self) -> int:
        """Fleet-wide queue room (admitting replicas only) — the front
        door's backpressure probe, replacing the single-replica check
        that would 503 while a peer had room."""
        return sum(r.engine.scheduler.capacity_left
                   for r in self.replicas if r.admitting)

    @property
    def degradation_rung(self) -> int:
        """The fleet's Retry-After input: the LEAST degraded admitting
        replica (traffic routes toward it, so its rung is the honest
        backoff hint); the max over everyone when nothing admits."""
        rungs = [getattr(r.engine, "degradation_rung", 0)
                 for r in self.replicas if r.admitting]
        if rungs:
            return min(rungs)
        return max((getattr(r.engine, "degradation_rung", 0)
                    for r in self.replicas), default=0)

    @property
    def health(self) -> str:
        """/healthz for the fleet: healthy while ANY admitting replica
        is healthy (the router steers around the rest), degraded while
        only degraded replicas admit, unhealthy when nothing admits."""
        states = [r.engine.health for r in self.replicas if r.admitting]
        if any(s == "healthy" for s in states):
            return "healthy"
        if states:
            return "degraded"
        return "unhealthy"

    def prom_sets(self):
        """``[(step, labels, metrics), ...]`` for
        `PrometheusTextWriter.render_sets`: the UNLABELED merged set
        first (fleet gauges + the exact `LogHistogram` merge of every
        replica's latency histograms — `histogram_quantile` over the
        merged series equals the quantile over the union), then one
        ``replica="rN"``-labeled set per replica. Each replica's
        snapshot AND the merge of its live histograms happen under its
        step lock, so a histogram mid-`add` can never tear the merged
        series (the merge itself is also copy-safe — hist.merge_from).

        Staleness: a shard that stopped moving describes a rotation
        the replica is no longer part of — silently merging it skews
        the fleet quantiles toward history. Every labeled set carries
        ``serve/shard_age_s`` (seconds since the shard last recorded)
        and ``serve/shard_stale`` (1 when the replica is NOT admitting
        and its age exceeds `stale_shard_cutoff_s`); stale shards are
        SKIPPED by the histogram merge (tagged, not silently merged —
        the labeled set still serves the frozen numbers) and counted
        in ``fleet/stale_shards``."""
        merged: dict[str, LogHistogram] = {}
        per = []
        max_step = 0
        stale_shards = 0
        for r in self.replicas:
            m = r.engine.metrics
            ref = m._t_last if m._t_last is not None else m._t_first
            age = (max(smetrics.now() - ref, 0.0)
                   if ref is not None else 0.0)
            stale = (not r.admitting
                     and age > self.stale_shard_cutoff_s)
            stale_shards += stale

            def grab(eng=r.engine, stale=stale):
                snap = eng.metrics.prom_snapshot()
                if not stale:
                    for k, v in snap.items():
                        if isinstance(v, LogHistogram):
                            acc = merged.get(k)
                            if acc is None:
                                merged[k] = acc = LogHistogram(
                                    *v.layout[:2],
                                    buckets_per_decade=v.layout[2])
                            acc.merge_from(v)
                return eng._step_idx, snap
            step, snap = r.loop._locked(grab)
            snap["serve/shard_age_s"] = round(age, 3)
            snap["serve/shard_stale"] = float(stale)
            max_step = max(max_step, step)
            per.append((step, {"replica": r.rid}, snap))
        fleet = {
            "fleet/replicas": float(len(self.replicas)),
            "fleet/admitting": float(
                sum(r.admitting for r in self.replicas)),
            "fleet/draining": float(
                sum(r.draining for r in self.replicas)),
            "fleet/capacity_left": float(self.capacity_left),
            "fleet/stale_shards": float(stale_shards),
        }
        with self._lock:
            for k, v in self.stats.items():
                fleet[f"fleet/{k}"] = float(v)
        fleet.update(merged)
        return [(max_step, None, fleet)] + per

    def statusz(self) -> dict:
        """The /statusz ``fleet`` section: per-replica admission facts
        (host-mirror reads — safe from request threads, same contract
        as `ServeEngine.statusz`) plus policy + routing counters."""
        reps = {}
        for r in self.replicas:
            eng = r.engine
            d = {
                "health": getattr(eng, "health", "healthy"),
                "draining": r.draining,
                "admitting": r.admitting,
                "rung": getattr(eng, "degradation_rung", 0),
                "loop_error": (None if r.loop.error is None else
                               f"{type(r.loop.error).__name__}: "
                               f"{r.loop.error}"),
                "step": eng._step_idx,
                "occupancy": round(eng.pool.occupancy, 4),
                "n_free": eng.pool.n_free,
                "queue_depth": len(eng.scheduler),
                "capacity_left": eng.scheduler.capacity_left,
                "recovered_requests": eng._recovered_total,
            }
            if getattr(eng.pool, "page_budget", 0):
                d["pages_free"] = eng.pool.pages_free
            reps[r.rid] = d
        with self._lock:
            routing = dict(self.stats)
        return {
            "replicas": reps,
            "policy": {"burn_threshold": self.burn_threshold},
            "routing": routing,
        }

    def timeseriesz(self) -> dict:
        """The fleet ``/timeseriesz`` body: one rolling-retrospective
        doc per replica that keeps one (`ServeConfig.timeseries`)."""
        out = {}
        for r in self.replicas:
            store = getattr(r.engine, "timeseries", None)
            if store is not None:
                out[r.rid] = store.doc()
        return {"replicas": out}

    # ----------------------------------------------------- stitched export

    def to_chrome_fleet(self) -> dict:
        """ONE Chrome trace for the whole fleet: the router recorder
        plus every replica recorder stitched process-per-replica
        (metrics/trace.fleet_events_to_chrome — all recorders share
        the engine clock, so one t0 aligns the sections; flows follow
        each request across reroutes and migrations via the rid args
        the router spans and engine submit instants carry)."""
        sections = []
        if self.trace is not None:
            sections.append(("router", self.trace.events()))
        for r in self.replicas:
            rec = getattr(r.engine, "trace", None)
            if rec is not None:
                sections.append((r.rid, rec.events()))
        if not sections:
            raise ValueError(
                "no recorders to stitch: run the replicas with "
                "ServeConfig.trace=True")
        return fleet_events_to_chrome(sections)

    def export_chrome_fleet(self, path: str) -> str:
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_fleet(), f)
        return path

    # ------------------------------------------------------------- drain

    def undrain(self, rid: str) -> None:
        """Reopen admissions to a drained replica (rolling upgrade done
        — the process came back; its journal starts empty of live
        entries, everything migrated out)."""
        self.replica(rid).draining = False

    def drain(self, rid: str, *, peer_slo_route: bool = True
              ) -> MigrationReport:
        """Stop admissions to `rid` and migrate every live stream to a
        peer — the journal-backed zero-drop rolling-upgrade drain.

        Protocol (the SSE half lives in serve/api.py):

        1. the replica's admission gate closes (`draining`), so the
           router sends it nothing new while the snapshot runs;
        2. under its step lock, in ONE critical section: the journal is
           synced, its live entries snapshotted (token lists copied —
           the entry objects keep mutating), and every in-flight
           request force-finished host-side with reason ``"migrated"``
           (`ServeEngine.force_drain`: slots/pages/lanes reclaim
           through the ordinary finish paths, so the drained replica
           passes `assert_no_leaks`; the finish lands in its journal).
           The single critical section is load-bearing: a token decoded
           AFTER the snapshot but BEFORE the stop would put the
           client's Last-Event-ID cursor past the peer's committed
           prefix — a 409 instead of a resume;
        3. each snapshotted entry is adopted by the best admitting peer
           (`ServeEngine.adopt` under the peer's lock: journaled into
           the peer, requeued through the recover() preemption-resume
           path — token-exact for greedy and seeded plain-decode
           streams), newest-first so the oldest ends at each peer's
           queue head (FIFO survives the migration). The owner map
           flips so reconnects and cancels follow the stream.

        The front door closes a ``"migrated"`` SSE stream WITHOUT a
        terminal chunk — the client's signal to reconnect with its
        Last-Event-ID cursor, which resolves on the peer through the
        recovered-set path. Entries no peer can adopt are reported in
        `MigrationReport.errors`, never silently dropped. The drained
        replica stays up (draining, zero streams) for its clients to
        finish reading; `undrain` reopens it.

        Raises KeyError for an unknown replica, ValueError when `rid`
        has no journal (migration IS journal replay), RuntimeError when
        no peer admits (the drain would drop streams — refused)."""
        rep = self.replica(rid)
        if rep.engine.journal is None:
            raise ValueError(
                f"drain({rid!r}) migrates via the write-ahead journal; "
                "the replica has no journal_path")
        if not any(r is not rep and r.admitting for r in self.replicas):
            raise RuntimeError(
                f"no admitting peer to drain {rid!r} into — refusing "
                "to drop its live streams")
        t0 = time.monotonic()
        t_d0 = smetrics.now()  # trace time base (patchable in tests)
        rep.draining = True

        def freeze(eng=rep.engine):
            eng.journal.sync()
            entries = [
                dataclasses.replace(e, tokens=list(e.tokens))
                for e in eng.journal.live_entries()
            ]
            eng.force_drain("migrated")
            return entries

        entries = rep.loop._locked(freeze)
        migrated, errors, targets = [], [], {}
        for e in reversed(entries):  # newest-first: see the docstring
            t_m0 = smetrics.now()
            slo = (e.params or {}).get("slo") if peer_slo_route else None
            target = self.route(np.asarray(e.prompt, np.int32), slo=slo)
            if target is None or target is rep:
                errors.append((e.rid, "no admitting peer"))
                continue
            try:
                req = target.loop._locked(
                    lambda eng=target.engine, e=e: eng.adopt(e))
            except ValueError as exc:
                errors.append((e.rid, str(exc)))
                continue
            target.loop._wake.set()
            self._remember(req.trace_id, target)
            targets[e.rid] = (target.rid, req.trace_id)
            migrated.append(req)
            if self.trace is not None:
                # the migration hop: freeze-to-adopt on the router's
                # lane, carrying the rid so the stitched flow follows
                # the stream from the drained replica to its peer
                self.trace.complete(
                    "migrate", "fleet", "router", ts=t_m0,
                    dur=max(smetrics.now() - t_m0, 0.0), req=req.id,
                    rid=req.trace_id, src=rid, dst=target.rid,
                    old_rid=e.rid)
        migrated.reverse()  # report in arrival order
        with self._lock:
            self.stats["drains"] += 1
            self.stats["migrated_streams"] += len(migrated)
            self.stats["migration_errors"] += len(errors)
        if self.trace is not None:
            self.trace.complete(
                "drain", "fleet", "router", ts=t_d0,
                dur=max(smetrics.now() - t_d0, 0.0), replica=rid,
                entries=len(entries), migrated=len(migrated),
                errors=len(errors))
        return MigrationReport(
            replica=rid, entries=len(entries), migrated=migrated,
            targets=targets, errors=errors,
            wall_s=time.monotonic() - t0,
        )

    # ------------------------------------------------------------- close

    def close(self, drain_timeout_s: float = 0.0) -> None:
        """Close every replica (loop then engine), sharing ONE drain
        budget across the fleet — the front door's close() deadline
        semantics, not per-replica multiplication."""
        deadline = time.monotonic() + max(drain_timeout_s, 0.0)
        for r in self.replicas:
            left = max(deadline - time.monotonic(), 0.0)
            r.loop.close(drain_timeout_s=left)
            r.engine.close()
