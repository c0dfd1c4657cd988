"""OpenAI-compatible HTTP front door over a live `ServeEngine`.

The engine can batch, page, sample and observe, but it is an in-process
object: this module is the network boundary — the vLLM-shaped serving
surface ROADMAP item 5 calls for. One stdlib `ThreadingHTTPServer` (the
`metrics/http.py` daemon-thread pattern — zero dependencies) exposes:

    POST /v1/completions        OpenAI completions, string or token-id
                                prompts, SSE streaming (`stream: true`)
    POST /v1/chat/completions   chat messages through a minimal template
    GET  /v1/models             the one hosted model
    GET  /healthz /metrics /statusz   the PR-5 inspection surface, on
                                the SAME port family (one listener to
                                probe, scrape and debug)

Concurrency model: the engine stays single-threaded. `EngineLoop` owns
the only thread that calls `engine.step()`, and serializes `submit` /
`cancel` from HTTP handler threads behind one lock (a submit waits at
most one decode block). Token flow back out is lock-free: the engine's
per-request `stream_cb` fires on the engine thread and pushes a COUNT
into the connection's bounded queue; the handler thread wakes, reads
the request's token list (append-only — a count-prefix read is safe
under the GIL), detokenizes the delta and writes the SSE event. A slow
reader fills its queue and events coalesce (counts, not payloads), so
no client can block the engine.

Cancellation is disconnect-driven: the SSE writer maps a broken pipe —
or a half-closed socket, probed between events — to `engine.cancel`,
freeing the slot at the next block boundary; `timeout_s` maps to
`submit(deadline_s=)`.

Stream resumption (serve/journal.py): every SSE chunk carries an
``id: <request id>:<token offset>`` field; a client that lost its
connection POSTs again with ``Last-Event-ID`` set to the last id it
saw, and the server replays the committed tokens past that offset and
re-attaches the connection to the live tail — from the in-process
registry, from the engine's recovered set after a crash-restart
(`ServeEngine.recover`), or from the write-ahead journal's record of a
finished stream. `GET /v1/requests/<id>` likewise falls back to the
journal (marked ``source: "journal"``) for requests evicted from the
bounded registry or served by a previous process incarnation. Admission pressure maps to HTTP: a full waiting
queue (or the paged pool's page-budget gate rejecting) answers 503 +
Retry-After, invalid requests answer 400 with the OpenAI error
envelope (serve/openai.py) — never a traceback over a socket.

Fleet mode (serve/fleet.py): constructed with a `FleetRouter`, the same
surface fronts N replicas — admissions route by prefix affinity /
SLO burn / load with ranked retry on a full replica (`X-Replica-Id`
says where a request landed), the 503 capacity probe and Retry-After
rung reflect the FLEET view, `/metrics` serves the merged + per-replica
labeled exposition, `/statusz` grows a ``fleet`` section, and a drained
replica's SSE streams close WITHOUT a terminal chunk — the reconnect-
with-Last-Event-ID signal; the cursor resolves on the adopting peer
(blocking responses ride the migration transparently instead).

Request tracing rides every completion: the front door honors an
`X-Request-Id` header (minting one when absent or malformed), echoes it
on the response, stamps it on the engine `Request`, and — when the
engine's flight recorder is on — records HTTP-layer spans (`accept` =
headers->body read, `parse` = body->validated, `queue_handoff` =
validated->engine submit, `sse_drain` = engine finish->last byte
written, `disconnect` instants) on an "http" trace track joined to the
engine's lifecycle spans by the request id. The boundaries are
CONTIGUOUS stamps on the engine's own clock, so accept + parse +
queue_handoff + queue + prefill + decode + sse_drain partitions the
server-observed wall exactly — `GET /v1/requests/<id>` assembles that
end-to-end timeline (plus the request's speculative-acceptance,
kv-quant and page-usage facts) from a bounded in-memory registry, with
or without the recorder.

Shutdown ordering (`ApiServer.close`, idempotent): stop accepting new
work (503), drain active streams up to `drain_timeout_s` then cancel
the stragglers, stop the engine loop, `engine.close()`, then tear down
the HTTP threads — so no handler ever touches a closed engine.
"""

from __future__ import annotations

import json
import queue
import random
import re
import select
import socket
import threading
import time
import uuid
from collections import OrderedDict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from solvingpapers_tpu.metrics.http import healthz_response
from solvingpapers_tpu.metrics.writer import PrometheusTextWriter
from solvingpapers_tpu.serve import metrics as smetrics
from solvingpapers_tpu.serve import openai as oai
from solvingpapers_tpu.serve.grammar import JsonStepper
from solvingpapers_tpu.serve.openai import ApiError
from solvingpapers_tpu.serve.scheduler import ACTIVE

# client-supplied X-Request-Id values we honor: short, printable, safe
# to echo into headers/JSON/trace args verbatim. Anything else gets a
# minted id (the request still traces — a hostile header must not be
# able to opt out of observability or smuggle bytes into the trace).
_RID_RE = re.compile(r"^[A-Za-z0-9._:-]{1,128}$")


class EngineLoop:
    """The engine's single driver thread + the submit/cancel gateway.

    Every engine interaction from a handler thread goes through
    `self.lock`; the loop holds it across each `step()`, so the engine
    never sees concurrent mutation. Idle (no work) it parks on an event
    that `submit` sets — no busy-spin, sub-ms wake."""

    def __init__(self, engine, start: bool = True):
        self.engine = engine
        self.lock = threading.RLock()
        self._waiters = 0
        self._waiter_lock = threading.Lock()  # += is not atomic
        self._wake = threading.Event()
        self._stop = threading.Event()
        self.error: BaseException | None = None
        self._thread = threading.Thread(
            target=self._run, name="engine-loop", daemon=True
        )
        if start:
            self._thread.start()

    def _locked(self, fn):
        """Run an engine call under the step lock, counted as a waiter
        so the loop hands the lock over instead of convoying."""
        with self._waiter_lock:
            self._waiters += 1
        try:
            with self.lock:
                return fn()
        finally:
            with self._waiter_lock:
                self._waiters -= 1

    def submit(self, *args, **kwargs):
        if self.error is not None:
            raise RuntimeError(
                f"engine loop died: {type(self.error).__name__}: "
                f"{self.error}"
            )
        req = self._locked(lambda: self.engine.submit(*args, **kwargs))
        self._wake.set()
        return req

    def cancel(self, req) -> None:
        # lock-free fast path for a live stream: cancelling an ACTIVE
        # request is ONE flag write the engine reads at the next block
        # boundary — taking the step lock here would make disconnect
        # cancel wait out the whole remaining stream (the loop re-wins
        # its own lock back-to-back; a handler thread parked on it can
        # starve for seconds — the classic convoy). The flag is written
        # directly, NOT via engine.cancel: its state re-check could race
        # a paged-pool preemption (ACTIVE -> WAITING) and run unlocked
        # queue surgery on this thread; the bare flag is safe in every
        # state (a preempted-then-resumed stream cancels at its next
        # block boundary, a finished one ignores it). A request we see
        # WAITING does need the lock for the queue removal; if it races
        # the other way (WAITING -> ACTIVE), the locked engine.cancel
        # re-checks and degrades to the same flag write.
        if req.state == ACTIVE:
            req.cancelled = True
        else:
            self._locked(lambda: self.engine.cancel(req))
        self._wake.set()

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                with self.lock:
                    busy = self.engine.has_work()
                    if busy:
                        self.engine.step()
            except BaseException as e:  # noqa: BLE001 — must not die mute
                self._fail(e)
                return
            if self._waiters:
                # hand the lock over: without an explicit yield this
                # thread re-acquires it before a parked submitter ever
                # gets scheduled (lock convoy), and submissions stall
                # until the engine drains
                time.sleep(0.001)
            elif not busy:
                self._wake.wait(0.05)
                self._wake.clear()

    def _fail(self, exc: BaseException) -> None:
        """A step() raised: the engine may be inconsistent, so the loop
        stops driving it — but silently wedging every open stream would
        be worse (heartbeats forever, /healthz green). Record the error
        (new submissions fail fast), then force-finish every in-flight
        request host-side with reason "error" so each connection gets
        its terminal event and closes."""
        import traceback

        self.error = exc
        traceback.print_exception(type(exc), exc, exc.__traceback__)
        with self.lock:
            inflight = [r for r in self.engine._slot_req if r is not None]
            inflight += list(self.engine.scheduler.queue)
            now = time.monotonic()
            for r in inflight:
                r.state = "finished"
                r.finish_reason = "error"
                r.finish_time = now
                cb = r.stream_cb
                if cb is not None:
                    try:
                        cb(r, 0, True)
                    except Exception:  # noqa: BLE001
                        pass

    def close(self, drain_timeout_s: float = 0.0) -> None:
        """Stop the loop; with a drain timeout, let in-flight work
        finish first, then cancel whatever remains so the loop can exit
        having returned every lane. BOUNDED end to end: the
        cancel-resolution drain is also wall-capped (a wedged or
        fault-stalled program must not turn SIGTERM into a hang), and
        anything still in flight past the cap is force-finished
        host-side via `engine.force_drain` — no further device work."""
        if not self._thread.is_alive():
            return
        # through `_locked`, as every other caller off the loop's thread:
        # taken bare, the lock is re-won by the loop step after step and
        # close() waits the streams out instead of cancelling them
        deadline = time.monotonic() + drain_timeout_s
        while time.monotonic() < deadline:
            if not self._locked(self.engine.has_work):
                break
            time.sleep(0.01)
        self._locked(lambda: self._cancel_and_drain(drain_timeout_s))
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=5)

    def _cancel_and_drain(self, drain_timeout_s: float) -> None:
        """Under the lock: cancel every request, step the cancels to their
        block boundary within a wall cap, force-finish what is left."""
        for r in list(self.engine._slot_req):
            if r is not None:
                self.engine.cancel(r)
        for r in list(self.engine.scheduler.queue):
            self.engine.cancel(r)
        # one bounded drain pass finishes the cancelled streams
        # (cancels resolve at the next block boundary); capped on
        # BOTH steps and wall clock — a step stalled past the cap
        # falls through to the host-side force drain below
        steps = 0
        cancel_deadline = time.monotonic() + min(
            5.0, max(1.0, drain_timeout_s)
        )
        while (self.engine.has_work() and steps < 64
               and time.monotonic() < cancel_deadline):
            self.engine.step()
            steps += 1
        if self.engine.has_work():
            self.engine.force_drain("cancelled")


class _Stream:
    """Per-connection bridge from the engine's stream_cb to a handler
    thread: a bounded queue of (n_new, finished) counts. Full queue =
    coalesce (the reader catches up from the request's token list);
    the terminal event always lands (a slot is drained to make room)."""

    def __init__(self, maxsize: int):
        self.q: queue.Queue = queue.Queue(maxsize=max(2, maxsize))

    def __call__(self, req, n_new: int, finished: bool) -> None:
        try:
            self.q.put_nowait((n_new, finished))
        except queue.Full:
            if finished:
                try:
                    self.q.get_nowait()
                except queue.Empty:
                    pass
                self.q.put_nowait((n_new, finished))


class ApiServer:
    """The front door: binds `engine.config.api_host:api_port` and
    serves the OpenAI surface + the status endpoints over one listener.

    `decode` (ids -> text) renders streamed text and backs json_object
    mode's token table; `encode` (text -> ids) admits string prompts —
    without it only token-id prompts are accepted. `token_table`
    (id -> string list) skips the per-id decode probe when the caller
    already built one (`cli serve` does — one source of truth). `loop`
    lets tests inject an unstarted `EngineLoop`; by default the server
    owns one.
    """

    # request timelines kept for GET /v1/requests/<id>: a debug surface,
    # so bounded and evict-oldest (a long-lived server must not grow a
    # dict per request served). A client re-using an id overwrites the
    # older entry — last-wins, like the header contract implies.
    timeline_cap = 1024
    # replay runs kept for GET /v1/replay/<id> — same bounded evict-
    # oldest discipline (each record holds a full divergence report)
    replay_cap = 16

    def __init__(self, engine=None, *, encode=None, decode=None,
                 token_table=None, model_name: str = "solvingpapers",
                 loop=None, router=None):
        # fleet mode (serve/fleet.py FleetRouter): the front door keeps
        # its single submit/SSE surface and routes through the router —
        # replica 0 stays `self.engine`/`self.loop` as the config /
        # vocab / grammar / fault-plane source (every replica serves
        # the same model), while admissions, capacity, health, metrics
        # and statusz consult the fleet views
        self.router = router
        if router is not None:
            if engine is None:
                engine = router.replicas[0].engine
            if loop is None:
                loop = router.replicas[0].loop
        if engine is None:
            raise ValueError("ApiServer needs an engine or a router")
        cfg = engine.config
        self.engine = engine
        self.encode = encode
        self.decode = decode
        self.model_name = model_name
        self.loop = loop if loop is not None else EngineLoop(engine)
        self.closing = threading.Event()
        self._closed = False
        self._active = 0          # streams currently open
        self._counts = {
            "requests": 0, "streams": 0, "disconnects": 0,
            "rejected": 0, "client_errors": 0,
        }
        self._count_lock = threading.Lock()
        # jittered Retry-After source: a fixed hint synchronizes every
        # rejected client into a retry herd that lands back as one
        # burst — each 503 draws its own delay instead (seeded for
        # reproducible tests; the draw ORDER across racing handler
        # threads is inherently nondeterministic, which is fine — the
        # point is that the hints differ, not which client gets which)
        self._retry_rng = random.Random(0xFA17)
        self._retry_lock = threading.Lock()
        self._timelines: OrderedDict[str, dict] = OrderedDict()
        self._timeline_lock = threading.Lock()
        # replay observatory (serve/replay.py): bounded run registry,
        # one run in flight at a time (each run builds its own engine —
        # a second concurrent build would thrash the host), and the
        # replay/* gauge payload of the LAST finished run (empty until
        # one exists — the present-iff-enabled key-surface contract)
        self._replays: OrderedDict[str, dict] = OrderedDict()
        self._replay_lock = threading.Lock()
        self._replay_active = False
        self._replay_gauge_vals: dict[str, float] = {}
        vocab = getattr(getattr(engine.model, "cfg", None), "vocab_size",
                        None) or (1 << 31)
        self.vocab_size = vocab
        # token table for grammar mode: caller-supplied, or derived by
        # decoding each id once (None = id outside the detokenizer's
        # range / unprintable)
        self.token_table = list(token_table) if token_table else None
        if self.token_table is None and decode is not None \
                and vocab < (1 << 20):
            table = []
            for i in range(vocab):
                try:
                    table.append(decode([i]))
                except Exception:
                    table.append(None)
            self.token_table = table
        # allowed-set memo shared by every request's stepper: all
        # steppers run over the one token table, so state-keyed entries
        # are valid across requests (serve/grammar.py)
        self._grammar_cache: dict = {}
        self._grammar_err = None
        if cfg.json_mode and self.token_table is not None:
            try:
                JsonStepper(self.token_table)  # vocabulary viability
            except ValueError as e:
                self._grammar_err = str(e)
        elif cfg.json_mode:
            self._grammar_err = (
                "json_object mode needs the server constructed with a "
                "`decode` callable (token table)"
            )
        self.engine.metrics.add_gauge_provider(self._gauges)
        server = self

        class Handler(BaseHTTPRequestHandler):
            # HTTP/1.0 close-delimited framing: SSE bodies end when the
            # connection does, no chunked encoding needed
            def log_message(self, fmt, *args):  # noqa: A003
                pass

            def do_GET(self):  # noqa: N802
                server._get(self)

            def do_POST(self):  # noqa: N802
                server._post(self)

        self._httpd = ThreadingHTTPServer(
            (cfg.api_host, cfg.api_port or 0), Handler
        )
        self._httpd.daemon_threads = True
        self.host = cfg.api_host
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="api-http", daemon=True
        )
        self._thread.start()

    # ------------------------------------------------------------ plumbing

    def url(self, path: str = "") -> str:
        return f"http://{self.host}:{self.port}{path}"

    def _gauges(self) -> dict:
        c = self._counts
        return {
            "serve/http_connections": float(self._active),
            "serve/http_requests": float(c["requests"]),
            "serve/http_streams": float(c["streams"]),
            "serve/http_disconnects": float(c["disconnects"]),
            "serve/http_rejected": float(c["rejected"]),
            "serve/http_client_errors": float(c["client_errors"]),
            # replay/* from the last finished replay run — {} until one
            # has run, so a replay-less server's key surface is unchanged
            **self._replay_gauge_vals,
        }

    def _bump(self, key: str, delta: int = 1) -> None:
        with self._count_lock:
            self._counts[key] += delta

    def _bump_active(self, delta: int) -> None:
        with self._count_lock:
            self._active += delta

    @staticmethod
    def _send(h, code: int, body: str, ctype: str,
              headers: dict | None = None) -> None:
        data = body.encode()
        h.send_response(code)
        h.send_header("Content-Type", ctype)
        h.send_header("Content-Length", str(len(data)))
        for k, v in (headers or {}).items():
            h.send_header(k, v)
        h.end_headers()
        h.wfile.write(data)

    def _send_json(self, h, code: int, obj: dict,
                   headers: dict | None = None) -> None:
        self._send(h, code, json.dumps(obj) + "\n", "application/json",
                   headers)

    def _retry_headers(self) -> dict:
        """Backpressure headers for every 503: a JITTERED Retry-After
        (integer seconds; the base grows with the degradation rung, so
        a deeper squeeze pushes retries further out) plus the current
        rung itself — client observability into WHY it was shed."""
        src = self.router if self.router is not None else self.engine
        rung = getattr(src, "degradation_rung", 0)
        with self._retry_lock:
            retry = self._retry_rng.randint(1 + rung, 4 + rung)
        return {"Retry-After": str(retry),
                "X-Degradation-Rung": str(rung)}

    def _engines(self) -> list:
        """Every engine this front door fronts (fleet or single) — the
        scan set for recovered-request and journal lookups: after a
        drain migration the stream's record lives on a PEER replica."""
        if self.router is not None:
            return [r.engine for r in self.router.replicas]
        return [self.engine]

    def _find_recovered(self, rid: str):
        """The recovered/adopted Request for `rid` on ANY replica, or
        None — the Last-Event-ID resolution step between the live
        registry and the journal fallback. When both a drained
        replica's "migrated" husk and a peer's adopted request carry
        the id, the adopted one wins: its token list is the stream."""
        best = None
        for eng in self._engines():
            req = getattr(eng, "_recovered", {}).get(rid)
            if req is None:
                continue
            if req.finish_reason != "migrated":
                return req
            best = best or req
        return best

    def _journal_lookup(self, rid: str):
        """The best journal record for `rid` across the fleet: a LIVE
        entry anywhere wins outright (the stream is still running —
        e.g. adopted by a peer but not yet recovered into a Request);
        among finished entries, a real outcome beats the drained
        replica's ``"migrated"`` tombstone (the adopting replica's
        record is the one whose tokens are the stream's truth)."""
        best = None
        for eng in self._engines():
            entry = (eng.journal.lookup(rid)
                     if eng.journal is not None else None)
            if entry is None:
                continue
            if not entry.finished:
                return entry
            if best is None or (best.finish_reason == "migrated"
                                and entry.finish_reason != "migrated"):
                best = entry
        return best

    def _loop_for(self, req):
        """The EngineLoop that owns `req` — the router's owner map in
        fleet mode (a migrated stream's cancel must land on the replica
        actually decoding it), `self.loop` otherwise."""
        if self.router is not None:
            return self.router.owner_loop(req)
        return self.loop

    def _send_error(self, h, err: ApiError,
                    headers: dict | None = None) -> None:
        self._bump("rejected" if err.status == 503 else "client_errors")
        headers = dict(headers or {})
        if err.status == 503:
            headers.update(self._retry_headers())
        try:
            self._send_json(h, err.status, err.body(), headers)
        except (BrokenPipeError, ConnectionResetError):
            pass

    # ------------------------------------------------------------- routes

    def _get(self, h) -> None:
        path = h.path.split("?", 1)[0]
        try:
            if path == "/healthz":
                # the engine's health state machine through the shared
                # wire mapping (metrics/http.py healthz_response — the
                # status-port endpoint uses the same one, so the two
                # /healthz surfaces can never diverge); a dead engine
                # loop is unhealthy regardless of what the engine says.
                # Fleet mode serves the ROUTER's view: healthy while any
                # admitting replica is (the router steers around the
                # rest — one sick replica must not fail the fleet out
                # of an external balancer's rotation)
                if self.router is not None:
                    state = self.router.health
                else:
                    state = getattr(self.engine, "health", "healthy")
                    if self.loop.error is not None:
                        state = "unhealthy"
                code, body = healthz_response(state)
                self._send(h, code, body, "text/plain")
            elif path == "/metrics":
                # prom_snapshot: latency histograms render as native
                # _bucket/_sum/_count series on this pull path. Fleet
                # mode: ONE exposition with the unlabeled merged series
                # (exact LogHistogram merge) + replica="rN"-labeled
                # per-replica series (render_sets keeps one # TYPE per
                # name across the label sets)
                if self.router is not None:
                    text = PrometheusTextWriter.render_sets(
                        self.router.prom_sets())
                else:
                    with self.loop.lock:
                        step, snap = (self.engine._step_idx,
                                      self.engine.metrics.prom_snapshot())
                    text = PrometheusTextWriter.render(step, snap)
                self._send(h, 200, text, "text/plain; version=0.0.4")
            elif path == "/statusz":
                with self.loop.lock:
                    doc = self.engine.statusz()
                if self.router is not None:
                    # replica 0's engine doc stays the backbone (same
                    # keys as single-engine serving — dashboards keep
                    # working); the fleet section adds the per-replica
                    # occupancy/health/rung table + routing counters
                    doc["fleet"] = self.router.statusz()
                self._send_json(h, 200, doc)
            elif path == "/timeseriesz":
                # the rolling retrospective: per-replica docs in fleet
                # mode, the single engine's doc otherwise; 404 when the
                # owner runs without a store (timeseries=False)
                if self.router is not None:
                    self._send_json(h, 200, self.router.timeseriesz())
                elif getattr(self.engine, "timeseries", None) is not None:
                    self._send_json(h, 200, self.engine.timeseries.doc())
                else:
                    self._send(h, 404, "no time-series store (run with "
                               "timeseries enabled)\n", "text/plain")
            elif path == "/v1/models":
                self._send_json(h, 200, {
                    "object": "list",
                    "data": [{"id": self.model_name, "object": "model",
                              "owned_by": "local"}],
                })
            elif path.startswith("/v1/requests/"):
                self._request_status(h, path[len("/v1/requests/"):])
            elif path.startswith("/v1/replay/"):
                self._replay_status(h, path[len("/v1/replay/"):])
            else:
                self._send(h, 404, "not found\n", "text/plain")
        except (BrokenPipeError, ConnectionResetError):
            pass
        except Exception as e:  # noqa: BLE001 — a handler must not die
            try:
                self._send(h, 500, f"{type(e).__name__}: {e}\n",
                           "text/plain")
            except (BrokenPipeError, ConnectionResetError):
                pass

    def _request_status(self, h, rid: str) -> None:
        """GET /v1/requests/<id>: the request's end-to-end timeline —
        HTTP phases + engine lifecycle phases (they partition the
        server-observed wall exactly: contiguous stamps on one clock)
        plus its speculative/kv-quant/page facts and SLO verdict."""
        with self._timeline_lock:
            rec = self._timelines.get(rid)
        if rec is None:
            # journal fallback: a request evicted from the bounded
            # registry (or served by a PREVIOUS process incarnation)
            # still has its full record in the write-ahead journal —
            # reconstruct what it holds, marked source "journal"
            doc = self._journal_timeline(rid)
            if doc is not None:
                self._send_json(h, 200, doc, {"X-Request-Id": rid})
                return
            self._send_json(h, 404, {"error": {
                "message": f"no timeline for request id {rid!r} (unknown, "
                           f"evicted past the last "
                           f"{self.timeline_cap} requests with no journal "
                           "record, or aged out of the journal's finished "
                           "window)",
                "type": "invalid_request_error", "param": None,
                "code": "request_not_found",
            }})
            return
        self._send_json(h, 200, self._assemble_timeline(rec),
                        {"X-Request-Id": rid})

    def _journal_timeline(self, rid: str) -> dict | None:
        """`GET /v1/requests/<id>` from the journal alone: no HTTP
        phases (the connection that carried the request may predate
        this process), but the durable facts — prompt/completion
        sizes, the committed token ids themselves, outcome, usage —
        are all reconstructible. `source: "journal"` marks the
        provenance; a live recovered request reports its current
        committed state."""
        entry = self._journal_lookup(rid)
        if entry is None:
            return None
        recovered = self._find_recovered(rid) is not None
        if entry.finished:
            state = "finished"
        elif recovered:
            state = "active"
        else:
            state = "journaled"
        return {
            "request_id": rid,
            "source": "journal",
            "state": state,
            "recovered": recovered,
            "finish_reason": entry.finish_reason,
            "tokens": list(entry.tokens),
            "usage": entry.usage,
            "facts": {
                "prompt_tokens": len(entry.prompt),
                "completion_tokens": len(entry.tokens),
                "grammar": entry.grammar,
            },
        }

    # ---------------------------------------------------------- replay

    def _post_replay(self, h) -> None:
        """POST /v1/replay: launch a bounded background replay of a
        journal against a candidate config (serve/replay.py) — the
        live engine's weights on a FRESH engine, the live engine never
        touched. Body: ``journal`` (default: this engine's own journal
        path), ``config_overrides`` (ServeConfig field -> value),
        ``max_requests`` (corpus cap, default 256), ``cut_stride``,
        ``pace``. One run in flight at a time (409 otherwise); poll
        GET /v1/replay/<id> for progress + the report. 202 on
        accept."""
        from solvingpapers_tpu.serve import replay as replay_mod

        try:
            body = self._read_body(h)
            journal = body.get("journal") or self.engine.config.journal_path
            if not journal:
                raise ApiError(
                    "no journal to replay: pass 'journal' (a path this "
                    "server can read) or serve with --journal",
                    param="journal")
            overrides = body.get("config_overrides") or {}
            if not isinstance(overrides, dict):
                raise ApiError("config_overrides must be an object",
                               param="config_overrides")
            try:
                candidate = replay_mod.apply_overrides(
                    self.engine.config, dict(overrides))
            except (ValueError, TypeError) as e:
                raise ApiError(str(e), param="config_overrides") from None
            max_requests = int(body.get("max_requests", 256))
            cut_stride = int(body.get("cut_stride", 8))
            pace = bool(body.get("pace", False))
        except ApiError as e:
            self._send_error(h, e)
            return
        with self._replay_lock:
            if self._replay_active:
                self._send_json(h, 409, {"error": {
                    "message": "a replay run is already in flight — "
                               "poll it to completion first",
                    "type": "invalid_request_error", "param": None,
                    "code": "replay_in_flight",
                }})
                return
            self._replay_active = True
            run_id = uuid.uuid4().hex[:12]
            rec = {
                "id": run_id, "state": "running",
                "progress": {"done": 0, "total": 1},
                "journal": journal, "config_overrides": overrides,
                "report": None, "error": None,
            }
            self._replays[run_id] = rec
            while len(self._replays) > self.replay_cap:
                self._replays.popitem(last=False)

        def work():
            try:
                harness = replay_mod.ReplayHarness.from_engine(
                    self.engine)
                entries = harness.load(journal)

                def prog(done, total):
                    rec["progress"] = {"done": done, "total": total}

                rec["report"] = harness.run(
                    entries, candidate, cut_stride=cut_stride,
                    max_requests=max_requests, pace=pace,
                    journal_path=journal, progress=prog)
                rec["state"] = "finished"
                # the replay/* gauges ride the LIVE engine's /metrics
                # and /statusz through the registered provider
                self._replay_gauge_vals = replay_mod.report_gauges(
                    rec["report"])
            except Exception as e:  # noqa: BLE001 — surfaced via GET
                rec["error"] = f"{type(e).__name__}: {e}"
                rec["state"] = "error"
            finally:
                with self._replay_lock:
                    self._replay_active = False

        threading.Thread(target=work, name="replay", daemon=True).start()
        self._send_json(h, 202, {"id": run_id, "state": "running"},
                        {"Location": f"/v1/replay/{run_id}"})

    def _replay_status(self, h, run_id: str) -> None:
        """GET /v1/replay/<id>: state + progress while running, the
        full divergence report once finished, the error string on
        failure. Bounded registry — evicted runs 404."""
        with self._replay_lock:
            rec = self._replays.get(run_id)
            doc = dict(rec) if rec is not None else None
        if doc is None:
            self._send_json(h, 404, {"error": {
                "message": f"no replay run {run_id!r} (unknown or "
                           f"evicted past the last {self.replay_cap} "
                           "runs)",
                "type": "invalid_request_error", "param": None,
                "code": "replay_not_found",
            }})
            return
        self._send_json(h, 200, doc)

    @staticmethod
    def _hop_phases(req) -> dict[str, float]:
        """One migration hop's engine phases from its Request stamps:
        queue / prefill / decode up to ITS finish (a migrated husk
        finishes "migrated" at the drain, so its intervals are closed
        — the trail's partition stays exact across the hop). A hop
        admitted but frozen before its first token spent its whole
        admitted life in prefill."""
        ph: dict[str, float] = {}
        if req.admit_time is not None:
            ph["queue"] = req.admit_time - req.submit_time
            if req.first_token_time is not None:
                ph["prefill"] = req.first_token_time - req.admit_time
                if req.finish_time is not None:
                    ph["decode"] = req.finish_time - req.first_token_time
            elif req.finish_time is not None:
                ph["prefill"] = req.finish_time - req.admit_time
        elif req.finish_time is not None:
            ph["queue"] = req.finish_time - req.submit_time
        return ph

    def _assemble_timeline(self, rec: dict) -> dict:
        """One JSON timeline from the HTTP record + the engine Request's
        own lifecycle timestamps. Phases are adjacent intervals —
        accept -> parse -> [route] -> queue_handoff -> queue -> prefill
        -> decode -> [migrate -> peer_queue -> peer_prefill ->
        peer_decode ...] -> sse_drain — so their sum equals t_done -
        t_accept (the server-observed e2e wall) to the clock's
        resolution; in-flight requests report the phases they have
        reached so far.

        Fleet: `route` is the router's ranking+retry wall
        (`Request.fleet_route_s`), carved out of the handoff window it
        happens inside so the partition is preserved; after a drain
        migration the trail keeps EVERY hop — the original replica's
        phases up to its "migrated" finish (the husks `rec["hops"]`
        preserved before the front door swapped in each successor),
        the `migrate` gap (freeze -> adoption on the peer), then the
        adopting replica's phases as peer_*."""
        req = rec["req"]
        hops = rec.get("hops") or []
        chain = [hp["req"] for hp in hops] + [req]
        req0 = chain[0]
        cfg = self.engine.config
        phases: dict[str, float] = {
            "accept": rec["t_body"] - rec["t_accept"],
            "parse": rec["t_parsed"] - rec["t_body"],
        }
        handoff = max(req0.submit_time - rec["t_parsed"], 0.0)
        route_s = min(max(getattr(req0, "fleet_route_s", 0.0), 0.0),
                      handoff)
        if route_s > 0:
            phases["route"] = route_s
        phases["queue_handoff"] = handoff - route_s
        if not hops:
            if req.admit_time is not None:
                phases["queue"] = req.admit_time - req.submit_time
                if req.first_token_time is not None:
                    phases["prefill"] = (req.first_token_time
                                         - req.admit_time)
                    if req.finish_time is not None:
                        phases["decode"] = (req.finish_time
                                            - req.first_token_time)
            elif req.finish_time is not None:
                # never admitted (cancel/timeout in the queue, or
                # rejected): its whole engine life was queue time
                phases["queue"] = req.finish_time - req.submit_time
        else:
            phases.update(self._hop_phases(req0))
            for prev, nxt in zip(chain, chain[1:]):
                if prev.finish_time is not None:
                    phases["migrate"] = (
                        phases.get("migrate", 0.0)
                        + max(nxt.submit_time - prev.finish_time, 0.0))
                for k, v in self._hop_phases(nxt).items():
                    key = f"peer_{k}"
                    phases[key] = phases.get(key, 0.0) + v
        if rec["t_done"] is not None and req.finish_time is not None:
            phases["sse_drain"] = max(rec["t_done"] - req.finish_time, 0.0)
        phases = {k: round(v, 6) for k, v in phases.items()}
        facts: dict = {
            "prompt_tokens": int(req.prompt.size),
            "completion_tokens": len(req.tokens),
            "kv_quant": cfg.kv_quant,
            "kv_exact": bool(req.params.kv_exact),
        }
        if cfg.speculative is not None:
            facts["spec"] = {
                "drafter": cfg.speculative,
                "proposed": req.spec_proposed,
                "accepted": req.spec_accepted,
                "acceptance_rate": round(
                    req.spec_accepted / req.spec_proposed, 4
                ) if req.spec_proposed else None,
            }
        if cfg.paged:
            facts["pages_held"] = req.pages_held
            facts["page_size"] = self.engine.pool.page_size
        doc = {
            "request_id": rec["trace_id"],
            "engine_req": req.id,
            "kind": "chat" if rec["chat"] else "completion",
            "stream": rec["stream"],
            "state": req.state,
            "finish_reason": req.finish_reason,
            "disconnected": rec["disconnected"],
            "phases": phases,
            "phase_sum_s": round(sum(phases.values()), 6),
            "e2e_s": round(rec["t_done"] - rec["t_accept"], 6)
            if rec["t_done"] is not None else None,
            "facts": facts,
        }
        if req.slo_result is not None:
            doc["slo"] = req.slo_result
        elif cfg.slo_targets is not None:
            # in flight (or excluded finish): class known, verdict not
            doc["slo"] = {"class": self.engine._slo.classify(req),
                          "attained": None}
        if self.router is not None:
            # the fleet trail facts: which replica served (or is
            # serving) the request, how many peers refused before one
            # took it, and — after a drain migration — every hop the
            # stream took (the husks' engine ids + finish reasons plus
            # the live successor), matching the phases' migrate/peer_*
            # entries above
            doc["fleet"] = {
                "replica": rec.get("replica"),
                "reroutes": int(rec.get("reroutes") or 0),
                "migrated": bool(hops),
                "hops": [
                    {"replica": hp.get("replica"),
                     "engine_req": hp["req"].id,
                     "finish_reason": hp["req"].finish_reason}
                    for hp in hops
                ] + [{"replica": rec.get("replica"),
                      "engine_req": req.id,
                      "finish_reason": req.finish_reason}],
            }
        return doc

    def _post(self, h) -> None:
        # accept boundary: first stamp after the server parsed the
        # request line + headers — everything from here to the last
        # response byte is carved into contiguous spans on this clock
        t_accept = smetrics.now()
        path = h.path.split("?", 1)[0]
        if path == "/v1/replay":
            self._post_replay(h)
            return
        chat = path == "/v1/chat/completions"
        if not chat and path != "/v1/completions":
            self._send(h, 404, "not found\n", "text/plain")
            return
        self._bump("requests")
        # stream resumption: a reconnect presents the last SSE event id
        # it saw ("<request id>:<token offset>") instead of a new job —
        # replay the already-committed tokens (live request, a recovered
        # one after a restart, or the journal's record of a finished
        # stream) and re-attach to the live tail
        lei = (h.headers.get("Last-Event-ID") or "").strip()
        if lei:
            try:
                self._drain_body(h)
                self._resume_stream(h, lei, chat)
            except ApiError as e:
                self._send_error(h, e)
            except (BrokenPipeError, ConnectionResetError):
                self._bump("disconnects")
            except Exception as e:  # noqa: BLE001
                try:
                    self._send_json(h, 500, {"error": {
                        "message": f"{type(e).__name__}: {e}",
                        "type": "internal_error", "param": None,
                        "code": None,
                    }})
                except (BrokenPipeError, ConnectionResetError):
                    pass
            return
        # honor the client's X-Request-Id (sane values only), else mint:
        # the id rides the engine Request, the trace, the response
        # header, and GET /v1/requests/<id> — one identity end to end
        rid_in = (h.headers.get("X-Request-Id") or "").strip()
        trace_id = rid_in if _RID_RE.match(rid_in) else uuid.uuid4().hex
        rid_headers = {"X-Request-Id": trace_id}
        try:
            body = self._read_body(h)
            t_body = smetrics.now()
            self._serve_completion(h, body, chat=chat, trace_id=trace_id,
                                   t_accept=t_accept, t_body=t_body)
        except ApiError as e:
            self._send_error(h, e, headers=rid_headers)
        except (BrokenPipeError, ConnectionResetError):
            self._bump("disconnects")
        except Exception as e:  # noqa: BLE001
            try:
                self._send_json(h, 500, {"error": {
                    "message": f"{type(e).__name__}: {e}",
                    "type": "internal_error", "param": None, "code": None,
                }}, rid_headers)
            except (BrokenPipeError, ConnectionResetError):
                pass

    @staticmethod
    def _check_resume_offset(offset: int, committed: int, rid: str) -> None:
        """Reject a resume offset past the committed prefix instead of
        silently clamping: fsync batches per step, so after a hard
        crash a client can hold tokens the journal never made durable —
        replaying from the clamp would hand it that span a SECOND time
        with no signal. 409 tells it to restart (or re-request inside
        the committed prefix) explicitly."""
        if offset > committed:
            raise ApiError(
                f"Last-Event-ID offset {offset} exceeds the {committed} "
                f"committed token(s) recoverable for request {rid!r} — "
                "the tail past the last durable commit was lost with "
                "the crash; resume from within the committed prefix or "
                "restart the stream",
                status=409, code="resume_offset_beyond_committed",
            )

    def _sse_open(self, h, trace_id: str, replica: str | None = None,
                  reroutes: int = 0):
        """Send the SSE response headers and return THE event writer
        (one framing implementation for live streams, re-attached
        resumes and journal-only replays): each chunk is an optional
        ``id: <trace_id>:<eid>`` resume cursor + a ``data:`` line, and
        the fault plane's ``sse_write`` site pokes per event
        (socket_reset/stall specs apply to replayed streams exactly
        like live ones). FaultPlan.poke serializes internally —
        handler threads and the engine loop share one plan across
        lock domains."""
        h.send_response(200)
        h.send_header("Content-Type", "text/event-stream")
        h.send_header("Cache-Control", "no-cache")
        h.send_header("X-Request-Id", trace_id)
        if replica is not None:
            h.send_header("X-Replica-Id", replica)
        if reroutes:
            # submit was retried on a peer after ranked replicas
            # refused — reroute visibility alongside X-Replica-Id
            h.send_header("X-Fleet-Reroutes", str(reroutes))
        h.end_headers()

        def event(obj, eid: int | None = None) -> None:
            faults = getattr(self.engine, "_faults", None)
            if faults is not None:
                for spec in faults.poke("sse_write"):
                    self.engine.metrics.record_fault_injected()
                    tr = self.engine.trace
                    if tr is not None:
                        # same instant the engine's _poke_site stamps,
                        # so counters and timeline agree on injections
                        tr.instant("fault_injected", "engine", "http",
                                   site="sse_write", kind=spec.kind,
                                   slot=spec.slot)
                    if spec.kind == "socket_reset":
                        raise ConnectionResetError(
                            "injected socket reset at sse_write"
                        )
                    if spec.kind == "stall":
                        time.sleep(spec.stall_s)
            payload = b""
            if eid is not None:
                payload += f"id: {trace_id}:{eid}\n".encode()
            payload += b"data: " + json.dumps(obj).encode() + b"\n\n"
            h.wfile.write(payload)
            h.wfile.flush()

        return event

    @staticmethod
    def _drain_body(h) -> None:
        """Consume (and discard) any request body: a resume reconnect
        needs only the Last-Event-ID header, but the bytes must still
        be read off the socket before the SSE response streams back."""
        try:
            n = int(h.headers.get("Content-Length", 0))
        except ValueError:
            n = 0
        if 0 < n <= (8 << 20):
            h.rfile.read(n)

    def _resume_stream(self, h, lei: str, chat: bool) -> None:
        """Resume a stream from its last delivered SSE event id.

        The id is ``<request id>:<token offset>`` (exactly what the
        server stamped on the `id:` field of every chunk). Sources, in
        order: the live request registry (same process), the engine's
        recovered set (`ServeEngine.recover` after a restart), then the
        write-ahead journal's record of a finished stream. Committed
        tokens past the offset replay immediately; a still-live request
        re-attaches this connection to its tail (the previous
        connection's bridge is abandoned — last reconnect wins, like
        the X-Request-Id contract)."""
        rid, _, off_s = lei.rpartition(":")
        # ASCII digits only: str.isdigit() accepts exotic Unicode
        # digits that int() then rejects, which would turn a malformed
        # header into a 500 instead of this 400
        if not rid or not (off_s.isascii() and off_s.isdigit()):
            raise ApiError(
                f"malformed Last-Event-ID {lei!r} — expected "
                "\"<request id>:<token offset>\" as stamped on the "
                "stream's id: fields", param="Last-Event-ID",
            )
        offset = int(off_s)
        with self._timeline_lock:
            rec = self._timelines.get(rid)
        req = rec["req"] if rec is not None else None
        if req is None:
            req = self._find_recovered(rid)
        if req is not None and req.finish_reason == "migrated" \
                and self.router is not None:
            # the registry's object is the DRAINED replica's husk; the
            # peer's adopted request (same id, same committed prefix,
            # still decoding) is the stream the cursor belongs to
            adopted = self._find_recovered(rid)
            if adopted is not None and adopted is not req:
                if rec is not None:
                    # keep the husk: its phases are the original
                    # replica's leg of the request trail
                    rec.setdefault("hops", []).append(
                        {"req": req, "replica": rec.get("replica")})
                    rec["req"] = adopted
                req = adopted
        if req is not None:
            self._check_resume_offset(offset, len(req.tokens), rid)
            owner = (self.router.owner(rid)
                     if self.router is not None else None)
            if rec is not None and owner is not None:
                rec["replica"] = owner.rid
            new_rec = {
                "trace_id": rid, "req": req, "chat": chat, "stream": True,
                "t_accept": smetrics.now(), "t_body": smetrics.now(),
                "t_parsed": smetrics.now(), "t_done": None,
                "disconnected": False,
                "replica": owner.rid if owner is not None else None,
            }
            bridge = _Stream(self.engine.config.stream_queue)
            if not req.done:
                # re-attach: the engine reads stream_cb at each notify,
                # so the flip is one reference write; a notification
                # racing the flip is absorbed by the drain loop's
                # req.done / token-count polling
                req.stream_cb = bridge
            # prime one event so the replay of already-committed tokens
            # does not wait out the loop's 0.5s poll
            bridge(req, 0, req.done)
            self._bump("streams")
            rid_out = ("chatcmpl-" if chat else "cmpl-") + uuid.uuid4().hex[:24]
            self._stream_response(h, req, bridge, rid_out, chat, new_rec,
                                  start=offset)
            return
        entry = self._journal_lookup(rid)
        if entry is None:
            raise ApiError(
                f"no resumable stream for request id {rid!r} (unknown, "
                "or aged out of the journal's finished window)",
                status=404, code="request_not_found",
            )
        # journal-only replay: the stream has no live engine object
        # (finished, or a restart that never ran recover()) — replay the
        # committed record and close it out honestly
        self._check_resume_offset(offset, len(entry.tokens), rid)
        self._bump("streams")
        rid_out = ("chatcmpl-" if chat else "cmpl-") + uuid.uuid4().hex[:24]
        event = self._sse_open(h, rid)

        # ONE delta implementation (_delta): render the already-seen
        # prefix, then diff — a non-prefix-stable detokenizer resends
        # the full text instead of slicing garbage
        rendered = ""
        if offset:
            _, rendered = self._delta(entry.tokens, offset, "")
        delta, _ = self._delta(entry.tokens, len(entry.tokens), rendered)
        upto = len(entry.tokens)
        if chat:
            event(oai.chat_chunk(rid_out, self.model_name, None,
                                 role=True), eid=offset)
            if delta:
                event(oai.chat_chunk(rid_out, self.model_name, delta),
                      eid=upto)
        elif delta:
            event(oai.completion_chunk(rid_out, self.model_name, delta),
                  eid=upto)
        reason = entry.finish_reason if entry.finished else "error"
        if not entry.finished:
            event(oai.error_event(
                "stream is not live on this server (it was journaled "
                "but not recovered) — committed tokens above are "
                "complete as delivered"))
        usage = entry.usage or {
            "prompt_tokens": len(entry.prompt),
            "completion_tokens": len(entry.tokens),
        }
        usage = {**usage, "total_tokens":
                 usage.get("prompt_tokens", 0)
                 + usage.get("completion_tokens", 0)}
        if chat:
            event(oai.chat_chunk(rid_out, self.model_name, None,
                                 reason=reason, usage=usage), eid=upto)
        else:
            event(oai.completion_chunk(rid_out, self.model_name, "",
                                       reason=reason, usage=usage),
                  eid=upto)
        h.wfile.write(b"data: [DONE]\n\n")
        h.wfile.flush()

    @staticmethod
    def _read_body(h) -> dict:
        try:
            n = int(h.headers.get("Content-Length", 0))
        except ValueError:
            n = 0
        if n <= 0 or n > (8 << 20):
            raise ApiError("request body required (JSON)", param=None)
        raw = h.rfile.read(n)
        try:
            body = json.loads(raw)
        except json.JSONDecodeError as e:
            raise ApiError(f"request body is not valid JSON: {e.msg}",
                           param=None) from None
        if not isinstance(body, dict):
            raise ApiError("request body must be a JSON object")
        return body

    # -------------------------------------------------------- completion

    def _serve_completion(self, h, body: dict, chat: bool, trace_id: str,
                          t_accept: float, t_body: float) -> None:
        cfg = self.engine.config
        if self.closing.is_set():
            raise ApiError("server is shutting down", status=503,
                           err_type="server_error", code="shutting_down")
        if self.router is None and self.loop.error is not None:
            # fleet mode has no single fatal loop: a dead replica just
            # stops admitting and the router routes around it (only an
            # empty candidate set 503s, below)
            raise ApiError(
                "engine loop failed — the server needs a restart "
                f"({type(self.loop.error).__name__})", status=503,
                err_type="server_error", code="engine_failed",
            )
        params, max_tokens, timeout_s = oai.parse_sampling(
            body,
            slo_classes=set(cfg.slo_targets) if cfg.slo_targets else None,
        )
        stream = bool(body.get("stream", False))
        json_mode = oai.wants_json(body, cfg.json_mode)
        if json_mode and self._grammar_err:
            raise ApiError(self._grammar_err, param="response_format")
        if chat:
            prompt_ids = oai.parse_prompt(
                {"prompt": oai.chat_prompt(body)}, self.encode,
                self.vocab_size,
            )
        else:
            prompt_ids = oai.parse_prompt(body, self.encode,
                                          self.vocab_size)
        if stream and self._active >= cfg.api_max_connections:
            raise ApiError(
                f"too many concurrent streams "
                f"({cfg.api_max_connections}) — retry shortly",
                status=503, err_type="server_error", code="overloaded",
            )
        # the backpressure probe consults FLEET-wide queue room when a
        # router fronts several replicas: one busy replica must not 503
        # a request a peer has capacity for (the router also retries
        # ranked candidates on a host-side queue-full rejection below)
        capacity = (self.router.capacity_left if self.router is not None
                    else self.engine.scheduler.capacity_left)
        if capacity == 0:
            raise ApiError(
                "waiting queue is full"
                + (" fleet-wide" if self.router is not None else "")
                + " — retry shortly", status=503,
                err_type="server_error", code="overloaded",
            )
        grammar = (JsonStepper(self.token_table, cache=self._grammar_cache)
                   if json_mode else None)
        bridge = _Stream(cfg.stream_queue)
        # parse boundary: body decoded, sampling/prompt validated, the
        # grammar built — the next stamp the request gets is its own
        # submit_time inside the locked engine call, so the gap between
        # here and there IS the submit-lock handoff
        t_parsed = smetrics.now()
        replica = None
        try:
            if self.router is not None:
                # prefix-affinity + SLO-burn + least-loaded routing,
                # with ranked retry on a full replica queue
                replica, req = self.router.submit(
                    np.asarray(prompt_ids, np.int32),
                    max_new_tokens=max_tokens, params=params,
                    deadline_s=timeout_s, grammar=grammar,
                    stream_cb=bridge, trace_id=trace_id,
                )
                if req is None:
                    raise ApiError(
                        "no replica is admitting (fleet draining or "
                        "unhealthy) — retry shortly", status=503,
                        err_type="server_error", code="engine_unhealthy",
                    )
            else:
                req = self.loop.submit(
                    np.asarray(prompt_ids, np.int32),
                    max_new_tokens=max_tokens, params=params,
                    deadline_s=timeout_s, grammar=grammar,
                    stream_cb=bridge,
                    # the engine journals under this id, so a restarted
                    # server can answer Last-Event-ID reconnects and
                    # /v1/requests/<id> for it
                    trace_id=trace_id,
                )
        except ValueError as e:
            code = ("context_length_exceeded"
                    if "exceeds the engine capacity" in str(e) else None)
            raise ApiError(str(e), code=code) from None
        if req.trace_id is not None and req.trace_id != trace_id:
            # the engine re-keyed a duplicate still-live X-Request-Id to
            # protect the journal (two streams must not merge commits):
            # the client must be told the id its stream is actually
            # addressable by — SSE cursors, the echoed header, the
            # registry entry and post-restart resume all use it (same
            # contract as minting over a malformed header)
            trace_id = req.trace_id
        rec = {
            "trace_id": trace_id, "req": req, "chat": chat,
            "stream": stream, "t_accept": t_accept, "t_body": t_body,
            "t_parsed": t_parsed, "t_done": None, "disconnected": False,
            # which replica admitted it (fleet mode) — the
            # X-Replica-Id response header, for debugging routing
            "replica": replica.rid if replica is not None else None,
            # how many ranked peers refused before one admitted it
            # (router retry-on-full) — the X-Fleet-Reroutes header
            "reroutes": int(getattr(req, "fleet_reroutes", 0) or 0),
            # migration hops: each drain that moved this stream swaps
            # rec["req"] to the adopted successor; the husk is kept
            # here FIRST, so /v1/requests/<id> can stitch the full
            # trail (original replica's phases + migrate gap + peer's)
            "hops": [],
        }
        with self._timeline_lock:
            self._timelines[trace_id] = rec
            self._timelines.move_to_end(trace_id)
            while len(self._timelines) > self.timeline_cap:
                self._timelines.popitem(last=False)
        tr = self.engine.trace
        if tr is not None:
            # HTTP-layer spans on the shared recorder, joined to the
            # engine's lifecycle spans by req id: contiguous boundaries
            # (t_accept -> t_body -> t_parsed -> submit_time) extend the
            # queue+prefill+decode partition across the HTTP boundary
            tr.complete("accept", "http", "http", ts=t_accept,
                        dur=t_body - t_accept, req=req.id,
                        trace_id=trace_id)
            tr.complete("parse", "http", "http", ts=t_body,
                        dur=t_parsed - t_body, req=req.id)
            tr.complete("queue_handoff", "http", "http", ts=t_parsed,
                        dur=max(req.submit_time - t_parsed, 0.0),
                        req=req.id)
        if req.state == "rejected":
            self._bump("rejected")
            rec["t_done"] = smetrics.now()
            why = req.reject_reason or ""
            if why == "unhealthy":
                err = ApiError(
                    "engine is unhealthy and draining — retry shortly",
                    status=503, err_type="server_error",
                    code="engine_unhealthy",
                )
            elif why.startswith("shed:"):
                shed_eng = (replica.engine if replica is not None
                            else self.engine)
                err = ApiError(
                    f"admissions for SLO class {why[5:]!r} are being "
                    f"load-shed (degradation rung "
                    f"{getattr(shed_eng, 'degradation_rung', 0)}) — "
                    "retry after the hinted delay",
                    status=503, err_type="server_error", code="overloaded",
                )
            else:
                err = ApiError(
                    "waiting queue is full"
                    + (" fleet-wide" if self.router is not None else "")
                    + " — retry shortly", status=503,
                    err_type="server_error", code="overloaded",
                )
            headers = {**self._retry_headers(), "X-Request-Id": trace_id}
            if rec["replica"] is not None:
                headers["X-Replica-Id"] = rec["replica"]
            if rec["reroutes"]:
                headers["X-Fleet-Reroutes"] = str(rec["reroutes"])
            self._send_json(h, 503, err.body(), headers)
            return
        rid = ("chatcmpl-" if chat else "cmpl-") + uuid.uuid4().hex[:24]
        if stream:
            self._bump("streams")
            self._stream_response(h, req, bridge, rid, chat, rec)
        else:
            self._blocking_response(h, req, bridge, rid, chat, rec)

    def _delta(self, tokens, upto: int, rendered: str) -> tuple[str, str]:
        """Text delta for tokens[:upto] given what was already rendered.
        Full re-decode (not per-token) so merge-y detokenizers stay
        correct; suffix-after-prefix keeps the stream append-only."""
        if self.decode is None:
            text = "".join(str(t) + " " for t in tokens[:upto])
        else:
            text = self.decode(list(tokens[:upto]))
        if text.startswith(rendered):
            return text[len(rendered):], text
        return text, text  # non-prefix-stable detokenizer: resend

    def _disconnected(self, h) -> bool:
        """Probe the socket for a client half-close without consuming
        request data (there is none after the body in this protocol)."""
        try:
            r, _, _ = select.select([h.connection], [], [], 0)
            if r:
                return h.connection.recv(1, socket.MSG_PEEK) == b""
        except OSError:
            return True
        return False

    def _mark_disconnect(self, req, rec) -> None:
        rec["disconnected"] = True
        rec["t_done"] = smetrics.now()
        self._bump("disconnects")
        tr = self.engine.trace
        if tr is not None:
            tr.instant("disconnect", "http", "http", req=req.id)

    def _mark_done(self, req, rec, events: int = 0) -> None:
        """Stamp the drain boundary: engine finish -> last response byte
        flushed (the tail the client observes after the engine is done —
        event rendering, detokenize, socket writes)."""
        t_done = smetrics.now()
        rec["t_done"] = t_done
        tr = self.engine.trace
        if tr is not None and req.finish_time is not None:
            tr.complete("sse_drain", "http", "http", ts=req.finish_time,
                        dur=max(t_done - req.finish_time, 0.0),
                        req=req.id, events=events)

    def _stream_response(self, h, req, bridge, rid: str,
                         chat: bool, rec: dict, start: int = 0) -> None:
        """`start` > 0 is a Last-Event-ID reconnect: tokens[:start] were
        already delivered to this client — replay resumes from there
        (the committed prefix re-renders so text deltas stay exact).
        Event framing (id: resume cursors + data: lines + the
        sse_write fault site) is `_sse_open`'s — one writer for live
        streams and journal replays."""
        event = self._sse_open(h, rec["trace_id"],
                               replica=rec.get("replica"),
                               reroutes=int(rec.get("reroutes") or 0))
        self._bump_active(1)
        emitted = start
        events = 0
        rendered = ""
        if start > 0:
            _, rendered = self._delta(req.tokens, start, "")

        def cancel_if_mine() -> None:
            # last reconnect wins: a Last-Event-ID re-attach flips
            # req.stream_cb to ITS bridge — an abandoned pre-reconnect
            # handler noticing its own dead socket afterwards must not
            # cancel the stream out from under the live client. The
            # owner lookup routes the cancel to the replica actually
            # decoding (it may have migrated since admission).
            if not req.done and req.stream_cb is bridge:
                self._loop_for(req).cancel(req)

        try:
            if chat:
                event(oai.chat_chunk(rid, self.model_name, None, role=True),
                      eid=emitted)
            while True:
                try:
                    _, finished = bridge.q.get(timeout=0.5)
                except queue.Empty:
                    if req.done:
                        finished = True  # cb raced the queue; finish now
                    elif self._disconnected(h):
                        cancel_if_mine()
                        self._mark_disconnect(req, rec)
                        return
                    else:
                        # SSE comment heartbeat: keeps proxies from
                        # timing the stream out AND surfaces a dead
                        # socket as a write error between tokens
                        h.wfile.write(b": ping\n\n")
                        h.wfile.flush()
                        continue
                # probe for a half-closed client BEFORE writing: a FIN
                # arrives long before a write raises (small SSE events
                # vanish into the send buffer and tiny models finish a
                # whole stream before the first EPIPE), and the peek is
                # two syscalls against a network round trip of tokens
                if self._disconnected(h):
                    cancel_if_mine()
                    self._mark_disconnect(req, rec)
                    return
                upto = len(req.tokens)
                if upto > emitted:
                    delta, rendered = self._delta(req.tokens, upto, rendered)
                    if chat:
                        event(oai.chat_chunk(rid, self.model_name, delta),
                              eid=upto)
                    else:
                        event(oai.completion_chunk(rid, self.model_name,
                                                   delta), eid=upto)
                    emitted = upto
                    events += 1
                if finished:
                    if req.finish_reason == "migrated":
                        # fleet drain: the stream CONTINUES on a peer
                        # replica — close WITHOUT a terminal chunk or
                        # [DONE] (an unterminated SSE stream is the
                        # standard "reconnect with your Last-Event-ID"
                        # signal; the cursor resolves on the adopting
                        # replica through the recovered-set path,
                        # token-exact from exactly this offset). The
                        # committed prefix was fully delivered above:
                        # force_drain froze the token list before the
                        # entries were snapshotted for adoption.
                        h.wfile.write(b": migrated - reconnect with "
                                      b"Last-Event-ID\n\n")
                        h.wfile.flush()
                        self._mark_done(req, rec, events=events)
                        return
                    if req.finish_reason == "error":
                        # SSE error protocol: a quarantined / engine-
                        # failed stream ends with a STRUCTURED error
                        # event before its terminal chunk — never a
                        # silently truncated stream
                        event(oai.error_event(
                            "the request failed in the engine "
                            "(finish_reason error) — partial output "
                            "above is complete as delivered",
                        ))
                    usage = oai.usage_block(req)
                    if chat:
                        event(oai.chat_chunk(rid, self.model_name, None,
                                             reason=req.finish_reason,
                                             usage=usage), eid=emitted)
                    else:
                        event(oai.completion_chunk(rid, self.model_name,
                                                   "",
                                                   reason=req.finish_reason,
                                                   usage=usage),
                              eid=emitted)
                    h.wfile.write(b"data: [DONE]\n\n")
                    h.wfile.flush()
                    self._mark_done(req, rec, events=events + 1)
                    return
        except (BrokenPipeError, ConnectionResetError, OSError):
            # client went away mid-stream: free the slot at the next
            # block boundary and count the disconnect
            cancel_if_mine()
            self._mark_disconnect(req, rec)
        except Exception as e:  # noqa: BLE001 — server-side failure
            # AFTER the 200 + SSE headers went out: the status line is
            # spent, so emit the structured error event + a terminal
            # chunk with finish_reason "error" + [DONE] (best-effort —
            # the socket may be the thing that broke), then release the
            # engine side
            cancel_if_mine()
            try:
                payload = (b"data: " + json.dumps(oai.error_event(
                    f"{type(e).__name__}: {e}")).encode() + b"\n\n")
                term = (oai.chat_chunk(rid, self.model_name, None,
                                       reason="error")
                        if chat else
                        oai.completion_chunk(rid, self.model_name, "",
                                             reason="error"))
                payload += (b"data: " + json.dumps(term).encode()
                            + b"\n\ndata: [DONE]\n\n")
                h.wfile.write(payload)
                h.wfile.flush()
            except OSError:
                pass
            self._mark_done(req, rec, events=events + 2)
        finally:
            self._bump_active(-1)

    def _blocking_response(self, h, req, bridge, rid: str,
                           chat: bool, rec: dict) -> None:
        self._bump_active(1)
        try:
            while True:
                while not req.done:
                    try:
                        _, finished = bridge.q.get(timeout=0.5)
                        if finished and req.done:
                            break
                    except queue.Empty:
                        if self._disconnected(h):
                            self._loop_for(req).cancel(req)
                            self._mark_disconnect(req, rec)
                            return
                if req.finish_reason != "migrated" or self.router is None:
                    break
                # fleet drain mid-request: no bytes have gone out on a
                # blocking response, so the migration is TRANSPARENT —
                # pick up the adopted request on the peer and keep
                # waiting (its committed prefix is this one's; SSE
                # clients get the reconnect protocol instead)
                nxt = self._find_recovered(req.trace_id)
                if nxt is None:
                    # the drain force-finishes the husk BEFORE the peer
                    # adopts it, so this thread can wake mid-migration:
                    # give the in-flight adoption a bounded window to
                    # land before honestly reporting the husk
                    deadline = time.monotonic() + 5.0
                    while nxt is None and time.monotonic() < deadline:
                        time.sleep(0.002)
                        nxt = self._find_recovered(req.trace_id)
                if nxt is None or nxt is req:
                    break  # adoption failed: report the husk honestly
                # keep the husk: its queue/prefill/decode up to the
                # "migrated" finish are the original replica's leg of
                # the request trail (/v1/requests/<id>)
                rec.setdefault("hops", []).append(
                    {"req": req, "replica": rec.get("replica")})
                req = nxt
                rec["req"] = req
                owner = self.router.owner(req.trace_id)
                rec["replica"] = owner.rid if owner is not None else None
                if not req.done:
                    req.stream_cb = bridge
                bridge(req, 0, req.done)  # re-prime past the 0.5s poll
            if self.decode is not None:
                text = self.decode(list(req.tokens))
            else:
                text = "".join(str(t) + " " for t in req.tokens)
            headers = {"X-Request-Id": rec["trace_id"]}
            if rec.get("replica") is not None:
                headers["X-Replica-Id"] = rec["replica"]
            if rec.get("reroutes"):
                headers["X-Fleet-Reroutes"] = str(rec["reroutes"])
            if req.finish_reason == "error":
                # no bytes have gone out on a blocking response: the
                # honest status is a 500 with the structured envelope,
                # not a 200 wrapping a failed stream
                self._send_json(h, 500, oai.error_event(
                    "the request failed in the engine "
                    "(finish_reason error)"), headers)
                self._mark_done(req, rec, events=1)
                return
            if chat:
                self._send_json(h, 200, oai.chat_response(
                    rid, self.model_name, req, text), headers)
            else:
                self._send_json(h, 200, oai.completion_response(
                    rid, self.model_name, req, text), headers)
            self._mark_done(req, rec, events=1)
        finally:
            self._bump_active(-1)

    # -------------------------------------------------------------- close

    def close(self) -> None:
        """Graceful shutdown, idempotent: refuse new work, drain active
        streams (up to `drain_timeout_s`, then cancel), stop the engine
        loop, close the engine, then the HTTP threads."""
        if self._closed:
            return
        self._closed = True
        self.closing.set()
        cfg = self.engine.config
        deadline = time.monotonic() + cfg.drain_timeout_s
        while self._active > 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        if self.router is not None:
            # every replica's loop + engine, sharing the drain budget
            self.router.close(drain_timeout_s=max(
                0.0, deadline - time.monotonic()))
        else:
            self.loop.close(drain_timeout_s=max(
                0.0, deadline - time.monotonic()))
            self.engine.close()
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)


def serve_api(engine, *, encode=None, decode=None,
              model_name: str = "solvingpapers") -> ApiServer:
    """Start the front door for `engine` (reads its ServeConfig api_*
    knobs); returns the running server — call `.close()` to shut the
    whole stack down in order."""
    return ApiServer(engine, encode=encode, decode=decode,
                     model_name=model_name)
