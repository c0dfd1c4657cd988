"""OpenAI-compatible HTTP front door (serve/api.py + serve/openai.py).

The serving contract over a REAL socket: an SSE stream is token-exact
vs direct `engine.submit` for the same prompt/params, client
disconnects cancel the request and free its slot (and, on the paged
pool, every page) within a block boundary, validation failures are
structured 400s in the OpenAI error envelope, admission pressure is a
503 with Retry-After, and shutdown is ordered and idempotent.
"""

import json
import socket
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from solvingpapers_tpu.models.gpt import GPT, GPTConfig
from solvingpapers_tpu.serve import (
    ApiServer,
    EngineLoop,
    ServeConfig,
    ServeEngine,
)

ALPHABET = '{}[]":,-.0123456789 \nabcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOP\\'
TABLE = list(ALPHABET[:64])
STOI = {c: i for i, c in enumerate(TABLE)}

GPT_TINY = GPTConfig(vocab_size=64, block_size=128, dim=32, n_layers=2,
                     n_heads=2, dropout=0.0)


def _encode(s):
    return [STOI[c] for c in s]


def _decode(ids):
    return "".join(TABLE[int(i)] for i in ids)


@pytest.fixture(scope="module")
def gpt_tiny():
    model = GPT(GPT_TINY)
    rng = jax.random.key(0)
    params = model.init({"params": rng}, jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def server(gpt_tiny):
    model, params = gpt_tiny
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=4, max_len=128, decode_block=4, bucket=8, api_port=0,
    ), detokenize=_decode)
    srv = ApiServer(eng, encode=_encode, decode=_decode,
                    model_name="gpt-tiny")
    yield srv, eng
    srv.close()


def _post(srv, path, body, timeout=120):
    req = urllib.request.Request(
        srv.url(path), data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


def _stream_events(srv, body, timeout=120):
    """POST with stream=true over a raw socket; returns parsed SSE
    events (the trailing '[DONE]' sentinel included as a string)."""
    payload = json.dumps({**body, "stream": True}).encode()
    s = socket.create_connection((srv.host, srv.port), timeout=timeout)
    s.sendall(
        b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
        b"Content-Type: application/json\r\nContent-Length: "
        + str(len(payload)).encode() + b"\r\n\r\n" + payload
    )
    buf = b""
    while b"\r\n\r\n" not in buf:
        buf += s.recv(4096)
    head, buf = buf.split(b"\r\n\r\n", 1)
    assert b"200" in head.split(b"\r\n")[0], head
    events = []
    while True:
        while b"\n\n" not in buf:
            chunk = s.recv(4096)
            if not chunk:
                s.close()
                return events
            buf += chunk
        frame, buf = buf.split(b"\n\n", 1)
        frame = frame.strip()
        # SSE frames are field lines: chunks now lead with an
        # ``id: <rid>:<offset>`` resume cursor before their data line
        data_lines = [ln for ln in frame.split(b"\n")
                      if ln.startswith(b"data: ")]
        if not data_lines:
            continue  # heartbeat comments
        payload = data_lines[-1][6:]
        if payload == b"[DONE]":
            s.close()
            events.append("DONE")
            return events
        events.append(json.loads(payload))


# ------------------------------------------------------------- happy path


def test_stream_token_exact_vs_direct_submit(server):
    """Acceptance: the SSE stream carries exactly the tokens
    `engine.submit` produces for the same prompt/params."""
    srv, eng = server
    prompt = list(range(20, 28))
    events = _stream_events(srv, {
        "prompt": prompt, "max_tokens": 12, "temperature": 0,
    })
    assert events[-1] == "DONE"
    chunks = [e for e in events if e != "DONE"]
    text = "".join(c["choices"][0]["text"] for c in chunks)
    terminal = [c for c in chunks if c["choices"][0]["finish_reason"]]
    assert terminal and terminal[-1]["choices"][0]["finish_reason"] == "length"
    assert terminal[-1]["usage"]["completion_tokens"] == 12

    ref = srv.loop.submit(np.asarray(prompt, np.int32), max_new_tokens=12)
    deadline = time.monotonic() + 60
    while not ref.done and time.monotonic() < deadline:
        time.sleep(0.01)
    assert ref.done
    assert text == _decode(ref.tokens)


def test_nonstreaming_completion_shape(server):
    srv, _ = server
    st, _, doc = _post(srv, "/v1/completions", {
        "prompt": list(range(10, 16)), "max_tokens": 8, "temperature": 0,
    })
    assert st == 200
    assert doc["object"] == "text_completion"
    choice = doc["choices"][0]
    assert choice["finish_reason"] == "length"
    assert doc["usage"] == {"prompt_tokens": 6, "completion_tokens": 8,
                            "total_tokens": 14}
    # same prompt, same params -> same greedy text (served twice)
    st2, _, doc2 = _post(srv, "/v1/completions", {
        "prompt": list(range(10, 16)), "max_tokens": 8, "temperature": 0,
    })
    assert doc2["choices"][0]["text"] == choice["text"]


def test_string_prompt_and_stop_strings(server):
    srv, _ = server
    st, _, doc = _post(srv, "/v1/completions", {
        "prompt": "abcd", "max_tokens": 16, "temperature": 0,
    })
    assert st == 200 and len(doc["choices"][0]["text"]) == 16
    gen = doc["choices"][0]["text"]
    stop = gen[2:4]  # a substring the greedy stream will emit
    st, _, doc2 = _post(srv, "/v1/completions", {
        "prompt": "abcd", "max_tokens": 16, "temperature": 0,
        "stop": stop,
    })
    assert st == 200
    assert doc2["choices"][0]["finish_reason"] == "stop"
    assert doc2["choices"][0]["text"].endswith(stop)


def test_chat_completion_shape(server):
    srv, _ = server
    st, _, doc = _post(srv, "/v1/chat/completions", {
        "messages": [{"role": "user", "content": "abc"}],
        "max_tokens": 6, "temperature": 0,
    })
    assert st == 200
    assert doc["object"] == "chat.completion"
    msg = doc["choices"][0]["message"]
    assert msg["role"] == "assistant" and len(msg["content"]) == 6


def test_json_mode_parses(server):
    srv, _ = server
    st, _, doc = _post(srv, "/v1/completions", {
        "prompt": list(range(5, 10)), "max_tokens": 24, "temperature": 0,
        "response_format": {"type": "json_object"},
    })
    assert st == 200
    assert doc["choices"][0]["finish_reason"] == "stop"
    json.loads(doc["choices"][0]["text"])


def test_models_and_status_surface(server):
    srv, _ = server
    with urllib.request.urlopen(srv.url("/v1/models"), timeout=30) as r:
        models = json.loads(r.read())
    assert models["data"][0]["id"] == "gpt-tiny"
    with urllib.request.urlopen(srv.url("/healthz"), timeout=30) as r:
        assert r.read() == b"ok\n"
    with urllib.request.urlopen(srv.url("/metrics"), timeout=30) as r:
        prom = r.read().decode()
    assert "serve_http_requests" in prom
    assert "serve_http_connections" in prom
    with urllib.request.urlopen(srv.url("/statusz"), timeout=30) as r:
        doc = json.loads(r.read())
    assert "engine" in doc and "slots" in doc


# ----------------------------------------------------------- error mapping


@pytest.mark.parametrize("body,param", [
    ({"prompt": [1, 2], "temperature": -1}, None),
    ({"prompt": [1, 2], "top_p": 0}, None),
    ({"prompt": "abc", "n": 2}, "n"),
    ({"prompt": "abc", "echo": True}, "echo"),
    ({"prompt": [], "max_tokens": 4}, "prompt"),
    ({"prompt": [999999]}, "prompt"),
    ({"prompt": [1, 2], "stop": [1]}, "stop"),
    ({"prompt": [1, 2], "logprobs": 5}, "logprobs"),
    ({"prompt": [1, 2], "response_format": {"type": "xml"}},
     "response_format"),
    ({"prompt": [1, 2], "timeout_s": -1}, "timeout_s"),
])
def test_400_envelope(server, body, param):
    srv, _ = server
    st, _, doc = _post(srv, "/v1/completions", body)
    assert st == 400, doc
    err = doc["error"]
    assert err["type"] == "invalid_request_error"
    assert err["message"]
    if param is not None:
        assert err["param"] == param


def test_400_submit_validation_maps_to_envelope(server):
    """Engine-side ValueErrors (host-side submit validation) come back
    as the same structured envelope — never a traceback."""
    srv, _ = server
    st, _, doc = _post(srv, "/v1/completions", {
        "prompt": list(range(8)), "max_tokens": 10_000,
    })
    assert st == 400
    assert doc["error"]["code"] == "context_length_exceeded"
    st, _, doc = _post(srv, "/v1/completions", {
        "prompt": list(range(8)), "top_k": 4096,  # over sample_cap
    })
    assert st == 400
    assert "sample_cap" in doc["error"]["message"]


def test_400_malformed_json(server):
    srv, _ = server
    req = urllib.request.Request(
        srv.url("/v1/completions"), data=b"{not json",
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=30)
    assert ei.value.code == 400
    assert "not valid JSON" in json.loads(ei.value.read())["error"]["message"]


def test_503_retry_after_when_queue_full(gpt_tiny):
    """A full waiting queue (the admission gate) maps to 503 +
    Retry-After instead of an unbounded backlog. The engine loop is
    deliberately NOT running, so the queue cannot drain mid-test."""
    model, params = gpt_tiny
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=1, max_len=128, decode_block=4, bucket=8, api_port=0,
        max_waiting=2,
    ))
    loop = EngineLoop(eng, start=False)
    srv = ApiServer(eng, decode=_decode, loop=loop)
    try:
        for _ in range(2):
            eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=4)
        st, headers, doc = _post(srv, "/v1/completions", {
            "prompt": [1, 2, 3], "max_tokens": 4,
        })
        assert st == 503
        assert headers.get("Retry-After") == "1"
        assert doc["error"]["code"] == "overloaded"
    finally:
        srv.close()


# -------------------------------------------------- disconnect-driven cancel


def test_disconnect_cancels_and_frees_pages(gpt_tiny):
    """Acceptance: a client dropping mid-stream cancels the request
    within a block boundary — the slot frees, `serve/finish_cancelled`
    counts it, and the paged pool leaks ZERO pages (refcounts return
    to the trash-page-only baseline)."""
    model, params = gpt_tiny
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=128, decode_block=4, bucket=8, api_port=0,
        paged=True, page_size=8,
    ))
    srv = ApiServer(eng, decode=_decode)
    try:
        payload = json.dumps({
            "prompt": [5, 6, 7, 8], "max_tokens": 100, "temperature": 0,
            "stream": True,
        }).encode()
        s = socket.create_connection((srv.host, srv.port), timeout=60)
        s.sendall(
            b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
            b"Content-Type: application/json\r\nContent-Length: "
            + str(len(payload)).encode() + b"\r\n\r\n" + payload
        )
        buf = b""
        while buf.count(b"data: ") < 2:
            buf += s.recv(4096)
        s.close()  # the disconnect
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            snap = eng.metrics.snapshot()
            if (snap.get("serve/finish_cancelled", 0) >= 1
                    and eng.pool.n_active == 0):
                break
            time.sleep(0.02)
        snap = eng.metrics.snapshot()
        assert snap.get("serve/finish_cancelled", 0) == 1, snap
        assert snap["serve/tokens_out"] < 100, "cancel missed the stream"
        assert eng.pool.n_active == 0
        # no leaked pages: free count back to the full budget and the
        # only live refcount is the permanently-held trash page
        assert eng.pool.pages_free == eng.pool.page_budget
        assert int(eng.pool.refcount.sum()) == 1
        assert snap["serve/http_disconnects"] >= 1
    finally:
        srv.close()


def test_timeout_s_maps_to_deadline(server):
    srv, _ = server
    st, _, doc = _post(srv, "/v1/completions", {
        "prompt": list(range(6)), "max_tokens": 100, "temperature": 0,
        "timeout_s": 0.001,
    })
    assert st == 200
    assert doc["choices"][0]["finish_reason"] == "timeout"


# ------------------------------------------------------------------ close


def test_close_is_ordered_and_idempotent(gpt_tiny):
    """Double-close regression: close() drains, closes the engine, and
    a second close is a no-op — no exception, no double shutdown."""
    model, params = gpt_tiny
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=128, decode_block=4, bucket=8, api_port=0,
        drain_timeout_s=5.0,
    ))
    srv = ApiServer(eng, decode=_decode)
    h = srv.loop.submit(np.arange(4, dtype=np.int32), max_new_tokens=8)
    srv.close()
    assert h.done  # drained, not abandoned
    assert not srv.loop._thread.is_alive()
    srv.close()  # idempotent
    # the port is actually released: a fresh connect fails
    with pytest.raises(OSError):
        socket.create_connection((srv.host, srv.port), timeout=1)


# --------------------------------------------- request tracing / timeline


@pytest.fixture(scope="module")
def traced_server(gpt_tiny):
    """Front door with the flight recorder + SLO accounting on and a
    1-token decode block, so requests run long enough (many engine
    steps) for the client-wall partition pin to be meaningful."""
    from solvingpapers_tpu.serve.slo import DEFAULT_SLO_TARGETS

    model, params = gpt_tiny
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=128, decode_block=1, bucket=8, api_port=0,
        trace=True, slo_targets=DEFAULT_SLO_TARGETS,
    ), detokenize=_decode)
    srv = ApiServer(eng, encode=_encode, decode=_decode,
                    model_name="gpt-tiny-traced")
    # warm every program shape so the pinned request pays no compile
    _post(srv, "/v1/completions", {"prompt": list(range(8)),
                                   "max_tokens": 4, "temperature": 0})
    yield srv, eng
    srv.close()


def _stream_with_rid(srv, body, rid=None, timeout=120):
    """Raw-socket SSE POST; returns (response headers dict, events,
    t_start, t_done) with the wall clock read immediately around the
    socket's life — the client-observed e2e."""
    payload = json.dumps({**body, "stream": True}).encode()
    hdrs = (b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
            b"Content-Type: application/json\r\n")
    if rid is not None:
        hdrs += b"X-Request-Id: " + rid.encode() + b"\r\n"
    hdrs += b"Content-Length: " + str(len(payload)).encode() + b"\r\n\r\n"
    t_start = time.monotonic()
    s = socket.create_connection((srv.host, srv.port), timeout=timeout)
    s.sendall(hdrs + payload)
    buf = b""
    while b"\r\n\r\n" not in buf:
        buf += s.recv(4096)
    head, buf = buf.split(b"\r\n\r\n", 1)
    lines = head.decode().split("\r\n")
    assert "200" in lines[0], head
    headers = {}
    for ln in lines[1:]:
        k, _, v = ln.partition(":")
        headers[k.strip().lower()] = v.strip()
    events = []
    t_done = None
    while True:
        while b"\n\n" not in buf:
            chunk = s.recv(4096)
            if not chunk:
                s.close()
                return headers, events, t_start, t_done or time.monotonic()
        frame, buf = buf.split(b"\n\n", 1)
        frame = frame.strip()
        if not frame.startswith(b"data: "):
            continue
        if frame[6:] == b"[DONE]":
            t_done = time.monotonic()
            s.close()
            return headers, events, t_start, t_done
        events.append(json.loads(frame[6:]))


def _get_json(srv, path):
    try:
        with urllib.request.urlopen(srv.url(path), timeout=30) as r:
            return r.status, dict(r.headers), json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


def test_request_id_round_trip_and_timeline_partition(traced_server):
    """Acceptance: X-Request-Id round-trips, GET /v1/requests/<id>
    returns the end-to-end timeline, and its phases (accept -> parse ->
    queue_handoff -> queue -> prefill -> decode -> sse_drain) partition
    the client-observed e2e wall within 5%."""
    srv, eng = traced_server
    rid = "pin-req-001"
    headers, events, t_start, t_done = _stream_with_rid(
        srv, {"prompt": list(range(12)), "max_tokens": 96,
              "temperature": 0, "slo": "standard"}, rid=rid)
    assert headers.get("x-request-id") == rid
    client_wall = t_done - t_start
    st, ghdrs, doc = _get_json(srv, f"/v1/requests/{rid}")
    assert st == 200
    assert ghdrs.get("X-Request-Id") == rid
    assert doc["request_id"] == rid
    assert doc["state"] == "finished"
    assert doc["finish_reason"] == "length"
    phases = doc["phases"]
    assert set(phases) == {"accept", "parse", "queue_handoff", "queue",
                           "prefill", "decode", "sse_drain"}
    assert all(v >= 0 for v in phases.values())
    # server-side partition is exact by construction (contiguous stamps
    # on one clock)...
    assert doc["phase_sum_s"] == pytest.approx(doc["e2e_s"], abs=2e-5)
    # ...and covers the CLIENT-observed wall within 5% (the remainder
    # is TCP connect + request write ahead of the accept stamp)
    assert doc["phase_sum_s"] == pytest.approx(client_wall, rel=0.05)
    # the timeline carries the request's serving facts
    facts = doc["facts"]
    assert facts["prompt_tokens"] == 12
    assert facts["completion_tokens"] == 96
    assert facts["kv_quant"] is None and facts["kv_exact"] is False
    assert doc["slo"]["class"] == "standard"
    assert doc["slo"]["attained"] in (True, False)
    assert set(doc["slo"]["latencies"]) >= {"ttft_s", "e2e_s"}


def test_request_id_minted_when_absent_or_malformed(traced_server):
    srv, _ = traced_server
    headers, _, _, _ = _stream_with_rid(
        srv, {"prompt": list(range(8)), "max_tokens": 4,
              "temperature": 0})
    minted = headers.get("x-request-id")
    assert minted and len(minted) == 32  # uuid4 hex
    st, _, doc = _get_json(srv, f"/v1/requests/{minted}")
    assert st == 200 and doc["request_id"] == minted
    # hostile/malformed ids are replaced, never echoed back verbatim
    headers, _, _, _ = _stream_with_rid(
        srv, {"prompt": list(range(8)), "max_tokens": 4,
              "temperature": 0}, rid="bad id\x7f!" )
    assert headers.get("x-request-id") != "bad id\x7f!"


def test_request_timeline_unknown_id_404_and_blocking_path(traced_server):
    srv, _ = traced_server
    st, _, doc = _get_json(srv, "/v1/requests/never-seen")
    assert st == 404
    assert doc["error"]["code"] == "request_not_found"
    # non-streaming responses carry the id + timeline too
    req = urllib.request.Request(
        srv.url("/v1/completions"),
        data=json.dumps({"prompt": list(range(6)), "max_tokens": 6,
                         "temperature": 0}).encode(),
        headers={"Content-Type": "application/json",
                 "X-Request-Id": "blocking-1"}, method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        assert r.status == 200
        assert r.headers.get("X-Request-Id") == "blocking-1"
        json.loads(r.read())
    st, _, doc = _get_json(srv, "/v1/requests/blocking-1")
    assert st == 200
    assert doc["stream"] is False
    assert doc["phases"]["sse_drain"] >= 0  # response-write drain


def test_http_spans_join_engine_trace(traced_server):
    """The recorder holds http-category spans for served requests, and
    summarize_trace assembles rows with BOTH engine and http phases."""
    from solvingpapers_tpu.metrics.trace import summarize_trace

    srv, eng = traced_server
    rid = "trace-join-1"
    _stream_with_rid(srv, {"prompt": list(range(10)), "max_tokens": 8,
                           "temperature": 0}, rid=rid)
    names = {e.name for e in eng.trace.events() if e.cat == "http"}
    assert {"accept", "parse", "queue_handoff", "sse_drain"} <= names
    accept = next(e for e in eng.trace.events()
                  if e.cat == "http" and e.name == "accept"
                  and (e.args or {}).get("trace_id") == rid)
    summary = summarize_trace(eng.trace.to_chrome())
    row = next(r for r in summary["requests"]
               if r["req"] == accept.req)
    assert {"accept", "parse", "queue_handoff",
            "sse_drain"} <= set(row["http_phases"])
    assert row["e2e_s"] > row["total_s"]
    assert "http" in summary


def test_service_tier_alias_is_best_effort(traced_server):
    """The explicit `slo` field validates strictly (typo -> 400), but
    OpenAI's `service_tier` only maps when it names a configured class
    — stock values this server has no class for must not turn a valid
    OpenAI request into a 400."""
    srv, _ = traced_server
    st, _, doc = _post(srv, "/v1/completions", {
        "prompt": list(range(6)), "max_tokens": 4, "temperature": 0,
        "service_tier": "flex",  # documented OpenAI value, no class here
    })
    assert st == 200, doc
    st, _, doc = _post(srv, "/v1/completions", {
        "prompt": list(range(6)), "max_tokens": 4, "temperature": 0,
        "service_tier": "interactive",  # names a configured class
    })
    assert st == 200
    st, hdrs, doc = _post(srv, "/v1/completions", {
        "prompt": list(range(6)), "max_tokens": 4, "temperature": 0,
        "slo": "platinum",  # explicit field stays strict
    })
    assert st == 400
    assert "unknown SLO class" in doc["error"]["message"]
    assert hdrs.get("X-Request-Id")  # even the 400 carries an id


def test_400_envelope_carries_request_id(traced_server):
    srv, _ = traced_server
    req = urllib.request.Request(
        srv.url("/v1/completions"),
        data=json.dumps({"prompt": "x", "temperature": -1}).encode(),
        headers={"Content-Type": "application/json",
                 "X-Request-Id": "err-1"}, method="POST")
    try:
        urllib.request.urlopen(req, timeout=60)
        raise AssertionError("expected a 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400
        assert e.headers.get("X-Request-Id") == "err-1"
        assert json.loads(e.read())["error"]["type"] == \
            "invalid_request_error"


# ------------------------------------------------------- fault tolerance


from conftest import assert_no_leaks  # noqa: E402


def _fault_server(gpt_tiny, plan, **cfg_kw):
    model, params = gpt_tiny
    base = dict(n_slots=2, max_len=128, decode_block=4, bucket=8,
                api_port=0, fault_plan=plan)
    base.update(cfg_kw)
    eng = ServeEngine(model, params, ServeConfig(**base),
                      detokenize=_decode)
    srv = ApiServer(eng, encode=_encode, decode=_decode,
                    model_name="gpt-tiny")
    return srv, eng


def test_sse_error_protocol_on_quarantine(gpt_tiny):
    """The mid-stream error contract: a quarantined stream must end
    with a structured OpenAI error event, a terminal chunk carrying
    finish_reason "error", and [DONE] — never a silently dropped
    connection."""
    plan = [dict(site="decode", kind="nan", visit=1, slot=0)]
    srv, eng = _fault_server(gpt_tiny, plan)
    try:
        events = _stream_events(srv, {
            "prompt": list(range(20, 28)), "max_tokens": 24,
            "temperature": 0,
        })
        assert events[-1] == "DONE", "stream must terminate cleanly"
        err_events = [e for e in events[:-1] if "error" in e]
        assert err_events, "no structured error event before [DONE]"
        assert err_events[0]["error"]["type"] == "server_error"
        terminal = [e for e in events[:-1] if "choices" in e
                    and e["choices"][0]["finish_reason"]]
        assert terminal and \
            terminal[-1]["choices"][0]["finish_reason"] == "error"
        assert_no_leaks(eng)
    finally:
        srv.close()


def test_injected_socket_reset_drives_disconnect_cancel(gpt_tiny):
    """A socket_reset fault at the sse_write site maps to the
    disconnect path: the engine cancels at the block boundary and the
    drained pool leaks nothing."""
    plan = [dict(site="sse_write", kind="socket_reset", visit=1)]
    srv, eng = _fault_server(gpt_tiny, plan, paged=True, page_size=8)
    try:
        events = _stream_events(srv, {
            "prompt": list(range(16, 24)), "max_tokens": 64,
            "temperature": 0,
        })
        assert "DONE" not in events, "reset stream cannot complete"
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            snap = eng.metrics.snapshot()
            if snap.get("serve/finish_cancelled"):
                break
            time.sleep(0.02)
        assert eng.metrics.snapshot().get("serve/finish_cancelled") == 1.0
        assert snap.get("serve/fault_injected") == 1.0
        deadline = time.monotonic() + 10
        while eng.pool.n_active and time.monotonic() < deadline:
            time.sleep(0.02)
        with srv.loop.lock:
            assert_no_leaks(eng)
    finally:
        srv.close()


def test_retry_after_is_jittered_and_carries_rung(gpt_tiny):
    """503s must not synchronize retry herds: the Retry-After hint is
    drawn per response (observably non-constant over a handful of
    draws) and the current degradation rung rides a response header."""
    model, params = gpt_tiny
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=1, max_len=128, decode_block=4, bucket=8, api_port=0,
        max_waiting=1,
    ), detokenize=_decode)
    loop = EngineLoop(eng, start=False)  # engine never steps: queue fills
    srv = ApiServer(eng, encode=_encode, decode=_decode,
                    model_name="gpt-tiny", loop=loop)
    try:
        # fill the 1-deep waiting queue directly (the loop never steps,
        # so it stays full and every HTTP submission bounces 503)
        srv.loop.submit(np.asarray([1, 2, 3], np.int32),
                        max_new_tokens=4)
        hints = set()
        for _ in range(12):
            st, hdrs, doc = _post(srv, "/v1/completions",
                                  {"prompt": [1, 2, 3], "max_tokens": 4})
            if st != 503:
                continue
            assert doc["error"]["code"] == "overloaded"
            assert hdrs.get("X-Degradation-Rung") == "0"
            retry = int(hdrs["Retry-After"])
            assert 1 <= retry <= 4
            hints.add(retry)
        assert len(hints) > 1, f"Retry-After never varied: {hints}"
    finally:
        srv.close()


def test_unhealthy_engine_503s_then_recovers_token_exact(gpt_tiny):
    """End-to-end recovery through the front door: persistent systemic
    faults drain the engine (blocking response = 500 envelope, /healthz
    = 503, new submissions = 503 engine_unhealthy), and after the
    backoff a fresh HTTP request streams token-exactly vs direct
    submit on the recovered engine."""
    plan = [dict(site="decode", kind="xla_error", visit=0, count=2)]
    srv, eng = _fault_server(
        gpt_tiny, plan, fault_max_retries=1, fault_retry_backoff_s=0.001,
        fault_recover_backoff_s=0.6,
    )
    try:
        prompt = list(range(30, 38))
        st, _, doc = _post(srv, "/v1/completions",
                           {"prompt": prompt, "max_tokens": 12,
                            "temperature": 0})
        assert st == 500, (st, doc)
        assert doc["error"]["code"] == "engine_error"
        with urllib.request.urlopen(srv.url("/healthz"),
                                    timeout=30) as r:
            raise AssertionError(f"healthz answered {r.status}")
    except urllib.error.HTTPError as e:
        assert e.code == 503 and e.read() == b"unhealthy\n"
        # inside the backoff: the front door sheds with the reason
        st, hdrs, doc = _post(srv, "/v1/completions",
                              {"prompt": prompt, "max_tokens": 12,
                               "temperature": 0})
        assert st == 503 and doc["error"]["code"] == "engine_unhealthy"
        assert "Retry-After" in hdrs
        time.sleep(0.65)
        st, _, doc = _post(srv, "/v1/completions",
                           {"prompt": prompt, "max_tokens": 12,
                            "temperature": 0})
        assert st == 200, (st, doc)
        assert doc["choices"][0]["finish_reason"] == "length"
        with urllib.request.urlopen(srv.url("/healthz"),
                                    timeout=30) as r:
            assert r.status == 200
        # token-exact vs direct submit on the recovered engine
        ref = srv.loop.submit(np.asarray(prompt, np.int32),
                              max_new_tokens=12)
        deadline = time.monotonic() + 60
        while not ref.done and time.monotonic() < deadline:
            time.sleep(0.02)
        assert ref.done
        assert doc["choices"][0]["text"] == _decode(ref.tokens)
    finally:
        srv.close()


def test_server_close_bounded_under_injected_stall(gpt_tiny):
    """SIGTERM cannot hang on a wedged request: with every step
    stalling, ApiServer.close() force-cancels and returns within its
    bound instead of waiting out 64 stalled steps."""
    plan = [dict(site="decode", kind="stall", visit=0, stall_s=0.3,
                 count=1000)]
    srv, eng = _fault_server(gpt_tiny, plan, drain_timeout_s=0.2)
    req = srv.loop.submit(np.asarray(list(range(8)), np.int32),
                          max_new_tokens=64)
    # let the loop start stepping (and stalling): past its first decode
    # block the programs are compiled, so close() is timed against the
    # stalls and not against XLA compiling in the loop's thread
    waited = time.monotonic() + 120
    while len(req.tokens) < 2 and time.monotonic() < waited:
        time.sleep(0.05)
    assert len(req.tokens) >= 2 and not req.done
    t0 = time.monotonic()
    srv.close()
    took = time.monotonic() - t0
    assert took < 6.0, f"close took {took:.1f}s — unbounded shutdown"
    assert req.done and req.finish_reason == "cancelled"
    assert_no_leaks(eng)
