"""CI crash-recovery smoke: SIGKILL a journaled `cli serve` mid-decode,
restart it on the same journal, and assert the recovered completions
are byte-identical to an uninterrupted reference.

The in-process recovery tests (tests/test_journal.py) abandon an engine
object; this smoke does the real thing — a subprocess
`python -m solvingpapers_tpu.cli serve --journal ...` killed with
SIGKILL while SSE streams are mid-flight — and drives the full client
resume protocol: each stream tracks the last ``id: <rid>:<offset>``
field it saw, reconnects to the RESTARTED server with
``Last-Event-ID``, and the replayed tail must splice byte-identically
onto what was delivered before the kill (greedy streams; same seed and
config on both boots, so the reference run is deterministic).

Also asserts: `/statusz` on the restarted server carries the journal
section with ``recovered_requests`` > 0, and `GET /v1/requests/<id>`
answers from the journal (``source: "journal"``) for streams the
restarted process never saw over HTTP.

Writes a JSON scorecard to --out (uploaded as a CI artifact along with
the journal file itself); exit 1 on any failed assertion.
"""

from __future__ import annotations

import argparse
import json
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request


def wait_healthy(port: int, proc, timeout_s: float = 420.0) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SystemExit(
                f"server exited early with rc {proc.returncode}"
            )
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=2
            ) as r:
                if r.status == 200:
                    return
        except (urllib.error.URLError, OSError):
            pass
        time.sleep(0.25)
    raise SystemExit("server never became healthy")


def start_server(port: int, journal: str, extra=()) -> subprocess.Popen:
    cmd = [
        sys.executable, "-m", "solvingpapers_tpu.cli", "serve",
        "--config", ARGS.config, "--port", str(port),
        "--journal", journal, "--slots", "2", "--decode-block", "4",
        "--max-len", "192", "--seed", "0", *extra,
    ]
    proc = subprocess.Popen(cmd)
    wait_healthy(port, proc)
    return proc


class SseClient(threading.Thread):
    """One SSE completion stream: collects text and the last event id;
    a dropped connection (the SIGKILL) is recorded, not raised."""

    def __init__(self, port: int, rid: str, prompt, max_tokens: int,
                 resume_from: str | None = None):
        super().__init__(daemon=True)
        self.port = port
        self.rid = rid
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.resume_from = resume_from
        self.text = ""
        self.last_id: str | None = None
        self.finish_reason: str | None = None
        self.done = False
        self.dropped = False

    def run(self) -> None:
        headers = {"Content-Type": "application/json"}
        if self.resume_from is not None:
            headers["Last-Event-ID"] = self.resume_from
            body = b"{}"
        else:
            headers["X-Request-Id"] = self.rid
            body = json.dumps({
                "prompt": self.prompt, "max_tokens": self.max_tokens,
                "stream": True, "temperature": 0,
            }).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}/v1/completions",
            data=body, headers=headers, method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=600) as r:
                cur = None
                for raw in r:
                    line = raw.decode().rstrip("\n")
                    if line.startswith("id: "):
                        cur = line[4:]
                    elif line.startswith("data: "):
                        payload = line[6:]
                        if payload == "[DONE]":
                            self.done = True
                            return
                        ev = json.loads(payload)
                        choice = (ev.get("choices") or [{}])[0]
                        self.text += choice.get("text", "")
                        if choice.get("finish_reason"):
                            self.finish_reason = choice["finish_reason"]
                        self.last_id = cur
        except (urllib.error.URLError, ConnectionError, OSError):
            self.dropped = True


def run_streams(port: int, rids, prompts, max_tokens: int,
                resume_ids=None) -> list[SseClient]:
    clients = [
        SseClient(port, rid, prompt, max_tokens,
                  resume_from=None if resume_ids is None
                  else resume_ids[i])
        for i, (rid, prompt) in enumerate(zip(rids, prompts))
    ]
    for c in clients:
        c.start()
    return clients


def main() -> int:
    failures: list[str] = []

    def check(ok: bool, msg: str) -> None:
        print(("ok  " if ok else "FAIL") + f" {msg}")
        if not ok:
            failures.append(msg)

    prompts = [[1 + i, 2, 3, 4, 5, 6, 7, 8] for i in range(ARGS.requests)]
    rids = [f"crash-{i}" for i in range(ARGS.requests)]

    # ---- reference: uninterrupted run, same config/seed
    ref_journal = ARGS.journal + ".ref"
    proc = start_server(ARGS.port, ref_journal)
    try:
        ref = run_streams(ARGS.port, rids, prompts, ARGS.max_new)
        for c in ref:
            c.join(timeout=600)
        check(all(c.done for c in ref), "reference streams completed")
        ref_text = [c.text for c in ref]
    finally:
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=60)

    # ---- crash run: SIGKILL once every stream has committed tokens
    proc = start_server(ARGS.port, ARGS.journal)
    clients = run_streams(ARGS.port, rids, prompts, ARGS.max_new)
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        offs = [int(c.last_id.rsplit(":", 1)[1]) if c.last_id else 0
                for c in clients]
        if all(4 <= o < ARGS.max_new for o in offs):
            break
        if any(c.done for c in clients):
            break  # model too fast — kill now, some streams finished
        time.sleep(0.02)
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=60)
    for c in clients:
        c.join(timeout=60)
    killed_mid = [c for c in clients if not c.done]
    check(len(killed_mid) > 0, "SIGKILL landed mid-stream for >= 1 stream")
    print(f"    killed with per-stream offsets "
          f"{[c.last_id for c in clients]}")

    # ---- restart on the same journal: recovery + client resume
    proc = start_server(ARGS.port, ARGS.journal)
    try:
        resumed = []
        for c in clients:
            if c.done:
                continue
            off = c.last_id or f"{c.rid}:0"
            r = SseClient(ARGS.port, c.rid, None, ARGS.max_new,
                          resume_from=off)
            r.pre_text = c.text
            resumed.append(r)
            r.start()
        for r in resumed:
            r.join(timeout=600)
        check(all(r.done for r in resumed),
              "resumed streams ran to [DONE]")
        exact = True
        for r in resumed:
            i = rids.index(r.rid)
            if r.pre_text + r.text != ref_text[i]:
                exact = False
                print(f"    {r.rid}: pre={r.pre_text!r} "
                      f"tail={r.text!r} want={ref_text[i]!r}")
        for c in clients:
            if c.done and c.text != ref_text[rids.index(c.rid)]:
                exact = False
        check(exact, "recovered completions byte-identical to the "
                     "uninterrupted reference")

        with urllib.request.urlopen(
            f"http://127.0.0.1:{ARGS.port}/statusz", timeout=10
        ) as r:
            statusz = json.loads(r.read())
        check("journal" in statusz, "/statusz carries the journal section")
        jsec = statusz.get("journal", {})
        check(jsec.get("recovered_requests", 0) >= len(resumed),
              f"statusz recovered_requests >= {len(resumed)}")
        check(jsec.get("degraded") is False, "journal not degraded")

        # journal fallback: the restarted process never saw these over
        # HTTP as ordinary registry entries
        probe = resumed[0].rid if resumed else rids[0]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{ARGS.port}/v1/requests/{probe}",
            timeout=10,
        ) as r:
            doc = json.loads(r.read())
        check(doc.get("source") == "journal",
              "GET /v1/requests/<id> answered from the journal")
        check(doc.get("state") == "finished"
              and len(doc.get("tokens", [])) == ARGS.max_new,
              "journal doc carries the full completion")
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()

    out = {
        "requests": ARGS.requests,
        "streams_killed_mid_decode": len(killed_mid),
        "streams_resumed": len(resumed),
        "recovered_token_exact": not failures
        or all("byte-identical" not in f for f in failures),
        "statusz_journal": jsec,
        "failures": failures,
    }
    with open(ARGS.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[smoke] wrote {ARGS.out}")
    return 1 if failures else 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="gpt_shakespeare")
    ap.add_argument("--port", type=int, default=8611)
    ap.add_argument("--journal", default="crash_smoke.jsonl")
    ap.add_argument("--out", default="crash_smoke.json")
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=48)
    ARGS = ap.parse_args()
    sys.exit(main())
