"""CI fleet-trace smoke: the trace fabric end to end on a live fleet.

An in-process 2-replica journaled + traced fleet behind the real HTTP
front door; a blocking request is caught LIVE mid-decode and its
replica drained so the stream migrates to the peer. Then every layer
of the fabric is asserted against the running system:

* `GET /v1/requests/<id>` returns the stitched trail — `fleet.migrated`
  true, both hops listed, and the phase walls (accept -> parse -> route
  -> queue_handoff -> queue/prefill/decode -> migrate -> peer_* ->
  sse_drain) PARTITION the client-observed e2e wall within 5%
  (the migration hop included — the invariant the trail exists for);
* `GET /timeseriesz` answers the rolling retrospective for BOTH
  replicas with at least one sampled window each (artifact);
* `FleetRouter.export_chrome_fleet` writes ONE valid Chrome trace:
  `fleet_manifest` declares router + both replicas, each is its own
  Perfetto process, and the migrated request's `fleet_flow` arrow
  spans >= 3 processes (router -> drained replica -> adopter);
* `cli trace-summary --fleet` exits 0 on the stitched file and 2 on a
  truncated copy (the operator-facing error contract).

Writes a JSON scorecard to --out (uploaded as a CI artifact along with
the stitched trace and the time-series dump); exit 1 on any failed
assertion.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request
import zlib

import jax
import jax.numpy as jnp


def build_fleet(jdir: str):
    from solvingpapers_tpu.models.gpt import GPT, GPTConfig
    from solvingpapers_tpu.serve.api import ApiServer
    from solvingpapers_tpu.serve.engine import ServeConfig, ServeEngine
    from solvingpapers_tpu.serve.fleet import FleetRouter

    model = GPT(GPTConfig(vocab_size=64, block_size=64, dim=32,
                          n_layers=2, n_heads=2, dropout=0.0))
    params = model.init({"params": jax.random.key(0)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    engines = [
        ServeEngine(model, params, ServeConfig(
            n_slots=2, max_len=48, decode_block=4, bucket=8,
            max_prefills_per_step=2, api_port=0, trace=True,
            # fast cadence so a seconds-long smoke still rolls windows
            timeseries_interval_s=0.05,
            journal_path=os.path.join(jdir, f"r{i}.jsonl")))
        for i in range(2)
    ]
    router = FleetRouter(engines)  # started loops: the real topology
    srv = ApiServer(
        router=router,
        decode=lambda ids: "".join(chr(97 + i % 26) for i in ids),
        model_name="gpt-tiny-fleet",
    )
    return srv, router


def _get_json(url: str, timeout: float = 30.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _live_tokens(rep, rid: str, max_new: int):
    e = rep.engine.journal.lookup(rid)
    if (e is None or e.finished or len(e.tokens) >= max_new
            or not rep.engine.journal.is_live(rid)):
        return None
    return len(e.tokens)


def drain_while_live(router, rid, max_new, thread, deadline_s=120.0):
    """Catch `rid` live mid-decode and drain its replica UNDER the held
    step lock (same discipline as tests/test_fleet.py) — the stream is
    deterministically live at the drain. ``(None, None)`` when it
    finished before the drain could land (caller retries)."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        owner = router.owner(rid)
        if owner is not None:
            with owner.loop.lock:
                if _live_tokens(owner, rid, max_new) is not None:
                    return owner, router.drain(owner.rid)
            if not thread.is_alive():
                return None, None
        time.sleep(0.001)
    raise SystemExit(f"{rid} never observed live mid-decode")


def main() -> int:
    from solvingpapers_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace-out", default="fleet_trace.json")
    ap.add_argument("--timeseries-out", default="fleet_timeseries.json")
    ap.add_argument("--out", default="fleet_trace_smoke.json")
    ap.add_argument("--max-new", type=int, default=40)
    args = ap.parse_args()

    jdir = tempfile.mkdtemp(prefix="fleet_trace_smoke_")
    srv, router = build_fleet(jdir)
    failures: list[str] = []

    def check(ok, msg: str) -> None:
        print(("ok   " if ok else "FAIL ") + msg)
        if not ok:
            failures.append(msg)

    # warm traffic: jit both paths, roll time-series windows, give the
    # router routing decisions on both replicas
    for i in range(4):
        body = json.dumps({"prompt": [1 + i, 2, 3, 4], "max_tokens": 4,
                           "temperature": 0}).encode()
        req = urllib.request.Request(
            srv.url("/v1/completions"), data=body,
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=120) as r:
            r.read()

    # ---- mid-decode drain around a live blocking request
    prompt = [2, 7, 1, 8, 2, 8]
    owner = report = None
    rid = ""
    out: dict = {}
    for attempt in range(8):
        rid = f"smoke-mig-{attempt}"
        out = {}

        def client(rid=rid, out=out):
            req = urllib.request.Request(
                srv.url("/v1/completions"),
                data=json.dumps({"prompt": prompt, "temperature": 0,
                                 "max_tokens": args.max_new}).encode(),
                headers={"Content-Type": "application/json",
                         "X-Request-Id": rid}, method="POST")
            t0 = time.monotonic()
            with urllib.request.urlopen(req, timeout=300) as r:
                out["replica"] = r.headers.get("X-Replica-Id")
                out["doc"] = json.loads(r.read())
            out["wall_s"] = time.monotonic() - t0

        t = threading.Thread(target=client)
        t.start()
        owner, report = drain_while_live(router, rid, args.max_new, t)
        t.join(timeout=300)
        if owner is not None:
            break
    check(owner is not None, "drain landed while the stream was live")
    if owner is None:
        srv.close()
        return 1
    check(rid in report.targets, "drained stream adopted by a peer")
    peer, _ = report.targets[rid]
    check(out.get("replica") == peer,
          "blocking response came back from the ADOPTER")
    check(out["doc"]["choices"][0]["finish_reason"] == "length",
          "migrated stream ran to its token budget")

    # ---- the trail: GET /v1/requests/<id> partitions the client wall
    trail = _get_json(srv.url(f"/v1/requests/{rid}"))
    fleet = trail.get("fleet") or {}
    check(fleet.get("migrated") is True, "trail marks the migration")
    check(len(fleet.get("hops") or []) >= 2,
          "trail lists both hops (drained replica + adopter)")
    phases = trail.get("phases") or {}
    check("migrate" in phases and "peer_decode" in phases,
          "trail carries migrate + peer_* phases")
    psum = trail["phase_sum_s"]
    e2e = trail["e2e_s"]
    server_err = abs(psum - e2e)
    check(server_err <= max(0.05 * e2e, 1e-3),
          f"phases partition the server e2e wall "
          f"(sum {psum:.4f}s vs {e2e:.4f}s)")
    wall = out["wall_s"]
    # 5% of the client-observed wall, with a small absolute floor for
    # loopback connect/teardown jitter at smoke scale
    client_err = abs(psum - wall)
    check(client_err <= max(0.05 * wall, 0.02),
          f"phases partition the CLIENT-observed e2e wall within 5% "
          f"(sum {psum:.4f}s vs client {wall:.4f}s)")
    router.undrain(owner.rid)

    # ---- the rolling retrospective
    ts = _get_json(srv.url("/timeseriesz"))
    reps = ts.get("replicas") or {}
    check(sorted(reps) == ["r0", "r1"],
          "/timeseriesz answers for both replicas")
    check(all(d.get("n", 0) >= 1 for d in reps.values()),
          "both replicas sampled at least one window")
    with open(args.timeseries_out, "w") as f:
        json.dump(ts, f)

    # ---- the stitched Perfetto export
    router.export_chrome_fleet(args.trace_out)
    with open(args.trace_out) as f:
        doc = json.load(f)  # must be VALID JSON end to end
    events = doc["traceEvents"]
    manifest = next(e for e in events if e.get("name") == "fleet_manifest")
    check(manifest["args"]["sections"] == ["router", "r0", "r1"],
          "fleet_manifest declares router + both replicas")
    pnames = {e["pid"]: e["args"]["name"] for e in events
              if e.get("ph") == "M" and e.get("name") == "process_name"}
    check(sorted(pnames.values()) == ["r0", "r1", "router"],
          "each section is its own Perfetto process")
    fid = zlib.crc32(rid.encode())
    flow_pids = {e["pid"] for e in events
                 if e.get("cat") == "fleet_flow" and e.get("id") == fid}
    check(len(flow_pids) >= 3,
          f"migrated request's flow spans router + both replicas "
          f"({len(flow_pids)} processes)")
    migrates = [e for e in events if e.get("cat") == "fleet"
                and e.get("name") == "migrate"
                and (e.get("args") or {}).get("rid") == rid]
    check(bool(migrates), "router stamped the migrate span for the rid")

    # ---- the operator summary + its error contract
    from solvingpapers_tpu.cli import main as cli_main

    rc = cli_main(["trace-summary", args.trace_out, "--fleet"])
    check(rc == 0, "cli trace-summary --fleet summarizes the export")
    trunc = args.trace_out + ".trunc"
    with open(args.trace_out) as f:
        raw = f.read()
    with open(trunc, "w") as f:
        f.write(raw[: len(raw) // 2])
    rc = cli_main(["trace-summary", trunc, "--fleet"])
    check(rc == 2, "truncated export refused with exit 2")
    os.unlink(trunc)

    srv.close()
    scorecard = {
        "ok": not failures,
        "failures": failures,
        "rid": rid,
        "phases": phases,
        "phase_sum_s": psum,
        "server_e2e_s": e2e,
        "client_e2e_s": wall,
        "client_partition_err_s": round(client_err, 6),
        "flow_processes": len(flow_pids),
        "trace_out": args.trace_out,
        "timeseries_out": args.timeseries_out,
    }
    with open(args.out, "w") as f:
        json.dump(scorecard, f, indent=2)
    print(("fleet-trace smoke OK" if not failures
           else f"fleet-trace smoke FAILED ({len(failures)})"))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
