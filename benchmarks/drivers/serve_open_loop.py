"""Driver `serve_open_loop`: requests sent on a schedule fixed by the traffic
file (`rate_rps`, Poisson or gamma gaps), whether or not earlier ones have
finished: independent users. One thread submits what is due and then calls
`eng.step()`, as `serve/bench.py`'s engine arm does. A request's latency
counts from when it was due, not from when it was sent. After `--seconds`
nothing more is sent; what is in flight is drained outside the window so
that every request sent has its times.

Traffic file: `rate_rps`, `arrivals`, (`cv`), `mix_seed`, `prompt_len`,
`output_len`, `engine`, `check_requests`, `trace_seconds`.
"""

from __future__ import annotations

import gc
import time

from benchmarks import harness, trafficgen
from benchmarks.drivers import serve_common as sc


def run(run: harness.Run) -> None:
    traffic, seconds = run.traffic, run.window_seconds
    eng, sizes = sc.start(run)
    n = max(1, round(traffic["rate_rps"] * seconds))
    reqs = trafficgen.make_requests(traffic, run.seed, sizes.vocab, n, True)
    tracks = [sc.Track(r, r.due_s) for r in reqs if r.due_s < seconds]
    live, occupancy, i = {}, [], 0
    with run.window():
        t0 = sc.now()
        while (t := sc.now() - t0) < seconds:
            while i < len(tracks) and tracks[i].due <= t:
                sc.submit(eng, tracks[i], t0, live)
                i += 1
            if eng.has_work():
                sc.step_and_record(eng, live, t0, occupancy)
            else:  # idle until the next request is due
                nxt = tracks[i].due if i < len(tracks) else seconds
                time.sleep(max(0.0, min(nxt, seconds) - (sc.now() - t0)))
    sc.finish(run, eng, tracks, t0, occupancy, live, sizes)
    del eng
    gc.collect()
    sc.check_served(run, tracks, sizes)
    run.phase("reference")
