"""Driver `serve_closed_loop`: `callers` callers, each sending its next
request when its last one completes: an offline batch or an evaluation job.
A slow system receives less load, so the number judged is tokens completed
per second. One thread drives `eng.step()`.

Traffic file: `callers`, `requests_per_caller`, `mix_seed`, `prompt_len`,
`output_len`, `engine`, `check_requests`, `trace_seconds`.
"""

from __future__ import annotations

import gc

from benchmarks import harness, trafficgen
from benchmarks.drivers import serve_common as sc


def run(run: harness.Run) -> None:
    traffic, seconds = run.traffic, run.window_seconds
    eng, sizes = sc.start(run)
    callers = traffic["callers"]
    reqs = trafficgen.make_requests(
        traffic, run.seed, sizes.vocab,
        callers * traffic["requests_per_caller"], False)
    queues = [reqs[c::callers] for c in range(callers)]
    tracks, current, sent = [], [None] * callers, [0] * callers
    live, occupancy = {}, []
    with run.window():
        t0 = sc.now()
        while sc.now() - t0 < seconds:
            for c in range(callers):
                tr = current[c]
                if tr is None or tr.finish is not None:
                    # a caller that has sent its whole list starts it again
                    req = queues[c][sent[c] % len(queues[c])]
                    sent[c] += 1
                    tr = current[c] = sc.Track(req, sc.now() - t0)
                    sc.submit(eng, tr, t0, live)
                    tracks.append(tr)
            sc.step_and_record(eng, live, t0, occupancy)
    sc.finish(run, eng, tracks, t0, occupancy, live, sizes)
    del eng
    gc.collect()
    sc.check_served(run, tracks, sizes)
    run.phase("reference")
