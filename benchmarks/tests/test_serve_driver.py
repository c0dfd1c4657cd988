"""The serving drivers end to end at a tiny size on the CPU (the cells'
traffic files with short requests and four slots). The cells are not in
BENCHMARK.json (PERF.md section 7 says why); the drivers, their traffic files
and readers wait here for a configuration that fills the chip."""

import copy

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.tests import tiny


def _serve_run(monkeypatch, traffic_name, **kw):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    bench = copy.deepcopy(bench)
    name = "dsv3_tinystories." + traffic_name
    if not any(w["name"] == name for w in bench["workloads"]):
        bench["workloads"].append({"name": name, "config": "dsv3_tinystories",
                                   "traffic": traffic_name, "chips": 1})
    have = {m["name"] for m in bench["end_to_end"]}
    for m in ("ttft_p95_ms", "tpot_p95_ms", "serve_tokens_per_s"):
        if m not in have:
            bench["end_to_end"].append(
                {"name": m, "unit": "x", "workloads": [name]})
    monkeypatch.setattr(harness, "load_json", _patched(bench))
    monkeypatch.setattr(harness, "peak_bytes", lambda n: (1, 1))
    return tiny.tiny_run(name, seconds=1.5, **kw)


def _patched(bench):
    real = harness.load_json

    def load(*parts):
        return bench if parts[-1] == "BENCHMARK.json" else real(*parts)

    return load


@pytest.mark.parametrize("traffic", ["serve_chat", "serve_batch"])
def test_sound_serving_run(monkeypatch, traffic):
    run = _serve_run(monkeypatch, traffic)
    line = run.result()
    assert line["correct"] is True, run.checks
    assert line["attempted"] > 5 and line["failed"] == 0
    assert {"serve_tokens_per_s", "setup_s"} <= set(line["metrics"])
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert all(0 <= x <= 1 for x in run.obs["occupancy"])
    assert min(run.obs["late_s"]) >= 0


def test_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from solvingpapers_tpu.serve import scheduler

    real = list.extend

    class Altered(list):
        def extend(self, items):  # every served token moved by one
            real(self, [(int(t) + 1) % 256 for t in items])

        def append(self, t):
            list.append(self, (int(t) + 1) % 256)

    init = scheduler.Request.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        self.tokens = Altered()

    monkeypatch.setattr(scheduler.Request, "__init__", patched)
    run = _serve_run(monkeypatch, "serve_chat")
    assert run.result()["correct"] is False
