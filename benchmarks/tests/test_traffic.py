"""The traffic generator and the latency arithmetic."""

import math

import numpy as np
import pytest

from benchmarks import harness, trafficgen

CHAT = harness.load_json(harness.HERE, "traffic", "serve_chat.json")


def test_same_seed_same_requests_other_seed_other_order_same_work():
    a = trafficgen.make_requests(CHAT, 7, 50257, 200, True)
    b = trafficgen.make_requests(CHAT, 7, 50257, 200, True)
    c = trafficgen.make_requests(CHAT, 2**31 + 9, 50257, 200, True)
    assert all(x.due_s == y.due_s and x.max_new == y.max_new
               and np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in c]
    # the same multiset of lengths and gaps, so the same work and duration
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in c)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in c)
    assert a[-1].due_s == pytest.approx(c[-1].due_s)
    gaps = lambda r: sorted(np.diff([0.0] + [x.due_s for x in r]))  # noqa: E731
    assert gaps(a) == pytest.approx(gaps(c))


def test_lengths_respect_the_file():
    reqs = trafficgen.make_requests(CHAT, 1, 50257, 2000, True)
    lens = np.array([len(r.prompt) for r in reqs])
    outs = np.array([r.max_new for r in reqs])
    assert lens.min() >= 8 and lens.max() <= 160
    assert outs.min() >= 16 and outs.max() <= 96
    assert (lens + outs).max() <= CHAT["engine"]["max_len"]
    assert 40 <= np.median(lens) <= 56 and 58 <= np.median(outs) <= 70
    rate = len(reqs) / reqs[-1].due_s
    assert rate == pytest.approx(CHAT["rate_rps"], rel=0.1)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 50257 for r in reqs)


def test_gamma_arrivals_are_burstier_at_the_same_rate():
    rng = np.random.default_rng(0)
    spec = {"rate_rps": 10.0, "arrivals": "gamma", "cv": 3.0}
    g = trafficgen.draw_gaps(rng, spec, 20000)
    assert g.mean() == pytest.approx(0.1, rel=0.1)
    assert g.std() / g.mean() == pytest.approx(3.0, rel=0.15)


def test_percentile_arithmetic():
    assert trafficgen.percentile([1, 2, 3, 4, 5], 50) == 3
    assert trafficgen.percentile(range(101), 95) == 95
    assert trafficgen.percentile([0, 10], 95) == pytest.approx(9.5)
    assert math.isnan(trafficgen.percentile([], 95))
    # failures count as infinite and reach the tail once they pass 5%
    assert trafficgen.percentile([1.0] * 99 + [math.inf], 95) == 1.0
    assert trafficgen.percentile([1.0] * 90 + [math.inf] * 10, 95) == math.inf


def test_latency_counts_from_when_a_request_was_due():
    from benchmarks.drivers import serve_common as sc

    class H:
        finish_reason, admit_time, tokens = "length", 100.4, [1, 2, 3]

    req = trafficgen.Req(0.0, np.zeros(4, np.int32), 3)
    tr = sc.Track(req, due=0.2, submit=0.5, handle=H(), first_token=0.9,
                  deliveries=[(0.9, 1), (1.3, 3)], finish=1.3)
    run = harness.Run(workload="w", seed=0, seconds=2, trace=False,
                      t_start=0, bench={}, cell={}, config={}, traffic={},
                      device={}, peaks={})
    run.obs["window_s"] = 2.0
    sc.observe(run, None, [tr], 100.0, [2, 4], 4)
    o = run.obs
    assert o["ttft_s"] == [pytest.approx(0.7)]  # from due, not from submit
    assert o["late_s"] == [pytest.approx(0.3)]
    assert o["queue_wait_s"] == [pytest.approx(0.2)]
    assert o["tpot_s"] == [pytest.approx(0.2)]  # (1.3 - 0.9) / (3 - 1)
    assert o["decode_gap_max_s"] == [pytest.approx(0.4)]
    assert o["tokens_completed"] == 3 and o["occupancy"] == [0.5, 1.0]
    assert run.attempted == 1 and run.failed == 0
