"""The reduction from a profiler trace to busy time, idle share, per-name
sums and attributed gaps: first on hand-made events, then on one small
trace recorded on the v5e (`data/small_v5e.xplane.pb`, made by
`record_small_trace.py`: five runs of one jitted program, a 20 ms sleep
under a `data_wait` annotation before each but the first)."""

import os

import pytest

from benchmarks.trace import xplane
from benchmarks.trace.xplane import Event

SMALL = os.path.join(os.path.dirname(__file__), "data", "small_v5e.xplane.pb")


def test_union_merges_overlaps_and_drops_empty():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (3, 3), (6, 9)]) == [
        (0, 3), (5, 9)]


def test_clip_and_gaps():
    busy = [(0, 3), (5, 9)]
    assert xplane.clip(busy, 1, 6) == [(1, 3), (5, 6)]
    assert xplane.gaps_of(xplane.clip(busy, 1, 12), 1, 12) == [
        (3, 5), (9, 12)]
    assert xplane.gaps_of([], 2, 4) == [(2, 4)]


def test_gap_goes_to_the_shortest_covering_span():
    spans = [Event("outer", 0, 100), Event("inner", 40, 60),
             Event("elsewhere", 70, 80)]
    assert xplane.attribute_gap((45, 55), spans, "none") == "inner"
    assert xplane.attribute_gap((10, 20), spans, "none") == "outer"
    assert xplane.attribute_gap((150, 160), spans, "none") == "none"


def test_reduce_hand_made_planes(monkeypatch):
    planes = {
        "/host:CPU": {"main": [Event("bench_window", 100, 1100),
                               Event("data_wait", 400, 700)]},
        "/device:TPU:0": {
            "XLA Modules": [Event("jit_f(1)", 100, 400),
                            Event("jit_f(1)", 700, 1000),
                            Event("jit_g(2)", 50, 90)],
            "XLA Ops": [Event("%a = f32[] add()", 100, 300),
                        Event("%b = f32[] mul()", 250, 400),
                        Event("%a = f32[] add()", 700, 1000)],
            "Async XLA Ops": [Event("%copy-start = ...", 100, 1100)],
        },
    }
    monkeypatch.setattr(xplane, "load_planes", lambda path: planes)
    r = xplane.reduce_trace("x", window_span="bench_window",
                            host_spans=("data_wait",), fallback="host")
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(600e-9)  # async copies are not busy time
    assert r.idle_share == pytest.approx(0.4)
    assert r.modules == {"jit_f(1)": [pytest.approx(300e-9)] * 2}
    assert sum(r.ops["%a = f32[] add()"]) == pytest.approx(500e-9)
    assert r.gaps == [("data_wait", pytest.approx(300e-9)),
                      ("host", pytest.approx(100e-9))]



@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_recorded_v5e_trace():
    r = xplane.reduce_trace(SMALL, window_span="bench_window",
                            host_spans=("data_wait",), fallback="host")
    assert r.n_devices == 1
    runs = [d for name, ds in r.modules.items()
            if name.startswith("jit_small_step(") for d in ds]
    # the device's clock runs about 1 ms ahead of the host's in this trace
    # (device start 0.98 ms before the host's dispatch, every time), so the
    # first of the five executions falls before the host's window
    assert len(runs) == 4
    # busy time is the ops' union: no more than the programs' time, and
    # most of it (a program is its ops back to back)
    assert 0.8 * sum(runs) <= r.busy_s <= sum(runs) * 1.001
    assert 0.0 < r.busy_s < r.window_s
    assert r.idle_share == pytest.approx(1 - r.busy_s / r.window_s)
    # four sleeps of 20 ms under `data_wait` left the device idle
    gaps = dict(r.gaps)
    assert 0.075 <= gaps["data_wait"] <= 0.12
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s,
                                               rel=1e-6)
    assert sum(sum(d) for d in r.ops.values()) >= r.busy_s * 0.999
