"""The readers of PR 37's eleven metrics: each on a hand-made recorder,
rows or trace gives the hand-computed number, returns None against a
program that has none of these, and stands in BENCHMARK.json with its five
cells; `trace/host.py` also on the small trace recorded on the v5e."""

import json
import os

import pytest

from benchmarks import harness
from benchmarks.trace import host, startup, xplane
from benchmarks.trace.xplane import Event, Reduced
from solvingpapers_tpu.metrics import trace
from solvingpapers_tpu.metrics.trace import FlightRecorder

SMALL = os.path.join(os.path.dirname(__file__), "data", "small_v5e.xplane.pb")
CELLS = ["dsv3_long.train_16k", "dsv3_tinystories.train_64x256",
         "qwen3next_ep16.train_16k", "kimi_linear_ep32.train_16k",
         "nemotron3_nano_ep16.train_16k"]
# name: (unit, better, source, layer, moves, the hand-computed reading)
METRICS = {
    "startup_import_s": ("s", "lower", "program_span", "start-up",
                         "setup_s", 12.0),
    "startup_build_s": ("s", "lower", "program_span", "start-up", "setup_s",
                        (3.0 - 0.5) + 0.25 + 1.0 + 2.0),
    "startup_init_state_s": ("s", "lower", "program_span", "start-up",
                             "setup_s", 4.0 - 1.0 - 0.5 - 1.5),
    "startup_trace_lower_s": ("s", "lower", "program_span", "start-up",
                              "setup_s", 1.0 + 0.5 + 6.0 + 2.0),
    "startup_compile_s": ("s", "lower", "program_span", "start-up",
                          "setup_s", 0.5 + 1.5 + 3.0 + 0.75),
    "startup_first_step_s": ("s", "lower", "program_span", "start-up",
                             "setup_s", 12.0 - 6.0 - 2.0 - 3.0),
    "startup_program_s": ("s", "lower", "program_span", "start-up",
                          "setup_s", 12 + 3 + 0.25 + 1 + 2 + 4 + 12 + 0.75),
    "compile_cache_miss_count": ("count", "lower", "program_counter",
                                 "start-up", "setup_s", 2.0),
    "train_dispatch_max_ms": ("ms", "lower", "program_counter",
                              "train loop (host)", "train_tokens_per_s",
                              41.5),
    "host_gap_max_ms": ("ms", "lower", "program_counter",
                        "train loop (host)", "train_tokens_per_s", 7.25),
    "idle_named_pct": ("%", "higher", "device_trace", "train loop (host)",
                       "train_tokens_per_s", 100.0 * 400 / 550),
}
PARTS = ("startup_import_s", "startup_build_s", "startup_init_state_s",
         "startup_trace_lower_s", "startup_compile_s",
         "startup_first_step_s")


def hand_made_recorder() -> FlightRecorder:
    rec = FlightRecorder(capacity=256)

    def span(name, ts, dur, cat="startup", **args):
        rec.complete(name, cat, "startup", ts=ts, dur=dur, **args)

    span("import:solvingpapers_tpu", 0.0, 0.0)
    span("import:configs", 0.0, 12.0)
    span("import:train", 2.0, 8.0)
    span("create_mesh", 12.5, 0.25)
    span("build_run", 13.0, 3.0)
    span("data_open", 13.0, 1.0)
    span("model_build", 14.5, 1.0)
    span("compile:jit(crop)", 13.25, 0.5, cat="jax")
    span("trainer_init", 16.0, 1.0)
    span("build_steps", 17.0, 2.0)
    span("init_state", 20.0, 4.0)
    span("init_eval_shape", 20.0, 1.0)
    span("init_jit", 21.0, 3.0)
    span("trace:make", 21.0, 1.0, cat="jax")
    span("lower:jit(make)", 22.0, 0.5, cat="jax")
    span("compile:jit(make)", 22.5, 1.5, cat="jax")
    span("fit_first_step", 30.0, 12.0, fit=1, step=1)
    span("trace:train_step", 30.0, 6.0, cat="jax")
    span("lower:jit(train_step)", 36.0, 2.0, cat="jax")
    span("compile:jit(train_step)", 38.0, 3.0, cat="jax")
    rec.counter("compile_cache", "jax", "startup", ts=41.0, hits=1,
                misses=2, retrieval_s=0.5)
    span("fit_first_step", 50.0, 0.5, fit=2, step=2)
    span("compile:jit(delta)", 55.0, 0.75, cat="jax")  # the driver's own
    span("fit_first_step", 60.0, 0.5, fit=3, step=4)
    # the window's own call, and what follows the window
    span("fit_first_step", 70.0, 0.5, fit=4, step=9)
    span("compile:jit(reference)", 100.0, 9.0, cat="jax")
    rec.counter("compile_cache", "jax", "startup", ts=109.0, hits=1,
                misses=3, retrieval_s=0.5)
    return rec


def hand_made_planes():
    ms = 1e6  # ns
    return {
        "/host:CPU": {"main": [
            Event("bench_window", 0, 1000 * ms),
            Event("train", 50 * ms, 400 * ms),
            Event("data_wait", 100 * ms, 300 * ms),
            Event("train_dispatch", 300 * ms, 400 * ms),
            Event("log_fetch", 400 * ms, 800 * ms),
            Event("log_write", 800 * ms, 900 * ms),
            Event("init_state", 0, 1000 * ms),  # no span of the loop's
        ]},
        "/device:TPU:0": {"XLA Ops": [
            Event("%a = f32[] add()", 350 * ms, 700 * ms),
            Event("%b = f32[] add()", 850 * ms, 950 * ms),
        ]},
    }


@pytest.fixture
def obs(monkeypatch, tmp_path):
    monkeypatch.setattr(trace, "RUN", hand_made_recorder())
    monkeypatch.setattr(xplane, "load_planes", lambda path: hand_made_planes())
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path))
    run_dir = tmp_path / "trace" / "some.cell" / "plugins" / "profile" / "t0"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(b"")
    tr = Reduced(window_s=1.0, busy_s=0.45, n_devices=1, modules={}, ops={},
                 gaps=[])
    return {"trace": tr, "rows": [
        {"step": 10, "dispatch_max_ms": 3.5, "dispatch_max_step": 9,
         "host_gap_max_ms": 7.25, "host_gap_max_step": 8},
        {"step": 20, "dispatch_max_ms": 41.5, "dispatch_max_step": 17,
         "host_gap_max_ms": 0.5, "host_gap_max_step": 12},
        {"step": 21, "train_loss": 1.0},
    ]}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_gives_the_hand_computed_number(name, obs):
    got = harness.load_module("metrics", name).read(obs)
    assert got == pytest.approx(METRICS[name][-1])


def test_the_parts_add_up_to_what_the_program_accounts_for(obs):
    read = {n: harness.load_module("metrics", n).read(obs) for n in METRICS}
    assert sum(read[n] for n in PARTS) == pytest.approx(
        read["startup_program_s"])


def test_the_window_begins_at_the_last_fit_calls_first_step():
    events = hand_made_recorder().events()
    assert startup.window_start(events) == 70.0
    assert startup.window_start(
        [e for e in events if e.name != "fit_first_step"]) is None


def test_idle_is_shared_out_by_overlap_and_a_frame_is_no_name(obs, capsys):
    assert host.read(obs) == pytest.approx(METRICS["idle_named_pct"][-1])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # gaps: 0-350 (50 under nothing, 50 under the step's annotation alone,
    # 200 of data_wait, 50 of train_dispatch), 700-850 (100 of log_fetch,
    # 50 of log_write), 950-1000 (under no span of the loop's)
    assert line == {"idle_by_program_span": {
        "unnamed": pytest.approx(0.1), "train": pytest.approx(0.05),
        "data_wait": pytest.approx(0.2),
        "train_dispatch": pytest.approx(0.05),
        "log_fetch": pytest.approx(0.1), "log_write": pytest.approx(0.05)},
        "longest_gaps": [
        [350.0, 0.0, {"unnamed": 50.0, "train": 50.0, "data_wait": 200.0,
                      "train_dispatch": 50.0}],
        [150.0, 700.0, {"log_fetch": 100.0, "log_write": 50.0}],
        [50.0, 950.0, {"unnamed": 50.0}]]}
    assert host.read(obs) is not None  # kept on obs: no second line
    assert capsys.readouterr().out == ""


def test_pieces_give_each_stretch_to_the_shortest_span_over_it():
    spans = [Event("train", 10, 90), Event("data_wait", 20, 40),
             Event("data_wait", 21, 39),  # the driver's wrapper around it
             Event("train_dispatch", 40, 80), Event("fit_setup", 0, 10)]
    assert host.pieces(spans, 5, 100) == [
        (5, 10, "fit_setup"), (10, 20, "train"), (20, 21, "data_wait"),
        (21, 39, "data_wait"), (39, 40, "data_wait"),
        (40, 80, "train_dispatch"), (80, 90, "train"), (90, 100, "unnamed")]
    assert host.named_pct({"train": 3.0, "fit_first_step": 1.0,
                           "log_write": 1.0}) == pytest.approx(20.0)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_finds_nothing_in_a_program_without_the_recorder(
        name, obs, monkeypatch):
    """The parent commit: no `RUN`, no `summarize_startup`, rows without
    the maxima, a trace without the loop's annotations."""
    monkeypatch.delattr(trace, "RUN")
    monkeypatch.delattr(trace, "summarize_startup")
    planes = hand_made_planes()
    planes["/host:CPU"]["main"] = [
        e for e in planes["/host:CPU"]["main"]
        if e.name in ("bench_window", "data_wait")]  # the driver's wrapper
    monkeypatch.setattr(xplane, "load_planes", lambda path: planes)
    obs["rows"] = [{"step": 10, "data_wait_ms": 0.1}]
    assert harness.load_module("metrics", name).read(obs) is None


def test_startup_readers_find_nothing_before_any_fit(obs, monkeypatch):
    rec = FlightRecorder(capacity=8)
    rec.complete("import:configs", "startup", "startup", ts=0.0, dur=1.0)
    monkeypatch.setattr(trace, "RUN", rec)
    assert startup.summary({}) is None
    assert harness.load_module("metrics", "idle_named_pct").read({}) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_stands_in_benchmark_json_with_its_five_cells(name):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    unit, better, source, layer, moves, _ = METRICS[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": CELLS}
    assert [m["name"] for m in bench["per_layer"][-11:]] == list(METRICS)
    assert any(m["name"] == moves for m in bench["end_to_end"])


def test_host_reader_on_the_recorded_trace():
    """Five runs of one program, a 20 ms sleep under `data_wait` before
    each but the first: the sleeps are the device's idle time."""
    gaps = host.idle_gaps(SMALL, names=("data_wait",), needs="data_wait")
    by_span = host.by_span(gaps)
    assert sorted(at for at, _, _ in gaps) == [at for at, _, _ in gaps]
    assert all(sum(shares.values()) == pytest.approx(ns)
               for _, ns, shares in gaps)
    assert set(by_span) <= {"data_wait", "unnamed"}
    assert 0.075 < by_span["data_wait"] < 0.1
    assert host.named_pct(by_span) > 90.0
    tr = xplane.reduce_trace(SMALL, window_span="bench_window",
                             host_spans=("data_wait",))
    assert sum(by_span.values()) == pytest.approx(
        tr.window_s - tr.busy_s, rel=1e-6)
    # the loop's own annotations are not in it: nothing to read
    assert host.idle_gaps(SMALL) is None and host.by_span(None) is None
    assert host.named_pct(None) is None and host.named_pct({}) is None
