"""Start-up as a layer: the run's recorder (`metrics.trace.RUN`), the spans
start-up writes to it, JAX's compile events beside them
(`metrics.xla_obs.CompileSpans`), `summarize_startup`, and the train loop's
always-on host annotations and per-row maxima."""

import glob
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from solvingpapers_tpu.configs import get_config
from solvingpapers_tpu.configs.factory import (
    build_char_lm_run, init_fn_for, loss_fn_for, rules_for,
)
from solvingpapers_tpu.metrics import trace
from solvingpapers_tpu.metrics.trace import TraceEvent, summarize_startup
from solvingpapers_tpu.metrics.xla_obs import compile_spans
from solvingpapers_tpu.models.gpt import GPT, GPTConfig
from solvingpapers_tpu.train import TrainConfig, Trainer

pytestmark = pytest.mark.fast

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STARTUP_SPANS = (
    "build_run", "data_open", "model_build", "create_mesh", "trainer_init",
    "build_steps", "init_state", "init_eval_shape", "init_jit",
    "fit_first_step",
)
PARENT_OF = {
    "data_open": "build_run", "model_build": "build_run",
    "create_mesh": "trainer_init", "init_eval_shape": "init_state",
    "init_jit": "init_state",
}


class Rows:
    def __init__(self):
        self.rows = []

    def write(self, step, row):
        self.rows.append({"step": int(step), **row})

    def close(self):
        pass


def X(name, ts, dur, **args):
    return TraceEvent(name, "startup", "startup", "X", ts, dur=dur,
                      args=args or None)


# ----------------------------------------------------------- pure arithmetic


def test_summarize_startup_gives_each_second_to_the_innermost_span():
    events = [
        X("import:configs", 0.0, 10.0),
        X("import:train", 1.0, 6.0),  # inside configs: the same part
        X("build_run", 10.0, 4.0),
        X("data_open", 10.5, 1.0),
        X("model_build", 12.0, 1.5),
        X("compile:jit_crop", 10.6, 0.4),  # inside data_open
        X("init_state", 20.0, 5.0),
        X("init_eval_shape", 20.0, 1.0),
        X("init_jit", 21.0, 4.0),
        X("trace:make", 21.0, 0.5),
        X("lower:jit_make", 21.5, 0.25),
        X("compile:jit_make", 21.75, 2.0),
        X("fit_first_step", 30.0, 8.0, fit=1),
        X("trace:train_step", 30.0, 3.0),
        X("compile:jit_train_step", 33.0, 2.0),
        X("fit_first_step", 50.0, 1.0, fit=2),  # a later call: not start-up
        X("compile:jit_delta", 60.0, 1.0),  # outside every span: still compile
        TraceEvent("compile_cache", "jax", "startup", "C", 34.0,
                   args={"hits": 3, "misses": 1, "retrieval_s": 0.75}),
    ]
    s = summarize_startup(events)
    assert s["import_s"] == pytest.approx(10.0)
    assert s["build_s"] == pytest.approx(4.0 - 0.4)
    assert s["init_state_s"] == pytest.approx(5.0 - 0.5 - 0.25 - 2.0)
    assert s["trace_s"] == pytest.approx(3.5)
    assert s["lower_s"] == pytest.approx(0.25)
    assert s["compile_s"] == pytest.approx(0.4 + 2.0 + 2.0 + 1.0)
    assert s["first_step_s"] == pytest.approx(8.0 - 3.0 - 2.0)
    # the parts partition the union of the spans
    assert s["program_s"] == pytest.approx(10 + 4 + 5 + 8 + 1)
    assert s["program_s"] == pytest.approx(sum(
        s[k] for k in trace.STARTUP_PARTS))
    assert (s["cache_hits"], s["cache_misses"]) == (3, 1)
    assert s["cache_retrieval_s"] == 0.75


def test_summarize_startup_counts_only_what_ended_by_until():
    events = [
        X("import:configs", 0.0, 2.0),
        X("compile:jit_a", 3.0, 1.0),
        X("compile:jit_b", 4.5, 1.0),  # ends at 5.5, after `until`
        TraceEvent("compile_cache", "jax", "startup", "C", 4.0,
                   args={"hits": 1, "misses": 0, "retrieval_s": 0.1}),
        TraceEvent("compile_cache", "jax", "startup", "C", 5.5,
                   args={"hits": 1, "misses": 1, "retrieval_s": 0.1}),
    ]
    s = summarize_startup(events, until=5.0)
    assert s["compile_s"] == pytest.approx(1.0)
    assert s["program_s"] == pytest.approx(3.0)
    assert (s["cache_hits"], s["cache_misses"]) == (1, 0)
    assert summarize_startup([])["program_s"] == 0.0


def test_overlapping_spans_of_two_threads_are_counted_once():
    events = [X("build_run", 0.0, 4.0), X("compile:jit_a", 3.0, 3.0),
              X("trace:f", 5.0, 2.0)]
    s = summarize_startup(events)
    assert s["program_s"] == pytest.approx(7.0)
    assert s["build_s"] == pytest.approx(3.0)
    assert s["compile_s"] == pytest.approx(2.0)
    assert s["trace_s"] == pytest.approx(2.0)


def test_begin_names_the_parent_and_survives_a_span_left_open():
    t0 = trace.RUN.clock()
    outer = trace.begin("t_outer")
    trace.begin("t_left_open")  # an exception skipped its end
    assert trace.current_span() == "t_left_open"
    outer()
    assert trace.current_span() is None
    with trace.run_span("t_a", k=1):
        with trace.run_span("t_b"):
            assert trace.current_span() == "t_b"
    evs = {e.name: e for e in trace.RUN.events()
           if e.ts >= t0 and e.name.startswith("t_")}
    assert set(evs) == {"t_outer", "t_a", "t_b"}
    assert evs["t_b"].args["parent"] == "t_a"
    assert evs["t_a"].args == {"parent": None, "k": 1}
    assert evs["t_a"].ts <= evs["t_b"].ts
    assert evs["t_b"].ts + evs["t_b"].dur <= evs["t_a"].ts + evs["t_a"].dur


# -------------------------------------------------- a process of its own


def run_python(code: str) -> dict:
    """The last line of a child's standard output, as JSON. The child is
    held to the CPU and leaves the persistent compile cache alone."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_recorder_is_there_before_jax_or_numpy():
    got = run_python("""
        import json, sys
        import solvingpapers_tpu
        from solvingpapers_tpu.metrics import trace
        print(json.dumps({
            "heavy": sorted(m for m in ("jax", "numpy", "flax", "optax")
                            if m in sys.modules),
            "names": [e.name for e in trace.RUN.events()]}))
    """)
    assert got["heavy"] == []
    assert got["names"] == ["import:solvingpapers_tpu"]


@pytest.fixture(scope="module")
def first_process():
    """Events and rows of a process that imports the package, builds
    `gpt_tiny` as `cli train` does and fits three steps."""
    return run_python("""
        import json
        import jax
        jax.config.update("jax_enable_compilation_cache", False)
        import dataclasses
        from solvingpapers_tpu.configs import get_config
        from solvingpapers_tpu.configs.factory import (
            build_char_lm_run, init_fn_for, loss_fn_for, rules_for)
        from solvingpapers_tpu.metrics import trace
        from solvingpapers_tpu.train import Trainer

        class Rows:
            rows = []
            def write(self, step, row): self.rows.append({"step": step, **row})
            def close(self): pass

        cfg = get_config("gpt_tiny")
        cfg = dataclasses.replace(cfg, train=dataclasses.replace(
            cfg.train, steps=3, log_every=1, eval_every=0))
        cfg, model, _, it, _ = build_char_lm_run(cfg)
        tr = Trainer(model, cfg.train, loss_fn=loss_fn_for(cfg),
                     init_fn=init_fn_for(cfg), rules=rules_for(cfg))
        state = tr.fit(it, None, writer=Rows())
        tr.config = dataclasses.replace(cfg.train, steps=5)
        tr.fit(it, None, writer=Rows(), state=state)
        print(json.dumps({"rows": Rows.rows, "events": [
            e.to_dict() for e in trace.RUN.events()]}))
    """)


def test_import_spans_nest_as_the_imports_do(first_process):
    spans = {e["name"]: e for e in first_process["events"]
             if e["name"].startswith("import:")}
    assert {"import:solvingpapers_tpu", "import:configs", "import:train",
            "import:checkpoint", "import:sharding", "import:ops",
            "import:data", "import:models"} <= set(spans)

    def inside(child, parent):
        c, p = spans[child], spans[parent]
        return p["ts"] <= c["ts"] and c["ts"] + c["dur"] <= p["ts"] + p["dur"]

    assert spans["import:train"]["args"]["parent"] == "import:configs"
    assert spans["import:checkpoint"]["args"]["parent"] == "import:train"
    assert inside("import:train", "import:configs")
    assert inside("import:checkpoint", "import:train")
    assert spans["import:configs"]["args"]["parent"] is None


def test_first_fit_of_a_process_writes_one_startup_row(first_process):
    rows = [r for r in first_process["rows"]
            if any(k.startswith("startup/") for k in r)]
    assert len(rows) == 1 and rows[0]["step"] == 1
    row = rows[0]
    assert set(row) - {"step"} == {
        "startup/" + k for k in (
            "import_s", "build_s", "init_state_s", "trace_s", "lower_s",
            "compile_s", "first_step_s", "program_s", "cache_hits",
            "cache_misses", "cache_retrieval_s")}
    parts = sum(row["startup/" + k] for k in trace.STARTUP_PARTS)
    assert row["startup/program_s"] == pytest.approx(parts)
    assert row["startup/import_s"] > 0 and row["startup/compile_s"] > 0
    # the row is what the reader's function gives on the same events
    evs = [TraceEvent(e["name"], e["cat"], e["track"], e["ph"], e["ts"],
                      dur=e["dur"], args=e.get("args"))
           for e in first_process["events"]]
    firsts = [e for e in evs if e.name == "fit_first_step"]
    assert [e.args["fit"] for e in firsts] == [1, 2]
    until = firsts[0].ts + firsts[0].dur
    again = summarize_startup(evs, until=until)
    assert again["program_s"] == pytest.approx(row["startup/program_s"])
    assert again["first_step_s"] == pytest.approx(
        row["startup/first_step_s"])


# ------------------------------------------------------- in this process


def tiny_gpt():
    return GPT(GPTConfig(vocab_size=32, block_size=16, dim=16, n_layers=1,
                         n_heads=2, dropout=0.0))


def batches(batch=8, sleep_at=None, widen_at=None):
    """Batches of 8 rows (the conftest's 8-device data mesh); the
    `sleep_at`-th is 0.25 s late, from the `widen_at`-th on they hold 16."""
    rng = np.random.default_rng(0)
    n = 0
    while True:
        n += 1
        if n == sleep_at:
            time.sleep(0.25)
        rows = 16 if widen_at is not None and n >= widen_at else batch
        x = rng.integers(0, 32, size=(rows, 16)).astype(np.int32)
        yield {"x": jnp.asarray(x), "y": jnp.asarray(x)}


def events_since(t0, cat=None):
    return [e for e in trace.RUN.events()
            if e.ts >= t0 and (cat is None or e.cat == cat)]


def test_every_startup_span_is_recorded_once_and_nests():
    import dataclasses

    t0 = trace.RUN.clock()
    cfg = get_config("gpt_tiny")
    cfg = dataclasses.replace(cfg, train=dataclasses.replace(
        cfg.train, steps=2, log_every=1, eval_every=0))
    cfg, model, _, it, _ = build_char_lm_run(cfg)
    tr = Trainer(model, cfg.train, loss_fn=loss_fn_for(cfg),
                 init_fn=init_fn_for(cfg), rules=rules_for(cfg))
    tr.fit(it, None, writer=Rows())
    evs = events_since(t0)
    spans = {}
    for e in evs:
        if e.ph == "X" and e.cat == "startup":
            assert e.name not in spans, f"{e.name} recorded twice"
            spans[e.name] = e
    assert set(spans) == set(STARTUP_SPANS)
    for child, parent in PARENT_OF.items():
        c, p = spans[child], spans[parent]
        assert c.args["parent"] == parent
        assert p.ts <= c.ts and c.ts + c.dur <= p.ts + p.dur
    assert spans["fit_first_step"].args["step"] == 1
    # self times sum to the parents': the parts of the summary are the
    # union of the top-level spans, JAX's events inside them included
    s = summarize_startup(evs)
    covered, at = 0.0, float("-inf")
    for a, b in sorted((e.ts, e.ts + e.dur) for e in evs if e.ph == "X"):
        covered += max(b - max(a, at), 0.0)
        at = max(at, b)
    assert s["program_s"] == pytest.approx(covered, rel=1e-9)
    top = [e for n, e in spans.items() if n not in PARENT_OF]
    assert sum(e.dur for e in top) <= s["program_s"]
    assert s["build_s"] + s["init_state_s"] + s["first_step_s"] \
        < s["program_s"]  # the compiles inside them are their own part
    assert s["compile_s"] > 0 and s["trace_s"] > 0 and s["lower_s"] > 0


def test_the_listener_records_trace_lower_compile_of_the_train_step():
    t0 = trace.RUN.clock()
    tc = TrainConfig(steps=2, batch_size=8, log_every=1, eval_every=0)
    Trainer(tiny_gpt(), tc).fit(batches(), writer=Rows())
    jax_events = [e for e in events_since(t0, "jax") if e.ph == "X"]
    names = {e.name for e in jax_events}
    assert {"trace:train_step", "lower:jit(train_step)",
            "compile:jit(train_step)"} <= names
    first = [e for e in events_since(t0, "startup")
             if e.name == "fit_first_step"][0]
    for e in jax_events:
        if "train_step" in e.name:
            assert e.args["parent"] == "fit_first_step"
            assert first.ts <= e.ts + 1e-3  # the listener's clock, JAX's span
            assert e.ts + e.dur <= first.ts + first.dur
    assert compile_spans() is compile_spans()


def test_a_second_fit_compiles_nothing():
    spans = compile_spans()
    tc = TrainConfig(steps=2, batch_size=8, log_every=1, eval_every=0)
    tr = Trainer(tiny_gpt(), tc)
    it = batches()
    state = tr.fit(it, writer=Rows())
    t0, before = trace.RUN.clock(), spans.recompiles_after_first_step
    import dataclasses

    tr.config = dataclasses.replace(tc, steps=5)
    rows = Rows()
    tr.fit(it, writer=rows, state=state)
    assert [e.name for e in events_since(t0, "jax") if e.ph == "X"] == []
    assert spans.recompiles_after_first_step == before
    for r in timed_rows(rows):
        assert r["recompiles_after_first_step"] == 0
        assert "recompile_last_step" not in r


def test_a_changed_batch_shape_is_one_recompile_with_its_step():
    spans = compile_spans()
    t0, before = trace.RUN.clock(), spans.recompiles_after_first_step
    tc = TrainConfig(steps=6, batch_size=8, log_every=1, eval_every=0)
    rows = Rows()
    with pytest.warns(UserWarning, match=r"compiled again inside a timed "
                                         r"step: .*jit\(train_step\) at "
                                         r"step 4.* \(\d this fit\)"):
        Trainer(tiny_gpt(), tc).fit(batches(widen_at=4), writer=rows)
    hits = [e for e in events_since(t0, "jax")
            if e.name == "recompiles_after_first_step"]
    # the step's program, and the one that lays the wider batch out
    assert "jit(train_step)" in {e.args["program"] for e in hits}
    assert {e.args["step"] for e in hits} == {4}
    n = len(hits)
    assert spans.recompiles_after_first_step == before + n
    assert hits[-1].args["count"] == before + n
    assert list(spans.recompiled)[-n:] == [
        (4, e.args["program"]) for e in hits]
    # the row an operator reads: how many since this call began, and where
    by_step = {r["step"]: r for r in timed_rows(rows)}
    assert [by_step[k]["recompiles_after_first_step"]
            for k in (2, 3, 4, 5, 6)] == [0, 0, n, n, n]
    assert "recompile_last_step" not in by_step[3]
    assert by_step[4]["recompile_last_step"] == by_step[6][
        "recompile_last_step"] == 4


def test_the_first_compile_of_eval_and_of_a_callback_is_no_recompile():
    """`eval_step`, and whatever a callback jits, compile after the call's
    first step by design and outside the step timing."""
    spans = compile_spans()
    t0, before = trace.RUN.clock(), spans.recompiles_after_first_step
    tc = TrainConfig(steps=4, batch_size=8, log_every=1, eval_every=2,
                     eval_batches=1)

    def fresh_program(state, step):
        jax.jit(lambda x: jnp.cos(x * 1.75).sum())(jnp.arange(7.0))

    rows = Rows()
    Trainer(tiny_gpt(), tc).fit(
        batches(), eval_iter_fn=lambda: batches(), writer=rows,
        callbacks=[(3, fresh_program)])
    compiled = {e.name for e in events_since(t0, "jax") if e.ph == "X"}
    assert "compile:jit(eval_step)" in compiled
    assert "compile:jit(<lambda>)" in compiled
    assert spans.recompiles_after_first_step == before
    assert {r["recompiles_after_first_step"] for r in timed_rows(rows)} \
        == {0}


def test_the_tail_step_of_a_scan_run_is_no_recompile():
    """`train_step` first compiles at the ragged tail of a scan-windowed
    run: the engine keeps that step out of the timing, and of the count."""
    spans = compile_spans()
    t0, before = trace.RUN.clock(), spans.recompiles_after_first_step
    tc = TrainConfig(steps=5, batch_size=8, log_every=2, eval_every=0,
                     scan_steps=2)
    Trainer(tiny_gpt(), tc).fit(batches(), writer=Rows())
    compiled = [e.name for e in events_since(t0, "jax")
                if e.name.startswith("compile:jit(train_step")]
    assert sorted(compiled) == ["compile:jit(train_step)",
                                "compile:jit(train_step_scan)"]
    assert spans.recompiles_after_first_step == before


def test_the_window_after_a_resumes_realigning_step_is_no_recompile():
    """A resume lands mid-window: the call's first step is a single one,
    and the scan program first compiles at the window after it. That
    window is kept out of the timing like the tail step, so it warns of
    nothing."""
    import warnings

    spans = compile_spans()
    tc = TrainConfig(steps=3, batch_size=8, log_every=1, eval_every=0)
    it = batches()
    state = Trainer(tiny_gpt(), tc).fit(it, writer=Rows())
    t0, before = trace.RUN.clock(), spans.recompiles_after_first_step
    tc = TrainConfig(steps=8, batch_size=8, log_every=2, eval_every=0,
                     scan_steps=2)
    rows = Rows()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        Trainer(tiny_gpt(), tc).fit(it, writer=rows, state=state)
    assert "compile:jit(train_step_scan)" in {
        e.name for e in events_since(t0, "jax")}
    assert spans.recompiles_after_first_step == before
    timed = timed_rows(rows)
    assert [r["step"] for r in timed] == [6, 8]
    assert {r["recompiles_after_first_step"] for r in timed} == {0}


def test_a_compile_on_another_thread_is_no_recompile():
    import threading

    spans = compile_spans()
    before = spans.recompiles_after_first_step
    with spans.steady(3):
        t = threading.Thread(target=lambda: jax.jit(
            lambda x: jnp.sin(x * 2.5).sum())(jnp.arange(5.0)))
        t.start()
        t.join()
        assert spans.recompiles_after_first_step == before
        jax.jit(lambda x: jnp.sin(x * 4.5).sum())(jnp.arange(5.0))
    assert spans.recompiles_after_first_step == before + 1
    assert spans.recompiled[-1] == (3, "jit(<lambda>)")
    assert spans.newest(1) == "jit(<lambda>) at step 3"
    jax.jit(lambda x: jnp.sin(x * 5.5).sum())(jnp.arange(5.0))
    with spans.steady(None):
        jax.jit(lambda x: jnp.sin(x * 6.5).sum())(jnp.arange(5.0))
    assert spans.recompiles_after_first_step == before + 1


def test_the_cache_counter_reads_a_miss_then_a_hit(tmp_path):
    """Two processes' worth of one cache directory: the second compile of
    the same program, after JAX forgot the first, is read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    spans = compile_spans()
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()

        def poly(x):
            return jnp.tanh(x * 3.25 + 1.5).sum()

        x = jnp.arange(12.0)
        start = dict(spans.cache)
        t0 = trace.RUN.clock()
        jax.jit(poly)(x).block_until_ready()
        mid = dict(spans.cache)
        assert mid["misses"] == start["misses"] + 1
        assert mid["hits"] == start["hits"]
        jax.clear_caches()
        jax.jit(poly)(x).block_until_ready()
        end = dict(spans.cache)
        assert end["hits"] == mid["hits"] + 1
        assert end["misses"] == mid["misses"]
        assert end["retrieval_s"] > mid["retrieval_s"]
        counters = [e for e in events_since(t0, "jax")
                    if e.ph == "C" and e.name == "compile_cache"]
        assert counters[-1].args == end
        compiles = [e for e in events_since(t0, "jax")
                    if e.name == "compile:jit(poly)"]
        assert len(compiles) == 2  # on the hit: the read and the load
        assert summarize_startup(events_since(t0))["cache_hits"] \
            == end["hits"]
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_the_loop_annotations_reach_a_profile_the_caller_opens(tmp_path):
    """Neither `profile_dir` nor `trace_path`: the session is the test's."""
    from jax.profiler import ProfileData

    tc = TrainConfig(steps=4, batch_size=8, log_every=2, eval_every=4,
                     eval_batches=1)
    assert tc.profile_dir is None and tc.trace_path is None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        Trainer(tiny_gpt(), tc).fit(
            batches(), eval_iter_fn=lambda: batches(), writer=Rows(),
            callbacks=[(4, lambda state, step: None)])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    host, at = {}, {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    host[e.name] = host.get(e.name, 0) + 1
                    at.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    assert host.get("train") == 4 and host.get("train_dispatch") == 4
    assert host.get("data_wait", 0) >= 4  # the eval's batches too
    assert host.get("log_fetch") == 2 and host.get("log_write") >= 2
    assert host.get("eval") == 1 and host.get("callback") == 1
    # start-up's spans are annotations of the same names
    for name in ("trainer_init", "init_state", "init_jit", "build_steps",
                 "fit_setup", "fit_first_step"):
        assert host.get(name) == 1, (name, host.get(name))
    # the events nest: the set-up ends before the first step's annotation
    # opens, and `fit_first_step` lies inside that annotation
    (setup,), (first,) = at["fit_setup"], at["fit_first_step"]
    step_1 = min(at["train"])
    assert setup[1] <= step_1[0]
    assert step_1[0] <= first[0] and first[1] <= step_1[1]
    assert all(any(a <= lo and hi <= b for a, b in at["train"])
               for lo, hi in at["train_dispatch"])


def timed_rows(rows):
    return [r for r in rows.rows if "step_time_s" in r]


def test_every_timed_row_carries_the_dispatch_and_gap_maxima():
    rows = Rows()
    tc = TrainConfig(steps=7, batch_size=8, log_every=2, eval_every=0)
    Trainer(tiny_gpt(), tc).fit(batches(), writer=rows)
    timed = timed_rows(rows)
    assert [r["step"] for r in timed] == [2, 4, 6, 7]
    for r in timed:
        lo = r["step"] - 1 if r["step"] != 2 else 2
        assert lo <= r["dispatch_max_step"] <= r["step"]
        assert r["dispatch_max_ms"] > 0
        assert r["host_gap_max_ms"] >= 0
        assert r["host_gap_max_step"] in (0, *range(lo, r["step"] + 1))


def test_a_planted_sleep_lands_in_host_gap_max_at_its_step():
    rows = Rows()
    tc = TrainConfig(steps=8, batch_size=8, log_every=4, eval_every=0)
    Trainer(tiny_gpt(), tc).fit(batches(sleep_at=7), writer=rows)
    first, second = timed_rows(rows)
    assert (first["step"], second["step"]) == (4, 8)
    assert second["host_gap_max_step"] == 7
    assert 250.0 <= second["host_gap_max_ms"] < 2000.0
    assert first["host_gap_max_ms"] < 200.0
    assert second["data_wait_ms"] * 4 >= 250.0  # the same wait, as a mean
