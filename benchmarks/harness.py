"""What every driver shares: the files of one cell, the device check, the
measured window with its compile watch and profiler, the comparisons that
decide `correct`, and the result line.

A driver (`benchmarks/drivers/<name>.py`) gets one `Run`, sets the system
up, calls `run.window()` around the measured work, puts what it observed
into `run.obs`, and holds the timed path's results against the plain
reference with `run.compare()`. Metric readers (`benchmarks/metrics/
<metric>.py`) take their numbers from `run.obs`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
# what a run leaves behind, all inside the checkout and git-ignored
WORK_DIR = os.path.join(ROOT, ".bench_work")
WINDOW_SPAN = "bench_window"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchFailure(RuntimeError):
    """The run is not a measurement; exit non-zero, print no result."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmarks/<kind>/<name>.py`, found by name (a name may hold dots,
    so not through the import system)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise BenchFailure(f"no {kind[:-1]} file {path}")
    mod_name = f"benchmarks_{kind}_{name.replace('.', '_').replace('-', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(workload: str) -> tuple[dict, dict, dict]:
    """(BENCHMARK.json, the cell's entry, its configuration's entry)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = [w for w in bench["workloads"] if w["name"] == workload]
    if not cells:
        raise BenchFailure(
            f"BENCHMARK.json has no workload {workload!r}; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    cell = cells[0]
    conf = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    return bench, cell, conf


def metrics_of(bench: dict, group: str, workload: str) -> list[dict]:
    """The entries of `end_to_end` or `per_layer` that this cell reports:
    those that list it, and those that list no cells at all."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def device_record(n_chips: int) -> tuple[dict, dict]:
    """JAX's devices as the result line gives them, and this device kind's
    row of `peaks.json`. Anything but enough TPU chips of a known kind is a
    failure: nothing here falls back to the CPU."""
    import jax

    devs = jax.devices()
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if rec["platform"] != "tpu":
        raise BenchFailure(f"JAX found no TPU (platform {rec['platform']!r})")
    if rec["count"] < n_chips:
        raise BenchFailure(
            f"the cell needs {n_chips} chip(s), JAX reports {rec['count']}")
    return rec, peaks_for(rec["kind"])


def peaks_for(kind: str) -> dict:
    table = load_json(HERE, "peaks.json")["devices"]
    if kind not in table:
        raise BenchFailure(
            f"device kind {kind!r} is not in benchmarks/peaks.json "
            f"({sorted(table)}); add its published peaks with their source")
    return table[kind]


def configure_jax() -> None:
    """The program's compile cache (fixed path in the checkout, or where
    JAX_COMPILATION_CACHE_DIR says), holding every program however small,
    so that only a checkout's first run of a cell compiles."""
    import jax

    from solvingpapers_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def peak_bytes(n_chips: int) -> tuple[int, int]:
    """(peak bytes held on the fullest chip, peak bytes of live buffers
    there). On this runtime `peak_bytes_in_use` counts buffers and leaves out
    the temporary memory of running programs, which `peak_bytes_reserved`
    (what the allocator took from the chip) includes; the first is the
    larger of the two."""
    import jax

    held, live = 0, 0
    for d in jax.devices()[:n_chips]:
        st = d.memory_stats() or {}
        live = max(live, int(st.get("peak_bytes_in_use", 0)))
        held = max(held, int(st.get("peak_bytes_reserved", 0)),
                   int(st.get("peak_bytes_in_use", 0)))
    if held <= 0:
        raise BenchFailure("the device reports no memory statistics")
    return held, live


@dataclasses.dataclass
class Run:
    workload: str
    seed: int
    seconds: float
    trace: bool
    t_start: float  # perf_counter at process start
    bench: dict
    cell: dict
    config: dict  # benchmarks/configs/<config>.json
    traffic: dict  # benchmarks/traffic/<traffic>.json
    device: dict
    peaks: dict
    obs: dict = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    setup_s: float | None = None
    _compiled: list = dataclasses.field(default_factory=list)
    _compile_log: list = dataclasses.field(default_factory=list)
    _cache_events: dict = dataclasses.field(default_factory=dict)
    _in_window: bool = False
    _phase_t0: float = 0.0

    def __post_init__(self):
        self._phase_t0 = self.t_start

    # ------------------------------------------------------------ window

    def note(self, **fields) -> None:
        """A line of detail on standard output, before the result line."""
        print(json.dumps(fields), flush=True)

    def watch_compiles(self) -> None:
        """Listen to JAX's own compile events: a program built (or read
        from the compile cache) inside the window fails the run; those of
        the whole run are summed for `compile_summary`."""
        from jax import monitoring

        def on_duration(event, duration, **kw):
            if event != COMPILE_EVENT:
                return
            name = kw.get("fun_name", "?")
            if self._in_window:
                self._compiled.append(name)
            self._compile_log.append((name, duration))

        def on_event(event, **kw):
            if event.startswith("/jax/compilation_cache/cache_"):
                key = event.rsplit("/", 1)[1]
                self._cache_events[key] = self._cache_events.get(key, 0) + 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def phase(self, name: str) -> None:
        """Mark the end of a phase of set-up (or of what follows the
        window); its seconds go on a line of their own."""
        now = time.perf_counter()
        self.note(phase=name, seconds=now - self._phase_t0,
                  since_start=now - self.t_start)
        self._phase_t0 = now

    def compile_summary(self) -> None:
        slow = sorted(((n, d) for n, d in self._compile_log if d >= 1.0),
                      key=lambda nd: -nd[1])
        self.note(compile_events=len(self._compile_log),
                  compile_seconds=sum(d for _, d in self._compile_log),
                  cache=self._cache_events,
                  over_1s=[[n, round(d, 2)] for n, d in slow[:8]])

    @property
    def window_seconds(self) -> float:
        """How long the window measures: `--seconds`, or with `--trace 1`
        the traffic file's shorter `trace_seconds` (traces are large)."""
        if self.trace:
            return min(self.seconds, self.traffic["trace_seconds"])
        return self.seconds

    @property
    def trace_dir(self) -> str:
        return os.path.join(WORK_DIR, "trace", self.workload)

    @contextlib.contextmanager
    def window(self):
        """The measured window. Set-up ends where it begins. With
        `--trace 1` the profiler runs over it and the host annotation
        `bench_window` marks it in the trace. A program compiled (or read
        from the compile cache) inside it fails the run."""
        import jax

        self.setup_s = time.perf_counter() - self.t_start
        if self.trace:
            import shutil

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._in_window = True
        t0 = time.perf_counter()
        try:
            with (jax.profiler.TraceAnnotation(WINDOW_SPAN) if self.trace
                  else contextlib.nullcontext()):
                yield
        finally:
            self.obs["window_s"] = time.perf_counter() - t0
            self._in_window = False
            if self.trace:
                jax.profiler.stop_trace()
        if self._compiled:
            raise BenchFailure(
                "compiled inside the measured window, so this is not a "
                f"measurement: {sorted(set(self._compiled))}")
        held, live = peak_bytes(self.cell["chips"])
        self.obs["peak_bytes"], self.obs["peak_live_bytes"] = held, live

    # ----------------------------------------------------------- correct

    def compare(self, name: str, value: float, limit: float) -> None:
        """One number of the comparison with the reference, beside its
        limit; printed in every run. `correct` needs every one within."""
        ok = bool(value <= limit)  # NaN fails
        self.checks.append({"check": name, "value": float(value),
                            "limit": float(limit), "ok": ok})
        self.note(**self.checks[-1])

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c["ok"] for c in self.checks)

    # ------------------------------------------------------------ result

    def reduce_trace(self, host_spans: tuple[str, ...], fallback: str):
        xplane = load_module("trace", "xplane")
        self.obs["trace"] = xplane.reduce_trace(
            xplane.find_xplane(self.trace_dir), window_span=WINDOW_SPAN,
            host_spans=host_spans, fallback=fallback)

    def result(self) -> dict:
        group = "per_layer" if self.trace else "end_to_end"
        self.obs.update(setup_s=self.setup_s, peaks=self.peaks,
                        config=self.config, traffic=self.traffic)
        metrics = {}
        for m in metrics_of(self.bench, group, self.workload):
            value = load_module("metrics", m["name"]).read(self.obs)
            if value is None:
                continue  # the reader found nothing to read
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device = dict(self.device,
                      memory_peak_bytes=int(self.obs["peak_bytes"]))
        line = {"correct": self.correct, "attempted": self.attempted,
                "failed": self.failed, "metrics": metrics, "device": device}
        if self.trace:
            tr = self.obs["trace"]
            device.update(busy_s=tr.busy_s, window_s=tr.window_s)
            xplane = load_module("trace", "xplane")
            line["breakdown"] = {
                "device_ops": [[n, s] for n, s in xplane.top_ops(tr.ops)],
                "idle_gaps": [[n, s] for n, s in tr.gaps[:10]],
            }
        return line
