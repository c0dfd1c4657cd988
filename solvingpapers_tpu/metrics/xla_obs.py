"""Compile & memory observatory: XLA compile registry, HBM ledger,
per-program roofline.

The flight recorder (metrics/trace.py) answers "where did the wall clock
go"; this module answers the device/compiler-side questions production
serving stacks triage capacity and latency regressions with:

* `CompileRegistry` — every jitted program the engines run is routed
  through an ahead-of-time signature cache: a call whose abstract
  signature (static args + dynamic shapes/dtypes) was never seen lowers
  and compiles explicitly (`jit(f).lower(...).compile()`), so the
  registry records the TRUE compile wall time plus the executable's
  `cost_analysis()` flops / bytes-accessed and `memory_analysis()` temp
  bytes — and subsequent calls dispatch the cached executable directly.
  A **recompile storm** (same program, >= `storm_k` new signatures
  inside `storm_window_s`) is the classic silent latency killer (a shape
  that never buckets, a stray weak_type flip); the registry counts it,
  warns once per program, and — when the engine's `AnomalyMonitor` is
  armed — dumps the flight-recorder ring through
  `AnomalyMonitor.observe_recompile`.

  Compiled executables are shared process-wide (`_AOT_CACHE`, the moral
  equivalent of jax's own jit cache) so a warmed benchmark arm or a
  second engine over the same model does not pay compilation twice;
  per-registry stats (calls, run seconds, signature misses) stay local
  so each engine reports its own view.

* `HBMLedger` — named live-byte pools (`params`, `kv_pool`,
  `prefix_cache`, `opt_state`, ...) registered as zero-arg providers and
  read lazily, plus the registry's max per-program temp bytes, give a
  projected decode-step peak; capacity is a PER-CHIP number, so mesh-
  aware providers use `pytree_device_bytes` (shard_shape bytes per
  device) rather than global bytes; against the device capacity
  (`memory_stats()["bytes_limit"]` where the backend reports it, or an
  explicit override) the ledger computes headroom and warns BEFORE the
  projected peak exceeds capacity — the admission-control signal, not
  the OOM post-mortem.

* roofline — joining cost_analysis flops/bytes with the registry's
  measured per-program run seconds yields achieved FLOP/s, arithmetic
  intensity (flops / byte), and per-program MFU against
  `metrics.mfu.chip_peak_flops` (NaN-safe: unknown backends simply omit
  the MFU gauge). The same join is available offline from an exported
  trace via `metrics.trace.summarize_trace` (the registry emits one
  `compile` event per compilation when a recorder is attached).

* `CompileSpans` (`compile_spans()`, one a process, always on once the
  train engine is imported) — JAX's own `jax.monitoring` compile events
  as `trace:` / `lower:` / `compile:` spans of the run's recorder
  (`metrics.trace.RUN`) and the persistent cache's hits and misses as a
  counter: nothing fenced, nothing compiled ahead of time.

The registry and the ledger are opt-in (`ServeConfig.xla_obs` /
`TrainConfig.xla_obs`); with them off every hook site is a single
`is not None` branch. With it on, program calls are fenced
(`block_until_ready`) so run seconds are device-true — the same
observability-mode contract as flight-recorder tracing; what the
fences cost is not measured on the chip.
"""

from __future__ import annotations

import contextlib
import hashlib
import math
import os
import tempfile
import threading
import time
import warnings
from collections import deque
from typing import Any, Callable

import jax

from solvingpapers_tpu.metrics import trace as run_trace
from solvingpapers_tpu.metrics.mfu import chip_peak_flops
from solvingpapers_tpu.metrics.writer import PrometheusTextWriter


def pytree_bytes(tree) -> int:
    """Total GLOBAL bytes of every array leaf in a pytree (device or
    host) — the logical array sizes, regardless of sharding."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = getattr(leaf, "size", None)
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", None)
        if size is not None and itemsize is not None:
            total += int(size) * int(itemsize)
    return total


def pytree_device_bytes(tree) -> int:
    """PER-DEVICE bytes of a pytree: a sharded leaf occupies its
    `Sharding.shard_shape` bytes on each device, not its global bytes —
    the number HBM capacity accounting must book under a mesh (a
    TP-sharded kernel costs 1/model of its global size per chip; a
    replicated one costs full size everywhere). Host arrays and leaves
    without a sharding fall back to global bytes (single-device
    semantics, where the two coincide)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", None)
        if itemsize is None:
            continue
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            try:
                total += int(
                    math.prod(sharding.shard_shape(leaf.shape)) * itemsize
                )
                continue
            except Exception:  # exotic sharding: global beats a crash
                pass
        size = getattr(leaf, "size", None)
        if size is not None:
            total += int(size) * int(itemsize)
    return total


def device_capacity_bytes(device=None) -> int | None:
    """Device memory capacity, or None where the backend does not report
    it (CPU: `memory_stats()` is None — the ledger then omits headroom
    gauges instead of inventing a number)."""
    device = device or jax.devices()[0]
    stats_fn = getattr(device, "memory_stats", None)
    if stats_fn is None:
        return None
    try:
        stats = stats_fn()
    except Exception:  # backend quirk: absent beats a crashed gauge read
        return None
    if not stats:
        return None
    limit = stats.get("bytes_limit")
    return int(limit) if limit else None


# ------------------------------------------------ JAX's own compile events

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # on a hit of the persistent cache: the read and the executable's load
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
# JAX reports a trace for every `jnp` function a traced program calls (they
# lie inside the program's own `trace:` span) and for every look-up of a
# trace it already has: thousands a model, microseconds each. Shorter ones
# are not recorded; every `compile:` is.
_MIN_TRACE_S = 1e-3


class CompileSpans:
    """JAX's compile events as spans and counters of one recorder, with no
    fence and no ahead-of-time compile (`CompileRegistry` is that mode):
    `trace:<fun>`, `lower:<fun>` (of a millisecond or more) and
    `compile:<fun>` as completed spans that end where `jax.monitoring`
    reports them (on the compiling thread, so
    `parent` is the span of `metrics.trace.begin` open there), and the
    persistent cache's hits, misses and seconds of retrieval as the
    counter `compile_cache`.

    `Trainer.fit` dispatches every step after a call's first inside
    `steady(step)`: a compile on that thread while it is open is one the
    timed steps paid for. It is counted in `recompiles_after_first_step`,
    kept in `recompiled` (step, program; the newest 32) for the logged row
    and the warning `fit` gives, and recorded as an instant of the
    counter's name.
    Evaluation, callbacks, the pipeline probe and a scan run's first tail
    step compile outside it, as does whatever another thread compiles."""

    def __init__(self, recorder):
        self.recorder = recorder
        self.cache = {"hits": 0, "misses": 0, "retrieval_s": 0.0}
        self.recompiles_after_first_step = 0
        self.recompiled: deque[tuple[int, str]] = deque(maxlen=32)
        self._steady = threading.local()
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def steady(self, step: int | None):
        """Compiles on this thread inside the block are recompiles at
        `step`; none is counted where it is None."""
        self._steady.step = step
        try:
            yield
        finally:
            self._steady.step = None

    def newest(self, n: int) -> str:
        """`<program> at step <step>` of the newest `n` recompiles."""
        return ", ".join(f"{program} at step {step}"
                         for step, program in list(self.recompiled)[-n:])

    def on_duration(self, event: str, duration: float, **kw) -> None:
        kind = _COMPILE_EVENTS.get(event)
        if kind is None:
            if event == _CACHE_RETRIEVAL:
                self._count("retrieval_s", duration)
            return
        if kind != "compile" and duration < _MIN_TRACE_S:
            return
        rec, name = self.recorder, str(kw.get("fun_name", "?"))
        rec.complete(f"{kind}:{name}", "jax", "startup",
                     ts=rec.clock() - duration, dur=duration,
                     parent=run_trace.current_span())
        step = getattr(self._steady, "step", None)
        if kind == "compile" and step is not None:
            with self._lock:
                self.recompiles_after_first_step += 1
                n = self.recompiles_after_first_step
                self.recompiled.append((step, name))
            rec.instant("recompiles_after_first_step", "jax", "startup",
                        count=n, step=step, program=name)

    def on_event(self, event: str, **kw) -> None:
        key = _CACHE_EVENTS.get(event)
        if key is not None:
            self._count(key, 1)

    def _count(self, key: str, by) -> None:
        with self._lock:
            self.cache[key] += by
            values = dict(self.cache)
        self.recorder.counter("compile_cache", "jax", "startup", **values)


_COMPILE_SPANS: CompileSpans | None = None
_COMPILE_SPANS_LOCK = threading.Lock()


def compile_spans() -> CompileSpans:
    """The process's one `CompileSpans`, on the run's recorder
    (`metrics.trace.RUN`); the first call registers it with
    `jax.monitoring`."""
    global _COMPILE_SPANS
    with _COMPILE_SPANS_LOCK:
        if _COMPILE_SPANS is None:
            from jax import monitoring

            _COMPILE_SPANS = CompileSpans(run_trace.RUN)
            monitoring.register_event_duration_secs_listener(
                _COMPILE_SPANS.on_duration)
            monitoring.register_event_listener(_COMPILE_SPANS.on_event)
    return _COMPILE_SPANS


# process-global executable cache: (id(jitted), statics, dynamic avals)
# -> _Executable. `jitted` is kept alive by the entry itself (strong ref)
# so an id() can never be recycled onto a different function while its
# executables are cached.
_AOT_CACHE: dict[tuple, "_Executable"] = {}
_AOT_LOCK = threading.Lock()


def clear_aot_cache() -> None:
    """Drop every cached executable (tests that must observe true
    compiles call this first; production code never needs to)."""
    with _AOT_LOCK:
        _AOT_CACHE.clear()


class _Executable:
    """One compiled program variant + its compile-time analyses."""

    __slots__ = ("compiled", "jitted", "compile_s", "flops",
                 "bytes_accessed", "temp_bytes", "arg_bytes", "out_bytes",
                 "collectives", "anatomy")

    def __init__(self, compiled, jitted, compile_s: float):
        self.compiled = compiled
        self.jitted = jitted  # strong ref: pins id(jitted) while cached
        self.compile_s = compile_s
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.temp_bytes = 0
        self.arg_bytes = 0
        self.out_bytes = 0
        # parse_hlo_collectives result, or None while unparsed (parsing
        # is lazy and gated on CompileRegistry(collectives=True) — the
        # HLO text render is not free, and most registries never ask)
        self.collectives: dict | None = None
        # parse_hlo_costs result (metrics/hlo_cost.py), same lazy
        # contract gated on CompileRegistry(anatomy=True): None = never
        # parsed, {} = parse failed (as_text unavailable) — absence,
        # never an invented zero ledger
        self.anatomy: dict | None = None
        try:
            ca = compiled.cost_analysis()
            d = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
            self.flops = float(d.get("flops", 0.0))
            self.bytes_accessed = float(d.get("bytes accessed", 0.0))
        except Exception:
            pass  # not every backend implements cost_analysis
        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                self.temp_bytes = int(ma.temp_size_in_bytes)
                self.arg_bytes = int(ma.argument_size_in_bytes)
                self.out_bytes = int(ma.output_size_in_bytes)
        except Exception:
            pass  # memory_analysis is backend-dependent


class _SigStats:
    """Per-registry stats for one (program, signature) variant."""

    __slots__ = ("exe", "calls", "run_s", "cached")

    def __init__(self, exe: _Executable, cached: bool):
        self.exe = exe
        self.calls = 0
        self.run_s = 0.0
        self.cached = cached  # served from the process-global cache


class _ProgramStats:
    """Per-registry stats for one named program across its signatures."""

    __slots__ = ("name", "signatures", "compile_s", "compiles", "cached",
                 "miss_stamps", "storms", "storm_warned", "in_storm")

    def __init__(self, name: str):
        self.name = name
        self.signatures: dict[Any, _SigStats] = {}
        self.compile_s = 0.0  # true XLA compiles this registry triggered
        self.compiles = 0  # signature misses (new program variants seen)
        self.cached = 0  # misses served by the process-global cache
        self.miss_stamps: deque[float] = deque(maxlen=64)
        self.storms = 0  # storm EPISODES (below-k -> at-k transitions)
        self.storm_warned = False
        self.in_storm = False

    @property
    def calls(self) -> int:
        return sum(s.calls for s in self.signatures.values())

    @property
    def run_s(self) -> float:
        return sum(s.run_s for s in self.signatures.values())

    def weighted_flops(self) -> float:
        return sum(s.exe.flops * s.calls for s in self.signatures.values())

    def weighted_bytes(self) -> float:
        return sum(
            s.exe.bytes_accessed * s.calls for s in self.signatures.values()
        )


class CompileRegistry:
    """Signature-keyed AOT dispatch + compile/roofline accounting.

    `call(program, key, jitted, args, static_argnums)` is the single
    entry point: `key` is a CHEAP hashable the call site derives from
    what actually varies (e.g. the prefill bucket's `(padded, chunk,
    start)`) so the hot path never hashes a parameter pytree; the full
    abstract signature is only computed on a registry-level miss, to key
    the process-global executable cache safely across engines whose
    cheap keys collide (two engines over different models share the same
    module-level jitted function).

    `time_programs=True` (default) fences every dispatch so per-program
    run seconds — the roofline denominator — are device wall time, not
    dispatch time. Observability mode, same contract as tracing.
    """

    def __init__(
        self,
        trace=None,
        monitor=None,
        storm_k: int = 8,
        storm_window_s: float = 60.0,
        clock: Callable[[], float] = time.monotonic,
        time_programs: bool = True,
        collectives: bool = False,
        anatomy: bool = False,
        hlo_dir: str | None = None,
    ):
        if storm_k < 2:
            raise ValueError(f"storm_k must be >= 2, got {storm_k}")
        if storm_window_s <= 0:
            raise ValueError(
                f"storm_window_s must be > 0, got {storm_window_s}"
            )
        self.trace = trace  # metrics.trace.FlightRecorder | None
        self.monitor = monitor  # metrics.trace.AnomalyMonitor | None
        self.storm_k = storm_k
        self.storm_window_s = storm_window_s
        self.clock = clock
        self.time_programs = time_programs
        # mesh observatory mode (metrics/mesh_obs.py): parse each
        # compiled program's HLO text for collective ops so the ledger
        # can report per-program comm bytes — compile-time-only cost
        self.collectives = collectives
        # program-anatomy mode (metrics/hlo_cost.py): parse each
        # compiled program's HLO text into the per-op-category cost
        # ledger (gather/scatter/dot/convert/... flops + output-shape
        # bytes, top-k heaviest ops) — compile-time-only cost, same
        # lazy contract as the collective ledger
        self.anatomy = anatomy
        # optional per-signature compiled-HLO text dump directory
        # (ServeConfig.obs_hlo_dir): one file per TRUE compile, written
        # atomically (tmp + rename), named
        # <sanitized program>__<signature hash>.hlo.txt — so anatomy
        # claims can be diffed offline against the exact HLO they came
        # from. Dump failures warn once and never break a compile.
        self.hlo_dir = hlo_dir
        self._hlo_dump_warned = False
        self._programs: dict[str, _ProgramStats] = {}
        self._lock = threading.Lock()
        # chip peak for per-program MFU; NaN on backends without a table
        # entry (metrics/mfu.py) — MFU gauges are omitted, never garbage
        self.peak_flops = chip_peak_flops()

    # ------------------------------------------------------------ dispatch

    def call(self, program: str, key, jitted, args: tuple,
             static_argnums: tuple = ()):
        """Run `jitted(*args)` through the registry: compile-on-new-
        signature (recorded), then dispatch the cached executable with
        the static args stripped (the AOT calling convention)."""
        st = self._programs.get(program)
        if st is None:
            with self._lock:
                st = self._programs.setdefault(program,
                                               _ProgramStats(program))
        sig = st.signatures.get(key)
        if sig is None:
            sig = self._admit(program, st, key, jitted, args, static_argnums)
        if static_argnums:
            dyn = tuple(a for i, a in enumerate(args)
                        if i not in static_argnums)
        else:
            dyn = args
        t0 = self.clock()
        out = sig.exe.compiled(*dyn)
        if self.time_programs:
            out = jax.block_until_ready(out)
            sig.run_s += self.clock() - t0
        sig.calls += 1
        return out

    def _admit(self, program: str, st: _ProgramStats, key, jitted,
               args: tuple, static_argnums: tuple) -> _SigStats:
        """Registry-level signature miss: resolve (or build) the
        executable, record the compilation, check for a storm."""
        statics = tuple(args[i] for i in static_argnums)
        avals = tuple(
            (tuple(leaf.shape), str(leaf.dtype))
            for i, a in enumerate(args) if i not in static_argnums
            for leaf in jax.tree_util.tree_leaves(a)
        )
        global_key = (id(jitted), statics, avals)
        with _AOT_LOCK:
            exe = _AOT_CACHE.get(global_key)
        cached = exe is not None
        if exe is None:
            lowered = jitted.lower(*args)
            t0 = self.clock()
            compiled = lowered.compile()
            exe = _Executable(compiled, jitted, self.clock() - t0)
            with _AOT_LOCK:
                exe = _AOT_CACHE.setdefault(global_key, exe)
        # the HLO text render is not free: do it ONCE per executable and
        # feed every consumer (collective ledger, anatomy ledger, dump)
        hlo_text: str | None = None
        if ((self.collectives and exe.collectives is None)
                or (self.anatomy and exe.anatomy is None)
                or (self.hlo_dir is not None and not cached)):
            try:
                hlo_text = exe.compiled.as_text()
            except Exception:  # backend without as_text: absent, not 0s
                hlo_text = None
        if self.collectives and exe.collectives is None:
            # lazy (a cache hit may come from a registry that never
            # parsed); a benign race would just parse twice
            from solvingpapers_tpu.metrics.mesh_obs import (
                parse_hlo_collectives,
            )

            try:
                exe.collectives = (parse_hlo_collectives(hlo_text)
                                   if hlo_text is not None else {})
            except Exception:  # {} = parse failed: absence, never zeros
                exe.collectives = {}
        if self.anatomy and exe.anatomy is None:
            from solvingpapers_tpu.metrics.hlo_cost import parse_hlo_costs

            try:
                exe.anatomy = (parse_hlo_costs(hlo_text)
                               if hlo_text is not None else {})
            except Exception:  # same contract as the collective ledger
                exe.anatomy = {}
        if self.hlo_dir is not None and not cached and hlo_text is not None:
            self._dump_hlo(program, key, hlo_text)
        sig = _SigStats(exe, cached)
        with self._lock:
            st.signatures[key] = sig
            st.compiles += 1
            if cached:
                st.cached += 1
            else:
                st.compile_s += exe.compile_s
            now = self.clock()
            st.miss_stamps.append(now)
            while st.miss_stamps and now - st.miss_stamps[0] > \
                    self.storm_window_s:
                st.miss_stamps.popleft()
            over = len(st.miss_stamps) >= self.storm_k
            # fire once per EPISODE (the below-k -> at-k transition): a
            # sustained storm stays over the threshold for every further
            # miss, and re-dumping per miss would both spam an fsync'd
            # multi-KB record onto the compile path and exhaust the
            # AnomalyMonitor's shared max_dumps budget, silencing later
            # timeout/reject anomalies in the same run
            storm = over and not st.in_storm
            st.in_storm = over
            if storm:
                st.storms += 1
        if self.trace is not None:
            ev = dict(
                program=program, signature=str(key),
                compile_s=round(exe.compile_s, 6), flops=exe.flops,
                bytes=exe.bytes_accessed, temp_bytes=exe.temp_bytes,
                cached=int(cached),
            )
            if math.isfinite(self.peak_flops):
                ev["peak_flops"] = self.peak_flops
            if exe.collectives and exe.collectives.get("ops"):
                # collective ledger (mesh observatory on): the offline
                # trace-summary comm section joins on these
                ev["comm_ops"] = exe.collectives["ops"]
                ev["comm_bytes"] = exe.collectives["bytes"]
                ev["comm_by_type"] = {
                    k: dict(v)
                    for k, v in exe.collectives["by_type"].items()
                }
            if exe.anatomy and exe.anatomy.get("ops"):
                # per-op anatomy ledger: the offline trace-summary
                # anatomy section joins on this one nested arg (empty
                # parse = absent, matching the statusz contract)
                ev["anatomy"] = exe.anatomy
            self.trace.instant("compile", "xla", "xla", **ev)
        if storm:
            if not st.storm_warned:
                st.storm_warned = True
                warnings.warn(
                    f"recompile storm: program {program!r} saw "
                    f"{len(st.miss_stamps)} new signatures within "
                    f"{self.storm_window_s:g}s — shape bucketing is not "
                    "holding, every miss pays a fresh XLA compile",
                    stacklevel=3,
                )
            if self.monitor is not None:
                self.monitor.observe_recompile(
                    program, new_signatures=len(st.miss_stamps),
                    window_s=self.storm_window_s,
                )
        return sig

    def _dump_hlo(self, program: str, key, text: str) -> None:
        """Write one compiled signature's HLO text to `hlo_dir`
        atomically (tmp + rename — a reader or an uploader never sees a
        torn file): ``<sanitized program>__<signature hash>.hlo.txt``.
        Prometheus-style sanitized program names keep the files
        shell/artifact safe; the hash keys the exact signature so two
        prefill buckets never clobber each other."""
        try:
            os.makedirs(self.hlo_dir, exist_ok=True)
            digest = hashlib.sha1(
                repr(key).encode("utf-8", "replace")
            ).hexdigest()[:12]
            name = (f"{PrometheusTextWriter.sanitize(program)}"
                    f"__{digest}.hlo.txt")
            fd, tmp = tempfile.mkstemp(dir=self.hlo_dir,
                                       prefix=".hlo_tmp_")
            try:
                with os.fdopen(fd, "w") as f:
                    f.write(text)
                os.replace(tmp, os.path.join(self.hlo_dir, name))
            except BaseException:
                os.unlink(tmp)
                raise
        except OSError as e:
            if not self._hlo_dump_warned:
                self._hlo_dump_warned = True
                warnings.warn(
                    f"obs_hlo_dir: cannot dump compiled HLO to "
                    f"{self.hlo_dir!r} ({e}) — continuing without dumps",
                    stacklevel=3,
                )

    # ------------------------------------------------------------- reading

    def anatomy_stats(self) -> dict:
        """Per-program anatomy ledger (programs whose registry was built
        with ``anatomy=True`` and that parsed): {program:
        parse_hlo_costs result} from the heaviest-bytes signature (the
        steady-state variant — the collective_stats convention). A
        program built without the flag, or whose as_text failed, is
        simply absent — never a zero ledger."""
        from solvingpapers_tpu.metrics.hlo_cost import best_anatomy

        with self._lock:
            out = {}
            for name, st in self._programs.items():
                best = best_anatomy(
                    s.exe.anatomy for s in st.signatures.values()
                )
                if best is not None:
                    out[name] = best
        return out

    def collective_stats(self) -> dict:
        """Per-program collective ledger (programs whose registry was
        built with `collectives=True` and that parsed): {program:
        {"ops", "bytes", "by_type", "calls", "run_s"}} — ops/bytes from
        the largest-traffic signature (the steady-state variant, the
        flops_per_call convention), calls/run_s summed for the wall
        join. A compiled program with no collectives reports a true
        zero; an unparsed one (registry built without the flag) is
        simply absent."""
        with self._lock:
            out = {}
            for name, st in self._programs.items():
                best: dict | None = None
                for s in st.signatures.values():
                    c = s.exe.collectives
                    # None = never parsed; {} = parse FAILED (as_text
                    # unavailable) — both are absence, never a zero. A
                    # parsed zero-collective program carries the full
                    # {"ops": 0, "bytes": 0, "by_type": {}} structure.
                    if not c:
                        continue
                    if best is None or c.get("bytes", 0) > best.get(
                            "bytes", 0):
                        best = c
                if best is None:
                    continue
                out[name] = {
                    "ops": best.get("ops", 0),
                    "bytes": best.get("bytes", 0),
                    "by_type": {k: dict(v)
                                for k, v in best.get("by_type", {}).items()},
                    "calls": st.calls,
                    "run_s": st.run_s,
                }
        return out

    def hlo_texts(self, program: str) -> list[str]:
        """Compiled HLO text of every signature `program` has run through
        this registry — the executables that actually ran, so a caller can
        check what the compiler put in them (a Mosaic kernel's
        `tpu_custom_call`, a mesh's collectives)."""
        with self._lock:
            st = self._programs.get(program)
            sigs = list(st.signatures.values()) if st is not None else []
        return [s.exe.compiled.as_text() for s in sigs]

    def max_temp_bytes(self) -> int:
        """Largest per-program XLA temp allocation seen — the scratch the
        ledger adds on top of live pools for the projected peak."""
        with self._lock:
            return max(
                (s.exe.temp_bytes
                 for st in self._programs.values()
                 for s in st.signatures.values()),
                default=0,
            )

    @property
    def total_compile_s(self) -> float:
        with self._lock:
            return sum(st.compile_s for st in self._programs.values())

    def gauges(self) -> dict[str, float]:
        """Flat `compile/*` + `roofline/*` metric keys (ServeMetrics
        gauge-provider / train log-row shape). The whole read holds the
        registry lock: gauge requests arrive from the status server's
        threads while the engine thread may be inserting a new signature
        (`_admit`), and iterating the signatures dict during that insert
        would raise mid-scrape."""
        with self._lock:
            progs = list(self._programs.values())
            out = {
                "compile/programs": float(len(progs)),
                "compile/compilations": float(
                    sum(p.compiles for p in progs)
                ),
                "compile/cached": float(sum(p.cached for p in progs)),
                "compile/recompiles": float(
                    sum(max(p.compiles - 1, 0) for p in progs)
                ),
                "compile/storms": float(sum(p.storms for p in progs)),
                "compile/time_s": float(sum(p.compile_s for p in progs)),
            }
            for p in progs:
                run_s = p.run_s
                if run_s <= 0.0 or not p.calls:
                    continue
                name = PrometheusTextWriter.sanitize(p.name)
                flops = p.weighted_flops()
                nbytes = p.weighted_bytes()
                achieved = flops / run_s
                out[f"roofline/{name}_flops_per_s"] = achieved
                if nbytes > 0:
                    out[f"roofline/{name}_intensity"] = flops / nbytes
                if math.isfinite(self.peak_flops) and self.peak_flops > 0 \
                        and flops > 0:
                    out[f"roofline/{name}_mfu"] = achieved / self.peak_flops
        return out

    def snapshot(self) -> dict:
        """Structured view for /statusz: per-program signature counts,
        compile seconds, calls, run seconds, and the roofline join.
        Built entirely under the lock — see `gauges`."""
        with self._lock:
            progs = {
                name: {
                    "signatures": len(st.signatures),
                    "compilations": st.compiles,
                    "cached": st.cached,
                    "compile_time_s": round(st.compile_s, 6),
                    "calls": st.calls,
                    "run_time_s": round(st.run_s, 6),
                    "storms": st.storms,
                    "flops_per_call": max(
                        (s.exe.flops for s in st.signatures.values()),
                        default=0.0,
                    ),
                    "bytes_per_call": max(
                        (s.exe.bytes_accessed
                         for s in st.signatures.values()),
                        default=0.0,
                    ),
                    "temp_bytes": max(
                        (s.exe.temp_bytes for s in st.signatures.values()),
                        default=0,
                    ),
                    "_flops": st.weighted_flops(),
                    "_bytes": st.weighted_bytes(),
                    # -1 = no signature parsed (collectives off, or the
                    # parse failed — empty dict): the key is dropped
                    # below rather than faked as zero
                    "_comm": max(
                        (s.exe.collectives.get("bytes", 0)
                         if s.exe.collectives else -1
                         for s in st.signatures.values()),
                        default=-1,
                    ),
                }
                for name, st in self._programs.items()
            }
            # per-program anatomy (ledger of the heaviest-bytes parsed
            # signature — hlo_cost.best_anatomy, ONE pick convention
            # with anatomy_stats and the offline trace join): present
            # IFF the registry parses anatomy and as_text worked — the
            # statusz `programs.<name>.anatomy` surface the trace
            # section and README document
            from solvingpapers_tpu.metrics.hlo_cost import best_anatomy

            for name, st in self._programs.items():
                best = best_anatomy(
                    s.exe.anatomy for s in st.signatures.values()
                )
                if best is not None:
                    progs[name]["anatomy"] = best
        for d in progs.values():
            comm = d.pop("_comm")
            if comm >= 0:
                d["comm_bytes_per_call"] = comm
            flops, nbytes = d.pop("_flops"), d.pop("_bytes")
            if d["run_time_s"] > 0 and d["calls"]:
                d["achieved_flops_per_s"] = flops / d["run_time_s"]
                if nbytes > 0:
                    d["intensity_flops_per_byte"] = flops / nbytes
                if math.isfinite(self.peak_flops) and flops > 0:
                    d["mfu"] = d["achieved_flops_per_s"] / self.peak_flops
        return {
            "programs": progs,
            "total_compile_time_s": round(
                sum(d["compile_time_s"] for d in progs.values()), 6
            ),
            "storms": sum(d["storms"] for d in progs.values()),
        }


class HBMLedger:
    """Named live-byte pools + projected-peak headroom accounting.

    `register(name, provider)` attaches a zero-arg callable returning
    the pool's CURRENT device bytes (providers read live engine state,
    so gauges are always fresh and the ledger never caches stale
    sizes); `temp_fn` (typically `CompileRegistry.max_temp_bytes`) adds
    the largest per-program scratch on top for the projected peak.
    `check()` warns once when the projection exceeds the device
    capacity — call it where memory can grow (the engine does so per
    admission), not per token.
    """

    def __init__(self, capacity_bytes: int | None = None, device=None):
        self.pools: dict[str, Callable[[], int]] = {}
        self.temp_fn: Callable[[], int] | None = None
        self.capacity_bytes = (
            capacity_bytes if capacity_bytes is not None
            else device_capacity_bytes(device)
        )
        self._warned = False

    def register(self, name: str, provider: Callable[[], int] | int) -> None:
        if not callable(provider):
            value = int(provider)
            provider = lambda: value  # noqa: E731 — constant pool size
        if name in self.pools:
            raise ValueError(f"pool {name!r} already registered")
        self.pools[name] = provider

    def pool_bytes(self) -> dict[str, int]:
        return {name: int(fn()) for name, fn in self.pools.items()}

    def live_bytes(self) -> int:
        return sum(self.pool_bytes().values())

    def temp_bytes(self) -> int:
        return int(self.temp_fn()) if self.temp_fn is not None else 0

    def projected_peak_bytes(self) -> int:
        """Live pools + the largest per-program XLA scratch: the
        estimate of the next decode step's high-water mark."""
        return self.live_bytes() + self.temp_bytes()

    def headroom_bytes(self) -> int | None:
        if self.capacity_bytes is None:
            return None
        return self.capacity_bytes - self.projected_peak_bytes()

    def check(self) -> bool:
        """True (and a one-shot warning) when the projected peak exceeds
        capacity — the moment admission control should stop admitting."""
        if self.capacity_bytes is None:
            return False
        peak = self.projected_peak_bytes()
        if peak <= self.capacity_bytes:
            return False
        if not self._warned:
            self._warned = True
            warnings.warn(
                f"projected HBM peak {peak} bytes exceeds device capacity "
                f"{self.capacity_bytes} bytes (pools {self.pool_bytes()}, "
                f"program temp {self.temp_bytes()}) — the next step may "
                "OOM; shed load or shrink the pools",
                stacklevel=2,
            )
        return True

    def gauges(self) -> dict[str, float]:
        """Flat `mem/*` metric keys."""
        pools = self.pool_bytes()
        out = {f"mem/{PrometheusTextWriter.sanitize(k)}_bytes": float(v)
               for k, v in pools.items()}
        temp = self.temp_bytes()
        live = sum(pools.values())
        out["mem/live_bytes"] = float(live)
        out["mem/program_temp_bytes"] = float(temp)
        out["mem/projected_peak_bytes"] = float(live + temp)
        if self.capacity_bytes is not None:
            out["mem/capacity_bytes"] = float(self.capacity_bytes)
            out["mem/headroom_bytes"] = float(
                self.capacity_bytes - live - temp
            )
        return out

    def snapshot(self) -> dict:
        """Structured view for /statusz."""
        pools = self.pool_bytes()
        temp = self.temp_bytes()
        live = sum(pools.values())
        return {
            "pools": pools,
            "live_bytes": live,
            "program_temp_bytes": temp,
            "projected_peak_bytes": live + temp,
            "capacity_bytes": self.capacity_bytes,
            "headroom_bytes": (
                None if self.capacity_bytes is None
                else self.capacity_bytes - live - temp
            ),
        }
