"""Reduction of a JAX profiler trace (`.xplane.pb`) to what the metric
readers need: the intervals in which an operation ran on each device, time
per program and per operation, and the idle gaps of the device named by the
host span they fall in.

Read with `jax.profiler.ProfileData` and nothing else. What a TPU v5e trace
holds (looked at by hand, PR 24): one plane `/device:TPU:<n>` per chip with
the lines `XLA Modules` (one event per execution of a compiled program,
named `jit_<fn>(<fingerprint>)`), `XLA Ops` (one event per HLO instruction
executed, named by the instruction's text) and `Async XLA Ops` (copies in
flight, which overlap the ops and are left out of busy time); and one plane
`/host:CPU` whose lines are host threads, where a
`jax.profiler.TraceAnnotation` shows under its own name. All planes count
nanoseconds since the profile began, but the device's clock ran about 1 ms
ahead of the host's in the traces read so far: a window of seconds loses
nothing by it, a gap of a millisecond or two can land in the neighbouring
host span.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    end: float  # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Reduced:
    """One traced window, reduced. Times in seconds."""

    window_s: float
    busy_s: float  # mean over devices of the union of op intervals
    n_devices: int
    modules: dict[str, list[float]]  # program name -> durations (device 0)
    ops: dict[str, list[float]]  # instruction text -> durations (device 0)
    gaps: list[tuple[str, float]]  # (host span or "unattributed", seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_planes(path: str) -> dict[str, dict[str, list[Event]]]:
    """{plane name: {line name: [Event]}}; host threads' lines keep their
    names, several lines of one name are merged."""
    from jax.profiler import ProfileData

    out: dict[str, dict[str, list[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                evs.append(Event(e.name, float(e.start_ns),
                                 float(e.start_ns) + float(e.duration_ns)))
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged, non-overlapping intervals."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps_of(busy: list[tuple[float, float]], lo: float, hi: float):
    """The complement of merged `busy` intervals inside [lo, hi]."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def window_of(planes, span: str) -> tuple[float, float]:
    """The interval of the host annotation `span` (the benchmark wraps its
    traced window in one)."""
    for evs in planes.get(HOST_PLANE, {}).values():
        for e in evs:
            if e.name == span:
                return e.start, e.end
    raise ValueError(f"trace holds no host span named {span!r}")


def attribute_gap(gap: tuple[float, float], spans: list[Event],
                  fallback: str) -> str:
    """The name of the shortest host span that covers the middle of
    `gap`; `fallback` where none does."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for e in spans:
        if e.start <= mid <= e.end and (best is None or e.dur < best.dur):
            best = e
    return best.name if best is not None else fallback


def reduce_trace(path: str, *, window_span: str, host_spans: tuple[str, ...],
                 fallback: str = "unattributed") -> Reduced:
    """Reduce the trace at `path` over the window that the host annotation
    `window_span` marks. `host_spans` are the annotation names (exact, or a
    prefix ending in `*`) that idle gaps are attributed to."""
    planes = load_planes(path)
    lo, hi = window_of(planes, window_span)
    devices = sorted((int(m.group(1)), name) for name in planes
                     if (m := DEVICE_PLANE.match(name)))
    if not devices:
        raise ValueError("trace holds no /device:TPU:<n> plane")

    def wanted(name: str) -> bool:
        return any(name == s or (s.endswith("*") and name.startswith(s[:-1]))
                   for s in host_spans)

    spans = [e for evs in planes.get(HOST_PLANE, {}).values() for e in evs
             if wanted(e.name)]
    busy_total = 0.0
    first_busy = None
    for _, name in devices:
        ops = planes[name].get(OPS_LINE, [])
        merged = clip(union([(e.start, e.end) for e in ops]), lo, hi)
        busy_total += sum(b - a for a, b in merged)
        if first_busy is None:
            first_busy = merged
    dev0 = planes[devices[0][1]]

    def by_name(line: str) -> dict[str, list[float]]:
        out: dict[str, list[float]] = {}
        for e in dev0.get(line, []):
            if e.start >= lo and e.end <= hi:
                out.setdefault(e.name, []).append(e.dur / 1e9)
        return out

    gap_s: dict[str, float] = {}
    for g in gaps_of(first_busy, lo, hi):
        who = attribute_gap(g, spans, fallback)
        gap_s[who] = gap_s.get(who, 0.0) + (g[1] - g[0]) / 1e9
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_total / len(devices) / 1e9,
        n_devices=len(devices),
        modules=by_name(MODULES_LINE),
        ops=by_name(OPS_LINE),
        gaps=sorted(gap_s.items(), key=lambda kv: -kv[1]),
    )


def median_module_ms(tr: Reduced | None, prefix: str | None):
    """Median device duration, in ms, of the executions of the programs
    named `<prefix>(<fingerprint>)`; None where there are none."""
    if tr is None or not prefix:
        return None
    durs = [d for name, ds in tr.modules.items()
            if name.startswith(prefix + "(") for d in ds]
    return 1e3 * statistics.median(durs) if durs else None


def top_ops(ops: dict[str, list[float]], n: int = 10):
    """[(label, seconds)] of the `n` instructions with most device time;
    the label is the instruction's name and result shape."""
    rows = []
    for text, durs in ops.items():
        head = text.split(" fusion(")[0].split(" custom-call(")[0]
        rows.append((head[:120], sum(durs)))
    return sorted(rows, key=lambda r: -r[1])[:n]
