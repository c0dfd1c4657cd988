"""The device's idle time inside the window, named by what the program's
train loop was doing.

`Trainer.fit` wraps every section of its loop in a
`jax.profiler.TraceAnnotation` (`data_wait`, `train_dispatch`, `log_fetch`,
`log_write`, `eval`, `callback`, `checkpoint`: `SECTIONS`), whether or not
it knows of a profiler session, so the benchmark's own session holds them
on the host plane of the window's `.xplane.pb`. Around them lie the frames
(`FRAMES`): a `StepTraceAnnotation("train")` over every step, `fit_setup`
before the loop and `fit_first_step` over a call's first step. The frames
tile the whole `fit` call, so an idle second under a frame alone has a name
but no account of what the host did: only a section counts as named.

Each idle gap of device 0 inside `bench_window` is shared out by overlap:
the window is cut at every start and end of these spans, each piece goes
to the shortest span that covers it (`xplane.attribute_gap`; a section
inside its step, a step inside nothing), or to `unnamed`, and a gap gives
each piece the nanoseconds it shares with it. (The driver's own wrapper
puts a `data_wait` of the same name around the program's; the two differ
by microseconds.)

A trace that holds no `train_dispatch` inside the window is one of a
program without these annotations: `read` returns None there.
"""

from __future__ import annotations

import bisect
import glob
import json
import os

from benchmarks import harness
from benchmarks.trace import xplane

SECTIONS = ("data_wait", "train_dispatch", "log_fetch", "log_write", "eval",
            "callback", "checkpoint")
FRAMES = ("train", "fit_setup", "fit_first_step")
UNNAMED = "unnamed"


def newest_window_trace() -> str | None:
    """The `.xplane.pb` of the run's window, as `harness.Run.trace_dir`
    lays it out (`<work>/trace/<workload>/plugins/profile/<time>/`): the
    newest one, since a run clears its own directory first."""
    found = glob.glob(os.path.join(
        harness.WORK_DIR, "trace", "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def pieces(spans, lo: float, hi: float):
    """[lo, hi] cut at every start and end of `spans`: [(start, end, name)]
    in order, the name that of the shortest span over the piece, or
    "unnamed"."""
    cuts = sorted({lo, hi, *(t for e in spans for t in (e.start, e.end)
                             if lo < t < hi)})
    return [(a, b, xplane.attribute_gap((a, b), spans, UNNAMED))
            for a, b in zip(cuts, cuts[1:])]


def idle_gaps(path: str, names=SECTIONS + FRAMES, needs="train_dispatch",
              window_span: str = harness.WINDOW_SPAN):
    """[(ns after the window's start, ns long, {span name or "unnamed":
    ns})] for every idle gap of device 0 inside the window, the spans those
    of `names`; None where the window holds no span named `needs`."""
    planes = xplane.load_planes(path)
    lo, hi = xplane.window_of(planes, window_span)
    spans = [e for evs in planes.get(xplane.HOST_PLANE, {}).values()
             for e in evs if e.name in names and e.end > lo and e.start < hi]
    if not any(e.name == needs for e in spans):
        return None
    device = min((int(m.group(1)), name) for name in planes
                 if (m := xplane.DEVICE_PLANE.match(name)))[1]
    ops = planes[device].get(xplane.OPS_LINE, [])
    busy = xplane.clip(xplane.union([(e.start, e.end) for e in ops]), lo, hi)
    tiles = pieces(spans, lo, hi)
    starts = [a for a, _, _ in tiles]
    out = []
    for a, b in xplane.gaps_of(busy, lo, hi):
        shares: dict[str, float] = {}
        for s, e, who in tiles[bisect.bisect_right(starts, a) - 1:]:
            if s >= b:
                break
            shares[who] = shares.get(who, 0.0) + min(e, b) - max(s, a)
        out.append((a - lo, b - a, shares))
    return out


def by_span(gaps) -> dict[str, float] | None:
    """{span name or "unnamed": idle seconds} of `idle_gaps`' result."""
    if gaps is None:
        return None
    out: dict[str, float] = {}
    for _, _, shares in gaps:
        for who, ns in shares.items():
            out[who] = out.get(who, 0.0) + ns / 1e9
    return out


def named_pct(seconds: dict | None, named=SECTIONS) -> float | None:
    """Share, in percent, of the idle seconds that lie in a span of
    `named`: the loop's sections, not the frames around them."""
    if not seconds:
        return None
    total = sum(seconds.values())
    if total <= 0:
        return None
    return 100.0 * sum(seconds.get(n, 0.0) for n in named) / total


def read(obs: dict) -> float | None:
    """`named_pct` of the run's window, computed once and kept on `obs`; a
    line of detail goes before the result line: the seconds by span
    (sections, frames and "unnamed"), and the six longest gaps (ms long, ms
    after the window's start, ms by span)."""
    if "idle_by_program_span" not in obs:
        gaps = None
        if obs.get("trace") is not None:
            path = newest_window_trace()
            gaps = idle_gaps(path) if path else None
            if gaps is not None:
                longest = sorted(gaps, key=lambda g: -g[1])[:6]
                print(json.dumps({
                    "idle_by_program_span": by_span(gaps),
                    "longest_gaps": [
                        [round(ns / 1e6, 4), round(at / 1e6, 3),
                         {who: round(part / 1e6, 4)
                          for who, part in shares.items()}]
                        for at, ns, shares in longest],
                }), flush=True)
        obs["idle_by_program_span"] = by_span(gaps)
    return named_pct(obs["idle_by_program_span"])
