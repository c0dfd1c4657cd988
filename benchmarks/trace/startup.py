"""Where set-up went, by the program's own account.

The program keeps one recorder a process
(`solvingpapers_tpu.metrics.trace.RUN`): start-up's spans (`import:<package>`,
`build_run`, `create_mesh`, `trainer_init`, `build_steps`, `init_state`,
`fit_first_step`, with their children), JAX's compile events as `trace:` /
`lower:` / `compile:` spans, and the compile cache's hits and misses, all on
`time.perf_counter`. Its `summarize_startup(events, until)` gives every
second that some span covers to the innermost span there, so the parts add
up to their union; the readers of `benchmarks/metrics/startup_*.py` take
their part from here.

Only what ended before the window counts. The driver's window is one
`Trainer.fit` call, the last of the run, so the window begins no later than
that call's `fit_first_step` span: nothing the program records lies between
the two (a compile there fails the run).

Against a program that has no such recorder (a commit before it) every
function here returns None.
"""

from __future__ import annotations

import json


def window_start(events) -> float | None:
    """Where the run's last `fit` call dispatched its first step, on the
    recorder's clock; None where no `fit` ran."""
    firsts = [e.ts for e in events if e.name == "fit_first_step"]
    return max(firsts) if firsts else None


def summary(obs: dict) -> dict | None:
    """`summarize_startup` of the run up to the window, computed once and
    kept on `obs`; a line of detail goes before the result line."""
    if "startup" not in obs:
        obs["startup"] = _summarize()
    return obs["startup"]


def _summarize() -> dict | None:
    from solvingpapers_tpu.metrics import trace

    # the parent of PR 37 has neither: nothing to read
    rec = getattr(trace, "RUN", None)
    summarize = getattr(trace, "summarize_startup", None)
    if rec is None or summarize is None:
        return None
    events = rec.events()
    until = window_start(events)
    if until is None:
        return None
    out = summarize(events, until=until)
    # the line of detail also names the spans themselves: seconds by name
    # (children inside their parents, so these do not add up)
    spans: dict[str, float] = {}
    for e in events:
        if e.ph == "X" and e.cat == "startup" and e.ts + e.dur <= until:
            spans[e.name] = spans.get(e.name, 0.0) + e.dur
    print(json.dumps({"startup": out, "startup_spans": spans}), flush=True)
    return out


def part(obs: dict, *keys: str) -> float | None:
    """Sum of the summary's `keys`; None where there is no summary."""
    s = summary(obs)
    return None if s is None else sum(s[k] for k in keys)


def largest_of_rows(obs: dict, key: str) -> float | None:
    """The largest `key` over the window's logged rows; None where no row
    has it."""
    vals = [r[key] for r in obs.get("rows", []) if key in r]
    return max(vals) if vals else None
