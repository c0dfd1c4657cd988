"""Device time of the train step by layer of the program.

The program wraps its layers in `jax.named_scope`s from a fixed vocabulary
and names its Pallas kernels (`solvingpapers_tpu/metrics/hlo_cost.py`:
`LAYER_SCOPES`, `KERNEL_SCOPES`); `hlo_cost.program_scopes(<program>)` maps
each instruction of the compiled program to `(layer or None, pass,
top_level)`. The profiler's `XLA Ops` events are named by the instruction's
text, which begins `%<name> = `, so the two meet on the instruction's name.

A `while`, a `conditional` or a `call` is one event and the instructions of
its body are events inside it, so only top-level instructions are summed:
a layer's time is the time of its own top-level events. An event whose
instruction the map does not hold, or holds without a layer, is `unscoped`.
Times are a step's: the window's sum over the executions of the program
on the `XLA Modules` line of the same window.

Against a program that has no such map (a commit before the scopes) every
function here returns None.
"""

from __future__ import annotations

import json
import re

UNSCOPED = "unscoped"
# `name=` of the flash attention kernels' three `pallas_call`s
KERNELS = ("flash_mla_fwd", "flash_mla_bwd_dq", "flash_mla_bwd_dkv")
_NAME = re.compile(r"^\s*%?([^\s=]+)\s*=")


def instruction_name(op_text: str) -> str:
    """`%fusion.55 = bf16[...] fusion(...)` -> `fusion.55`."""
    m = _NAME.match(op_text)
    return m.group(1) if m else op_text.strip().lstrip("%")


def device_scopes_of(obs: dict):
    """The program's instruction map for the traced train step, kept on
    `obs` (`program_scopes` compiles the step again, once, after the
    window); None where the program offers none."""
    if "device_scopes" not in obs:
        scopes = None
        if obs.get("trace") is not None and obs.get("train_step_module"):
            from solvingpapers_tpu.metrics import hlo_cost

            program_scopes = getattr(hlo_cost, "program_scopes", None)
            if program_scopes is not None:
                scopes = program_scopes(obs["train_step_module"])
        obs["device_scopes"] = scopes
    return obs["device_scopes"]


def layer_ms(obs: dict) -> dict[str, float] | None:
    """{layer scope or "unscoped": device ms a step}, computed once and
    kept on `obs` beside `layer_pass_ms` ({"<scope>/<pass>": ms})."""
    if "layer_ms" not in obs:
        obs["layer_ms"], obs["layer_pass_ms"] = _reduce(obs)
    return obs["layer_ms"]


def _reduce(obs: dict):
    tr, module = obs.get("trace"), obs.get("train_step_module")
    scopes = device_scopes_of(obs)
    if tr is None or not module or not scopes:
        return None, None
    n_exec = sum(len(durs) for name, durs in tr.modules.items()
                 if name.startswith(module + "("))
    if n_exec == 0:
        return None, None
    by_layer: dict[str, float] = {}
    by_pass: dict[str, float] = {}
    rows, inside = [], []  # (ms, instruction, layer, pass)
    for text, durs in tr.ops.items():
        name = instruction_name(text)
        layer, pass_, top_level = scopes.get(name, (None, "fwd", True))
        ms = 1e3 * sum(durs) / n_exec
        key = layer or UNSCOPED
        if not top_level:
            # inside the event of the loop or branch that holds it
            inside.append((ms, name, key, pass_))
            continue
        by_layer[key] = by_layer.get(key, 0.0) + ms
        by_pass[f"{key}/{pass_}"] = by_pass.get(f"{key}/{pass_}", 0.0) + ms
        rows.append((ms, name, key, pass_))

    def heaviest(found, n):
        return [[name, key, pass_, round(ms, 4)]
                for ms, name, key, pass_ in sorted(found, reverse=True)[:n]]

    # a line of detail before the result line: the breakdown as a person
    # reads it, and the layer of each of the heaviest instructions
    print(json.dumps({
        "layer_ms": by_layer, "layer_pass_ms": by_pass,
        "train_step_executions": n_exec,
        "top_level_ms": sum(by_layer.values()),
        "heaviest": heaviest(rows, 24),
        "heaviest_inside_loops": heaviest(inside, 6),
    }), flush=True)
    return by_layer, by_pass


def sum_ms(obs: dict, scopes: tuple[str, ...]) -> float | None:
    """Device ms a step of the layers `scopes` together; None where the
    trace holds none of them."""
    table = layer_ms(obs)
    if table is None:
        return None
    found = [table[s] for s in scopes if s in table]
    return sum(found) if found else None
