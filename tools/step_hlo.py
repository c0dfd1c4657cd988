#!/usr/bin/env python3
"""Compile a train cell's whole step for a DESCRIBED v5e on the CPU and
write its optimised HLO, so that two trees can be compared before the chip
is asked (PERF.md 7 (i); the recipe PRs 40-46 each rebuilt by hand).

    JAX_PLATFORMS=cpu python tools/step_hlo.py --out <dir> [--cells a b ...]
    python tools/step_hlo.py --diff <dir of one tree> <dir of the other>

The first form runs from the root of the tree it describes (it imports that
tree's `solvingpapers_tpu` and `benchmarks`), one cell after the other:
the cell's `RunConfig` as `benchmarks/drivers/train_job.py` builds it, a
`Trainer` on the first described device, the state abstract
(`jax.eval_shape`), `jax.devices` / `jax.device_count` patched so that every
kernel takes its TPU branch, `jit_train_step` lowered on shapes and
compiled. It writes `<dir>/<cell>.hlo` with what differs between two
checkouts of one program stripped (the source locations in `metadata`,
whose `op_name`, the scopes, stays; the Mosaic calls' `backend_config`,
which holds the kernel bodies' source locations; the file tables), and
`<dir>/<cell>.json` with the compiler's memory count and the Mosaic calls
by kernel name. Nothing is run, no array is made; a compile that
passes is not a run, and no number here is a device metric.

The second form prints, cell by cell, the count of lines and of differing
lines; it exits 1 if any cell differs.
"""

import argparse
import collections
import difflib
import json
import os
import pathlib
import re
import sys

ROOT = pathlib.Path.cwd()

# of an instruction's metadata the scopes stay (`op_name`: the per-layer
# readers go by it), the source location goes
_STRIP = [
    (re.compile(r' (source_file|source_line|source_end_line|source_column|'
                r'source_end_column|stack_frame_id)=("[^"]*"|\d+)'), ""),
    (re.compile(r', backend_config="[^"]*"'), ""),
    (re.compile(r", backend_config=\{.*\}(?=[,)]|$)"), ""),
]
_MOSAIC = re.compile(
    r'%([A-Za-z_0-9]+?)(?:\.\d+)? = .*custom_call_target="tpu_custom_call"')


def stripped(text: str) -> list[str]:
    lines = []
    for line in text.splitlines():
        if line.startswith(("FileNames", "FunctionNames", "FileLocations",
                            "StackFrames")) or re.match(r"^\d+ ", line):
            continue  # the file tables behind the module
        for pattern, to in _STRIP:
            line = pattern.sub(to, line)
        lines.append(line)
    return lines


def compile_cell(workload: str, out: pathlib.Path) -> dict:
    import unittest.mock as mock

    import jax
    import numpy as np
    from jax.experimental import topologies

    # a persistent-cache hit would hand back another checkout's `op_name`s
    jax.config.update("jax_enable_compilation_cache", False)
    from benchmarks import harness
    from benchmarks.drivers.train_job import run_config
    from solvingpapers_tpu.configs.factory import (
        build_char_lm_run, init_fn_for, loss_fn_for, rules_for,
    )
    from solvingpapers_tpu.sharding import batch_sharding, create_mesh
    from solvingpapers_tpu.train import Trainer

    _, cell, conf = harness.find_cell(workload)
    config = harness.load_json(str(ROOT), conf["file"])
    traffic = harness.load_json(harness.HERE, "traffic",
                                cell["traffic"] + ".json")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices[:cell["chips"]])
    cfg = run_config(config, traffic, seed=1)
    with mock.patch.object(jax, "devices", lambda *a: devices), \
            mock.patch.object(jax, "device_count", lambda *a: len(devices)):
        mesh = create_mesh(cfg.train.mesh, devices=devices)
        cfg, model, _, _, _ = build_char_lm_run(
            cfg, sharding=batch_sharding(mesh))
        trainer = Trainer(model, cfg.train, loss_fn=loss_fn_for(cfg),
                          init_fn=init_fn_for(cfg), mesh=mesh,
                          rules=rules_for(cfg))
        shape = (cfg.train.batch_size, model.cfg.block_size)
        example = {k: np.zeros(shape, np.int32) for k in ("x", "y")}
        # `init_state` as far as its shapes: what it would jit is never run
        with mock.patch.object(jax, "jit", _abstract_init(jax.jit)), \
                mock.patch.object(jax, "block_until_ready", lambda x: x):
            state = trainer.init_state(example)
        trainer._build_steps()
        batch = {k: jax.ShapeDtypeStruct(shape, np.int32,
                                         sharding=trainer._batch_shardings[k])
                 for k in example}
        compiled = trainer._train_step.lower(state, batch).compile()
    text = compiled.as_text()
    (out / f"{workload}.hlo").write_text("\n".join(stripped(text)) + "\n")
    mem = compiled.memory_analysis()
    calls = collections.Counter(_MOSAIC.findall(text))
    record = {
        "workload": workload,
        "lines": len(stripped(text)),
        "argument_gib": mem.argument_size_in_bytes / 2**30,
        "temp_gib": mem.temp_size_in_bytes / 2**30,
        "code_gib": mem.generated_code_size_in_bytes / 2**30,
        "mosaic_calls": dict(sorted(calls.items())),
    }
    (out / f"{workload}.json").write_text(json.dumps(record, indent=1) + "\n")
    return record


def _abstract_init(real_jit):
    """A stand-in for `jax.jit` inside `Trainer.init_state`: the jitted
    initialiser (`make`) returns the state's shapes, each with the sharding
    the trainer chose for it; any other function is jitted as ever."""
    import jax

    def jit(fun, **kw):
        if fun.__name__ != "make":
            return real_jit(fun, **kw)
        return lambda *args: jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            jax.eval_shape(fun, *args), kw["out_shardings"])

    return jit


def diff(a: pathlib.Path, b: pathlib.Path) -> int:
    differing = 0
    for left in sorted(a.glob("*.hlo")):
        right = b / left.name
        if not right.is_file():
            print(f"{left.stem}: only in {a}")
            differing += 1
            continue
        la, lb = left.read_text().splitlines(), right.read_text().splitlines()
        n = sum(1 for line in difflib.unified_diff(la, lb, lineterm="", n=0)
                if line[:1] in "+-" and line[:3] not in ("+++", "---"))
        print(f"{left.stem}: {len(la)} and {len(lb)} lines, {n} differing")
        differing += bool(n)
    return 1 if differing else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", type=pathlib.Path)
    ap.add_argument("--cells", nargs="*")
    ap.add_argument("--diff", nargs=2, type=pathlib.Path)
    args = ap.parse_args(argv)
    if args.diff:
        return diff(*args.diff)
    if args.out is None:
        ap.error("--out or --diff")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    args.out.mkdir(parents=True, exist_ok=True)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = args.cells or [w["name"] for w in bench["workloads"]]
    for workload in cells:
        print(json.dumps(compile_cell(workload, args.out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
