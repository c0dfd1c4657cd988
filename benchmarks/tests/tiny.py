"""A cell at a size the CPU holds: the cell's own files with the model cut
down in memory, the harness's look for a chip skipped, everything else as
in a real run."""

from __future__ import annotations

import time

from benchmarks import harness

TINY_MODEL = dict(vocab_size=256, block_size=32, dim=64, n_layers=2,
                  n_heads=2, latent_dim=16, n_experts=4)


def tiny_files(workload: str):
    bench, cell, conf = harness.find_cell(workload)
    config = harness.load_json(harness.ROOT, conf["file"])
    cut = dict(TINY_MODEL)
    if config["model"]["rope_dim"]:
        cut["rope_dim"] = 8
    if config["model"]["use_flash"]:
        cut["use_flash"] = False  # the kernel's interpreter is too slow here
    config["model"].update(cut)
    config["reduced"] = sorted(set(config["reduced"]) | set(cut))
    traffic = harness.load_json(harness.HERE, "traffic",
                                cell["traffic"] + ".json")
    if "engine" in traffic:  # a serving mix: short requests, four slots
        cut.update(block_size=64)
        config["model"].update(block_size=64)
        traffic["prompt_len"].update(median=12, min=4, max=24)
        traffic["output_len"].update(median=12, min=4, max=24)
        traffic["engine"].update(n_slots=4, max_len=64, bucket=8,
                                 decode_block=4)
        traffic.update(rate_rps=20.0, callers=4, check_requests=4)
    else:
        traffic.update(corpus_tokens=20000, reference_q_block=16,
                       batch_size=min(traffic["batch_size"], 4))
    return bench, cell, config, traffic


def tiny_run(workload: str, *, seed: int = 2**31 + 11, seconds: float = 1.0,
             trace: bool = False, limits: dict | None = None) -> harness.Run:
    """Drive the cell's driver once; returns the Run (its `.result()` is
    the line a real run prints)."""
    bench, cell, config, traffic = tiny_files(workload)
    if limits:
        for group, vals in limits.items():
            config["limits"][group].update(vals)
    run = harness.Run(
        workload=workload, seed=seed, seconds=seconds, trace=trace,
        t_start=time.perf_counter(), bench=bench, cell=cell, config=config,
        traffic=traffic,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        peaks=harness.peaks_for("TPU v5 lite"))
    run.watch_compiles()
    harness.load_module("drivers", traffic["driver"]).run(run)
    return run
