#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (build, weights from the seed, warm-up of the cell's own shapes), a
measured window of `--seconds` seconds, then the comparison with the plain
reference. The last line of standard output is the result as one JSON
object: with `--trace 0` the cell's end-to-end metrics, with `--trace 1` its
per-layer metrics, read from a profiler trace of a window of its own
(`trace_seconds` of the traffic file, at most `--seconds`). Needs a TPU:
with no accelerator, too few chips or a device that `peaks.json` does not
know, it exits non-zero and prints no result.

The cell's files are found by the names in BENCHMARK.json:
`configs/<config>.json`, `traffic/<traffic>.json` (which names its
`drivers/<driver>.py`), one `metrics/<metric>.py` per metric.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], required=True)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    bench, cell, conf = harness.find_cell(args.workload)
    config = harness.load_json(ROOT, conf["file"])
    traffic = harness.load_json(harness.HERE, "traffic",
                                cell["traffic"] + ".json")
    harness.configure_jax()
    device, peaks = harness.device_record(cell["chips"])
    run = harness.Run(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, bench=bench, cell=cell,
        config=config, traffic=traffic, device=device, peaks=peaks)
    run.watch_compiles()
    run.phase("imports_and_device")
    harness.load_module("drivers", traffic["driver"]).run(run)
    run.compile_summary()
    if run.setup_s is None or not run.checks:
        raise harness.BenchFailure(
            f"driver {traffic['driver']!r} measured no window or compared "
            "nothing with the reference")
    print(json.dumps(run.result()), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.BenchFailure as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        sys.exit(1)
