#!/usr/bin/env python3
"""The control of a cell's correctness check, run by hand on the chip at the
cell's own size:

    python benchmarks/control.py --workload <name> --seeds 1 2 3

For each seed it prints the numbers that `correct` compares, read from the
control (the plain reference in the precision below the configuration's, see
the reference's docstring) in the program's place. The limits in the
configuration's file have to lie under the smallest of these for at least
one number of the cell. The benchmark's own runs never run it; the same
function runs at a tiny size in `benchmarks/tests/`.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--quant", default="int8")
    args = ap.parse_args(argv)
    bench, cell, conf = harness.find_cell(args.workload)
    config = harness.load_json(ROOT, conf["file"])
    traffic = harness.load_json(harness.HERE, "traffic",
                                cell["traffic"] + ".json")
    harness.configure_jax()
    device, _ = harness.device_record(cell["chips"])
    driver = harness.load_module("drivers", traffic["driver"])
    for seed in args.seeds:
        got = driver.control_readings(config, traffic, seed, args.quant)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "quant": args.quant, "device": device, **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
