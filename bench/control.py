#!/usr/bin/env python3
"""Readings that the limits of ``correct`` were set from (``PERF.md``):
for each seed, in one process on the chip, the program's numbers after
a short window of the cell's own traffic at the cell's own size, and
the control's — the reference carried in float32, put in the program's
place.  The benchmark's own runs never run this.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 5]

One JSON line per seed; the last line sums up: the largest reading of
the program (the lower reading) and the smallest of the control (the
upper reading) for every number compared.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import compare, run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    _, cell_entry, config, traffic = run.resolve(args.workload)
    stamp = run.device_stamp()
    if missing := run.chips_missing(stamp, cell_entry):
        run.log("no reading: " + missing)
        return 2
    from blaze_tpu.runtime.kernel_cache import enable_persistent_cache

    enable_persistent_cache()
    lower, upper = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = run.Cell(config, traffic, seed)
        window = run.run_window(cell, args.seconds)
        cell.release()
        expected = cell.module.oracle(cell.tables)
        tolerance = getattr(cell.module, "TOLERANCE", None)  # the control is held to it too
        program, ok = compare.compare(window["results"], expected, cell.module.canonical, tolerance)
        control, control_ok = compare.compare([cell.module.control(cell.tables)], expected,
                                              cell.module.canonical, tolerance)
        for k in program:
            lower[k] = max(lower.get(k, 0), program[k]["value"])
            upper[k] = min(upper.get(k, float("inf")), control[k]["value"])
        print(json.dumps({"workload": args.workload, "seed": seed, "queries": len(window["results"]),
                          "program_correct": ok, "program": {k: v["value"] for k, v in program.items()},
                          "control_correct": control_ok,
                          "control": {k: v["value"] for k, v in control.items()}}), flush=True)
    print(json.dumps({"workload": args.workload, "device": stamp, "lower_reading": lower,
                      "upper_reading": upper, "limits": compare.LIMITS}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
