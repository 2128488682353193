#!/usr/bin/env python3
"""The two readings that q7's ``TOLERANCE`` on ``agg1`` lies between
(``PERF.md`` section 2), read on the chip at the cell's own size: for
each seed the largest relative error of the program's ``agg1`` against
the integer reference and the float32 control's, and the largest
distance of ``agg2..4`` in units of the sixth digit (limit 0).  Not a
cell: nothing in ``BENCHMARK.json`` names it and the driver never runs it.

    python3 tests/bench_harness/chip_q7_reading_probe.py --seeds 1,2,3
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

CELL = "tpcds_q07_sf1"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    _, cell_entry, config, traffic = run.resolve(CELL)
    stamp = run.device_stamp()
    if missing := run.chips_missing(stamp, cell_entry):
        run.log("no reading: " + missing)
        return 2
    from blaze_tpu.runtime.kernel_cache import enable_persistent_cache

    enable_persistent_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = run.Cell(config, traffic, seed)
        got, _ = cell.query()
        cell.release()
        expected = cell.module.oracle(cell.tables)
        control = cell.module.control(cell.tables)

        def readings(result):
            return {
                "agg1_max_rel": max(abs(a - b) / abs(b) for a, b in zip(result["agg1"], expected["agg1"])),
                "agg1_cells_not_bit_equal": sum(a != b for a, b in zip(result["agg1"], expected["agg1"])),
                "decimal_max_units": max(abs(a - b) for c in ("agg2", "agg3", "agg4")
                                         for a, b in zip(result[c], expected[c])),
            }

        print(json.dumps({"workload": CELL, "seed": seed, "device": stamp,
                          "same_groups": got["i_item_id"] == expected["i_item_id"],
                          "program": readings(got), "control": readings(control),
                          "tolerance": cell.module.TOLERANCE["agg1"]["rel"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
