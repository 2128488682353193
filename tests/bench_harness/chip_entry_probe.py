#!/usr/bin/env python3
"""A cell's own configuration, traffic and size read on the chip through
another entry than its configuration states — how ``PERF.md`` section 4
read TPC-H q6 at SF1 through ``entry: catalyst`` (PR 29).  Not a cell:
nothing in ``BENCHMARK.json`` names it and the driver never runs it.

    python3 tests/bench_harness/chip_entry_probe.py --entry catalyst \\
        --workload tpch_q06_sf1 --seed <n> --seconds <s> --trace <0|1>

Everything but ``--entry`` is ``bench/run.py``'s, and so is what it
prints: no result without a TPU, the per-layer metrics of ``--workload``.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--entry", required=True)
    args, rest = ap.parse_known_args(argv)
    resolve = run.resolve

    def with_entry(cell_name):
        manifest, cell, config, traffic = resolve(cell_name)
        return manifest, cell, dict(config, entry=args.entry), traffic

    run.resolve = with_entry
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
