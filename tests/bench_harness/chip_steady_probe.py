#!/usr/bin/env python3
"""Where a cell's run-to-run spread comes from, read on the chip: every
query's latency, its CPU and kernel seconds on the calling thread, its
involuntary context switches and the seconds the interpreter's collector
took inside it, by generation — how ``PERF.md`` section 2 read
``tpch_q01_sf1``'s steadiness and found the allocator's trim inside the
window of a run that compiled (PR 29).  Not a cell: nothing in
``BENCHMARK.json`` names it and the driver never runs it.

    python3 tests/bench_harness/chip_steady_probe.py --workload <cell> \\
        --seed <n> --queries <n> --phases as_is,frozen,off \\
        [--trim 1] [--stacks 6,7,8]

One process, the cell and one warm-up query as ``bench/run.py`` makes
them (without its ``trim_heap``: ``--trim 1`` is that call, timed), then
one closed loop of ``--queries`` queries a phase: ``as_is`` (the
collector as the interpreter starts it), ``frozen`` (``gc.collect();
gc.freeze()`` first: what set-up made is never traversed again), ``off``
(``gc.disable()``).  ``--stacks`` samples the calling thread's stack
every 50 ms in the queries at those places of a phase.  With
``JAX_COMPILATION_CACHE_DIR`` at an empty directory the process compiles,
as each side's first run of a check does.  Prints one JSON object: the
phases, each with its queries.
"""

import argparse
import gc
import json
import os
import resource
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402


class Sampler(threading.Thread):
    """Where the calling thread is, every 50 ms, in the queries whose
    place in the loop is in ``which``: its innermost frames, counted."""

    def __init__(self, which):
        super().__init__(daemon=True)
        self.which, self.main = which, threading.get_ident()
        self.query, self.stacks, self.done = None, {}, False

    def run(self):
        while not self.done:
            time.sleep(0.05)
            i = self.query
            if i in self.which:
                frame = sys._current_frames().get(self.main)
                lines = traceback.format_stack(frame)[-8:]
                key = " | ".join(ln.strip().split("\n")[0] for ln in lines)
                seen = self.stacks.setdefault(str(i), {})
                seen[key] = seen.get(key, 0) + 1


def loop(cell, n, sampler=None):
    """``n`` queries back to back; for each its wall and thread-CPU
    seconds, involuntary switches and the collector's seconds by
    generation."""
    out = []
    collecting = {}
    took = [0.0, 0.0, 0.0]
    runs = [0, 0, 0]

    def on_gc(phase, info):
        if phase == "start":
            collecting["t"] = time.perf_counter()
        else:
            took[info["generation"]] += time.perf_counter() - collecting["t"]
            runs[info["generation"]] += 1

    gc.callbacks.append(on_gc)
    try:
        for _ in range(n):
            took[:] = [0.0, 0.0, 0.0]
            runs[:] = [0, 0, 0]
            use0 = resource.getrusage(resource.RUSAGE_THREAD)
            sw0 = use0.ru_nivcsw
            c0, t0 = time.thread_time(), time.perf_counter()
            if sampler:
                sampler.query = len(out)
            cell.query()
            wall, cpu = time.perf_counter() - t0, time.thread_time() - c0
            if sampler:
                sampler.query = None
            use = resource.getrusage(resource.RUSAGE_THREAD)
            out.append({"wall_s": wall, "cpu_s": cpu, "sys_s": use.ru_stime - use0.ru_stime,
                        "nivcsw": use.ru_nivcsw - sw0,
                        "gc_s": list(took), "gc_n": list(runs)})
    finally:
        gc.callbacks.remove(on_gc)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--queries", type=int, default=27)
    ap.add_argument("--phases", default="as_is,frozen,off")
    ap.add_argument("--trim", type=int, choices=(0, 1), default=0,
                    help="after the warm-up query, hand the allocator's free pages back (malloc_trim)")
    ap.add_argument("--stacks", default="",
                    help="sample the calling thread's stack in these queries of a phase: 6,7")
    args = ap.parse_args(argv)

    manifest, cell_entry, config, traffic = run.resolve(args.workload)
    stamp = run.device_stamp()
    if missing := run.chips_missing(stamp, cell_entry):
        run.log("no result: " + missing)
        return 2
    from blaze_tpu.runtime.kernel_cache import enable_persistent_cache

    enable_persistent_cache()
    cell = run.Cell(config, traffic, args.seed)
    cell.query()
    trim_s = None
    if args.trim:
        import ctypes

        t0 = time.perf_counter()
        ctypes.CDLL("libc.so.6").malloc_trim(0)
        trim_s = time.perf_counter() - t0
    out = {"workload": args.workload, "seed": args.seed, "device": stamp, "malloc_trim_s": trim_s,
           "gc_threshold": gc.get_threshold(), "tracked_after_setup": len(gc.get_objects()),
           "gc_stats_after_setup": gc.get_stats(),
           "phases": {}}
    for phase in args.phases.split(","):
        if phase == "frozen":
            t0 = time.perf_counter()
            gc.collect()
            gc.freeze()
            out["collect_and_freeze_s"] = time.perf_counter() - t0
        elif phase == "off":
            gc.disable()
        elif phase != "as_is":
            raise SystemExit(f"no phase {phase!r}")
        sampler = Sampler({int(i) for i in args.stacks.split(",")}) if args.stacks else None
        if sampler:
            sampler.start()
        out["phases"][phase] = loop(cell, args.queries, sampler)
        if sampler:
            sampler.done = True
            sampler.join()
            out.setdefault("stacks", {})[phase] = sampler.stacks
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
