#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process on one chip.  It resolves the cell by name alone —
``BENCHMARK.json`` for the cell's configuration and traffic,
``bench/configs/<configuration>.json`` (its ``suite``, ``schema`` and
``entry``), ``bench/traffic/<traffic>.json``,
``bench/suites/<suite>/<query>.py`` for the columns, the reference, its
control and a float column's ``TOLERANCE``, ``bench/entries/<entry>.py``
for how the plan arrives, ``bench/metrics/<metric>.py`` for each
per-layer reader — and holds no suite's name itself.  So a later PR adds
a cell, a configuration or a metric with new files and new
``BENCHMARK.json`` entries only.  For a new suite those are:
``bench/suites/<suite>/`` (``datagen.py`` with ``generate_table(table,
scale, seed, columns)``, ``schema.json``, ``<query>.py`` and, where the
entry is ``catalyst``, ``<query>.plan.json``), a configuration with
``suite``, ``schema`` and ``entry``, a traffic file, and the manifest's
entries; the program's side is ``blaze_tpu.<suite>`` with its
``<SUITE>_SCHEMAS`` and ``build_query``.

The timed path is the one ``chip_smoke.py`` proved on the chip: for every
query a fresh plan from the configuration's entry (``builder``: the
program's ``build_query``; ``catalyst``: a catalyst ``toJSON`` dump
through ``BlazeSparkSession.plan``) -> ``scheduler.split_stages`` ->
``run_stages(..., max_task_attempts=1)`` (every stage decoded from
TaskDefinition bytes, no worker pool) -> ``batch_to_pydict`` of every
result batch, which is the D2H.  Traffic is a closed loop: the next
query starts when the last one's final batch is out.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  The last line of stdout is the result.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse
import glob
import importlib
import json
import math
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import compare as compare_mod  # noqa: E402
from bench import least_bytes as least_bytes_mod  # noqa: E402
from bench import trace_reduce  # noqa: E402

#: the host spans by which the trace names an idle gap: the window's,
#: the harness's two around a query's ends, and the program's leaf spans
#: between them (``runtime/trace.span``, PR 27)
SPANS = ("bench_query", "plan", "d2h", "blaze:task_decode", "blaze:scan_stage",
         "blaze:device_read", "blaze:exchange_read")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def read_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def resolve(cell_name):
    """The cell's manifest entry, configuration and traffic, by name."""
    manifest = read_json("BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if cell_name not in cells:
        raise SystemExit(f"no workload {cell_name!r} in BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[cell_name]
    config = read_json("bench", "configs", cell["config"] + ".json")
    traffic = read_json("bench", "traffic", cell["traffic"] + ".json")
    return manifest, cell, config, traffic


def metric_readers(manifest, cell_name):
    """name -> reader module, for the per-layer metrics this cell reports."""
    out = {}
    for m in manifest["per_layer"]:
        if cell_name in m.get("workloads", [cell_name]):
            out[m["name"]] = importlib.import_module("bench.metrics." + m["name"])
    return out


def device_stamp():
    import jax

    import blaze_tpu  # noqa: F401 — before the first array: turns x64 on

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def chips_missing(stamp, cell):
    """Why this machine cannot measure the cell, or None where it can."""
    if stamp["platform"] != "tpu" or stamp["count"] < cell["chips"]:
        return f"the cell needs {cell['chips']} TPU chip(s), JAX found {stamp}"
    return None


def trim_heap():
    """Hands the allocator's free pages back to the system (glibc's
    ``malloc_trim``; nothing where the C library has none).  A run that
    compiled holds the compiler's freed heap, and glibc returns it the
    first time the top of the heap comes free, in one call under the GIL:
    1.7 s inside the 8th query of every q01 window that followed a
    compile (``PERF.md`` section 2).  Called at the end of set-up, it is
    set-up's."""
    import ctypes

    trim = getattr(ctypes.CDLL(None), "malloc_trim", None)
    if trim is not None:
        trim(0)


def nearest_rank(values, q):
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


class Cell:
    """One cell, set up: the seeded host tables, the scans over them,
    the query's module and the entry's plan source.  ``query()`` is the
    timed path."""

    def __init__(self, config, traffic, seed, marks=None):
        from bench import entries

        suite = config["suite"]
        self.query_name = traffic["query"]
        self.module = importlib.import_module(f"bench.suites.{suite}.{self.query_name}")
        datagen = importlib.import_module(f"bench.suites.{suite}.datagen")
        self.tables = {
            t: datagen.generate_table(t, config["scale"], seed, cols)
            for t, cols in self.module.COLUMNS.items()
        }
        self.rows = {t: int(next(iter(tab.values()))[0].shape[0]) for t, tab in self.tables.items()}
        if marks is not None:
            marks.append(("datagen", time.perf_counter()))
        scans = entries.memory_scans(suite, self.tables, self.module.COLUMNS,
                                     config["partitions"], config["batch_rows"])
        entry = importlib.import_module("bench.entries." + config["entry"])
        self.plan = entry.source(suite, self.query_name, scans, config["partitions"])
        if marks is not None:
            marks.append(("host_batches", time.perf_counter()))

    def release(self):
        """Drops the scans: the program's state goes before the reference runs."""
        self.plan = None

    def query(self):
        """One query through the scheduler path.  Returns (result as
        python values, seconds in planning)."""
        import jax

        from blaze_tpu.batch import batch_to_pydict
        from blaze_tpu.runtime.scheduler import run_stages, split_stages

        span = jax.profiler.TraceAnnotation
        t0 = time.perf_counter()
        with span("plan"):
            # a fresh plan per query: exchanges memoize their map side
            # per exec instance, so a reused plan would skip the maps
            plan = self.plan()
            stages, manager = split_stages(plan)
        plan_s = time.perf_counter() - t0
        got = {f.name: [] for f in plan.schema.fields}
        # one attempt per task: a retry would hide a first failure
        batches = iter(run_stages(stages, manager, max_task_attempts=1))
        while True:
            with span("run_stages"):
                b = next(batches, None)
            if b is None:
                break
            with span("d2h"):
                for k, v in batch_to_pydict(b).items():
                    got[k].extend(v)
        return got, plan_s


def run_window(cell, seconds, trace_queries=0, trace_dir=None):
    """The closed loop: queries back to back, a new one only while less
    than ``seconds`` has passed; the window ends when the last one
    started completes.  With ``trace_queries`` the profiler runs over
    that many whole queries, after the first one of the window."""
    import jax

    from blaze_tpu.runtime import dispatch

    results, latencies, plan_s = [], [], []
    failed = 0
    tracing, traced = False, 0
    with dispatch.capture() as counters:
        t_open = time.perf_counter()
        while True:
            now = time.perf_counter()
            if now - t_open >= seconds:
                break
            if trace_queries and len(results) == 1:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # the harness's spans, not every frame
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                tracing = True
                now = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench_query"):
                    got, planned = cell.query()
                plan_s.append(planned)
            except Exception as e:  # the loop outlives one query; it counts as failed
                log(f"query failed: {type(e).__name__}: {e}"[:2000])
                got = None
                failed += 1
            latencies.append(time.perf_counter() - now)
            results.append(got)
            if tracing:
                traced += 1
                if traced >= trace_queries:
                    jax.profiler.stop_trace()
                    tracing = False
        window_s = time.perf_counter() - t_open
        if tracing:
            jax.profiler.stop_trace()
    return {"results": results, "latencies": latencies, "plan_s": plan_s, "failed": failed,
            "window_s": window_s, "counters": dict(counters)}


def measure(cell_name, manifest, config, traffic, seed, seconds, trace, stamp):
    """Everything after the look for a chip: set-up, the window, the
    comparison.  Returns the result object."""
    import jax

    from blaze_tpu.runtime.kernel_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    marks = [("start", time.perf_counter())]
    cell = Cell(config, traffic, seed, marks)
    try:
        cell.query()  # warm-up: compiles, or loads every program from the cache
    except Exception as e:  # the window's queries will fail too, and count
        log(f"warm-up query failed: {type(e).__name__}: {e}"[:2000])
    marks.append(("first_query", time.perf_counter()))
    trim_heap()  # what compiling left behind goes now, not inside the window
    marks.append(("heap_trim", time.perf_counter()))
    setup_s = marks[-1][1] - T_START
    setup_parts = {"imports_and_device": marks[0][1] - T_START}
    setup_parts.update({name: t - before for (_, before), (name, t) in zip(marks, marks[1:])})
    log(f"set-up {setup_s:.2f} s: {setup_parts}; rows {cell.rows}; cache {cache_dir}")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        window = run_window(cell, seconds, traffic["traced_queries"] if trace else 0, trace_dir)
        reduced = None
        if trace:
            found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
            reduced = trace_reduce.reduce_file(found[0], SPANS) if found else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    stats = jax.devices()[0].memory_stats() or {}
    device = dict(stamp, memory_peak_bytes=stats.get("peak_bytes_in_use"))
    cell.release()

    t_ref = time.perf_counter()
    expected = cell.module.oracle(cell.tables)
    compared, correct = compare_mod.compare(window["results"], expected, cell.module.canonical,
                                            getattr(cell.module, "TOLERANCE", None))
    reference_s = time.perf_counter() - t_ref

    n = len(window["results"])
    done = n - window["failed"]
    metrics = {}
    if not trace:
        metrics["query_s"] = {"value": window["window_s"] / max(done, 1), "unit": "s"}
        metrics["query_p95_s"] = {"value": nearest_rank(window["latencies"], 0.95), "unit": "s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    else:
        schema = read_json(*config["schema"].split("/"))
        peaks = read_json("bench", "peaks.json")["devices"]
        if stamp["kind"] not in peaks:
            raise SystemExit(f"no peak known for device kind {stamp['kind']!r}")
        run = {
            "queries": done,
            "counters": window["counters"],
            "plan_s": window["plan_s"],
            "trace": reduced,
            "memory_peak_bytes": device["memory_peak_bytes"],
            "least_bytes": least_bytes_mod.least_bytes(schema["tables"], cell.module.COLUMNS, cell.rows),
            "peak": peaks[stamp["kind"]],
        }
        units = {m["name"]: m["unit"] for m in manifest["per_layer"]}
        for name, reader in metric_readers(manifest, cell_name).items():
            value = reader.read(run)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]

    out = {"correct": bool(correct), "attempted": n, "failed": window["failed"],
           "metrics": metrics, "device": device}
    if reduced:
        out["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                            "idle_gaps": reduced["idle_gaps"][:10]}
    out["info"] = {"workload": cell_name, "seed": seed, "seconds": seconds,
                   "window_s": window["window_s"], "rows": cell.rows,
                   "latency_s": {"min": min(window["latencies"]),
                                 "median": nearest_rank(window["latencies"], 0.5),
                                 "max": max(window["latencies"]),
                                 "each": window["latencies"]},
                   "setup_parts": setup_parts, "reference_s": reference_s, "cache_dir": cache_dir,
                   "counters": window["counters"]}
    if reduced:
        # all the gaps by the span that names them, not the ten largest alone
        by_span = out["info"]["idle_by_span_s"] = {}
        for name, gap_s in reduced["idle_gaps"]:
            span = name.split(">")[0]
            by_span[span] = by_span.get(span, 0.0) + gap_s
    out["compared"] = compared  # last in the line, as the last lines of stderr too
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest, cell, config, traffic = resolve(args.workload)
    stamp = device_stamp()
    if missing := chips_missing(stamp, cell):
        log("no result: " + missing)
        return 2
    out = measure(args.workload, manifest, config, traffic, args.seed, args.seconds,
                  args.trace, stamp)
    for name, c in out["compared"].items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
