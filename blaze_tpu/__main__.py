"""Command-line runner: execute TPC-H / TPC-DS queries through the
engine from a shell.

≙ the reference's benchmark tooling (``dev/run-tpcds-test`` +
``tpcds/benchmark-runner`` — spark-submit launchers around the same
query set, ``tpcds/README.md:1-52``), sized for this engine: datagen
at the requested scale, plan build, execution either in-process or
through the stage scheduler (every task crossing TaskDefinition
protobuf bytes + shuffle files), wall-clock per query, and an optional
row-count/total printout.

Usage:
    python -m blaze_tpu tpch q6 q1 --scale 0.05
    python -m blaze_tpu tpcds q36 --scale 0.002 --parts 4 --scheduler
    python -m blaze_tpu tpch all --scale 0.01
    python -m blaze_tpu --warmup            # compile-cache pre-warm + gate
    python -m blaze_tpu --lint              # static analysis; nonzero on finding
    python -m blaze_tpu --lint --json -     # + machine-readable findings
    python -m blaze_tpu --lint --sarif -    # + SARIF 2.1.0 for code-scanning
    python -m blaze_tpu tpch q1 --explain   # EXPLAIN ANALYZE (runtime/perf.py)
    python -m blaze_tpu --chaos             # seeded fault-injection smoke
                                            #  (+ plan verifier + lock-order
                                            #   + lockset checker armed)
    python -m blaze_tpu tpch q1 --chaos --chaos-seed 42
    python -m blaze_tpu --chaos-seeds 3    # seeded sweep; seed 1 also arms
                                           #  speculation vs. a straggler
    python -m blaze_tpu tpch q1 --scheduler --trace   # write an event log
    python -m blaze_tpu --report <eventlog.jsonl>     # render the profile
    python -m blaze_tpu --report <log> --json out.json  # + JSON profile
    python -m blaze_tpu --serve [--monitor-port N]    # metrics service
    python -m blaze_tpu tpch q1 --scheduler --monitor # live-registry run
    python -m blaze_tpu --watch [URL|PORT]            # live progress table

``--serve`` / ``--monitor`` arm the live monitoring subsystem
(runtime/monitor.py, conf ``spark.blaze.monitor.enabled`` /
``.port`` / ``.heartbeatMs``): a background HTTP server exposes
``/metrics`` (Prometheus text exposition from the scheduler MetricNode
tree + dispatch counters) and ``/queries`` (per-query -> per-stage live
state fed by progress heartbeats), and ``--watch`` polls ``/queries``
into a refreshing console table.  Bare ``--serve`` runs the service in
the foreground until interrupted; with queries it serves for the
duration of the run.

``--trace`` arms the structured event log (runtime/trace.py, conf
``spark.blaze.trace.enabled`` / ``spark.blaze.eventLog.dir``): each
query appends lifecycle + kernel-attribution events to its own JSONL
file, and ``--report`` renders the per-query profile (stage timeline,
dispatch-floor vs device-compute split, plan-annotated metrics tree,
recovery timeline).

``--warmup`` populates the kernel and persistent XLA compile caches
(``JAX_COMPILATION_CACHE_DIR``, else ``spark.blaze.xla.cacheDir`` /
BLAZE_XLA_CACHEDIR, else ``<checkout>/.jax_cache``) by running the
listed queries (default q1 q6) twice, fused + pruned exactly as
run_task would, and GATES on the warm run: a second pass that triggers
any fresh XLA compile exits nonzero.  Run once per image so the first
compiles are never paid inside a query; CI pairs it with the
dispatch-budget regression test:

    python -m blaze_tpu --warmup && \
        pytest tests/test_dispatch_budget.py && python -m blaze_tpu --chaos

``--chaos`` is the CI-facing fault-tolerance gate: each query runs
once fault-free through the stage scheduler, then again under a
seed-derived random fault schedule (runtime/faults.py sites:
shuffle fetch/write, task compute) with task retry and fetch-failure
recovery enabled.  Exit is nonzero on any result mismatch or
unrecovered failure, and the recovery counters are printed (including
the per-run ``xla_dispatches`` / ``xla_compiles`` observability).
"""

from __future__ import annotations

import argparse
import sys
import time


def _load_suite(suite: str, names, scale: float, n_parts: int,
                batch_rows: int = 65536):
    """Shared setup for the runner and the chaos gate: resolve the
    query list ('all' expansion + validation) and build per-table
    MemoryScanExec scans over generated data.  Returns
    (build_query, names, scans) or (None, exit_code, None) on a usage
    error."""
    if suite == "tpch":
        from .tpch import TPCH_SCHEMAS as SCHEMAS
        from .tpch import build_query
        from .tpch.datagen import generate_all, table_to_batches
        from .tpch.queries import QUERIES
    else:
        from .tpcds import TPCDS_SCHEMAS as SCHEMAS
        from .tpcds import build_query, generate_all
        from .tpcds.queries import QUERIES
        from .tpch.datagen import table_to_batches

    if names == ["all"]:
        names = sorted(QUERIES)
    unknown = [n for n in names if n not in QUERIES]
    if unknown:
        print(f"unknown {suite} queries: {', '.join(unknown)} "
              f"(available: {', '.join(sorted(QUERIES))})", file=sys.stderr)
        return None, 2, None

    t0 = time.perf_counter()
    data = generate_all(scale)
    from .ops import MemoryScanExec

    scans = {
        name: MemoryScanExec(
            table_to_batches(data[name], SCHEMAS[name], n_parts,
                             batch_rows=batch_rows),
            SCHEMAS[name],
        )
        for name in SCHEMAS
    }
    # stderr: --explain promises a parseable stdout under --json -,
    # and the line is operator chatter either way
    print(f"# datagen scale={scale}: {time.perf_counter() - t0:.2f}s",
          file=sys.stderr)
    return build_query, names, scans


def _run_suite(suite: str, names, scale: float, n_parts: int,
               scheduler: bool) -> int:
    build_query, names, scans = _load_suite(suite, names, scale, n_parts)
    if build_query is None:
        return names

    from .runtime import monitor

    failed = []
    for name in names:
        t0 = time.perf_counter()
        try:
            # combined span: trace event log (when traced) + live
            # registry entry (when the monitor is armed)
            with monitor.query_span(
                    f"{suite}_{name}",
                    mode="scheduler" if scheduler else "in-process",
            ) as log_path:
                plan = build_query(name, scans, n_parts)
                rows = 0
                if scheduler:
                    from .runtime.scheduler import run_stages, split_stages

                    stages, manager = split_stages(plan)
                    for b in run_stages(stages, manager):
                        rows += b.num_rows
                else:
                    # in-process path: same query -> stage span shape
                    # as the scheduler path (one result stage)
                    tally: list = []
                    monitor.drive_result_stage(
                        plan, lambda b: tally.append(b.num_rows))
                    rows = sum(tally)
            dt = time.perf_counter() - t0
            print(f"{suite} {name}: {rows} rows in {dt:.2f}s"
                  + (" [scheduler]" if scheduler else "")
                  + (f" [eventlog: {log_path}]" if log_path else ""))
        except Exception as e:  # noqa: BLE001 — report per query, keep going
            failed.append(name)
            print(f"{suite} {name}: FAILED {type(e).__name__}: {e}",
                  file=sys.stderr)
    if failed:
        print(f"# {len(failed)} failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


def _rows_via_scheduler(plan, manager=None, pool=None):
    """Run a plan through the stage scheduler and collect its output as
    a sorted list of row tuples (order-insensitive comparison key).
    Pass ``manager`` to keep a handle on the shuffle root (the
    corruption storm inspects it for temps/quarantine files) and
    ``pool`` to bind map stages to a worker-host pool (the worker-kill
    storm)."""
    from .batch import batch_to_pydict
    from .runtime.scheduler import run_stages, split_stages

    stages, manager = split_stages(plan, manager)
    cols = None
    for b in run_stages(stages, manager, pool=pool):
        d = batch_to_pydict(b)
        if cols is None:
            cols = {k: [] for k in d}
        for k, v in d.items():
            cols[k].append(v)
    if cols is None:
        return []
    flat = {k: [x for chunk in v for x in chunk] for k, v in cols.items()}
    names = sorted(flat)
    return sorted(zip(*[flat[n] for n in names])) if names else []


def _warmup(suite: str, names, scale: float, n_parts: int) -> int:
    """Pre-warm the persistent XLA compile cache and gate on warm-run
    recompiles (see module docstring).  Two passes per query, each run
    twice (cold + gated warm):

    1. **in-process** — the plan fused/pruned exactly as run_task would;
    2. **scheduler** — the plan split at its exchanges and driven
       through real TaskDefinition bytes (``split_stages``/
       ``run_stages``), so the programs only that path compiles — the
       per-task ShuffleWriterExec wrap, the tier-5 fused shuffle-write
       kernels, the IPC reader decode — are warmed too and a
       scheduler-path warm run sees zero recompiles."""
    from . import conf
    from .runtime import dispatch
    from .runtime.kernel_cache import enable_persistent_cache

    # one function places the cache for every launcher; pool workers of
    # the pooled pass below resolve the same directory (inherited
    # JAX_COMPILATION_CACHE_DIR, the forwarded conf key, or the same
    # in-checkout default)
    print(f"# warmup: persistent XLA cache at {enable_persistent_cache()}")

    build_query, names, scans = _load_suite(suite, names, scale, n_parts)
    if build_query is None:
        return names

    from .ops.fusion import optimize_plan
    from .runtime.context import TaskContext

    def run_once(name):
        plan = optimize_plan(build_query(name, scans, n_parts))
        rows = 0
        for p in range(plan.num_partitions()):
            for b in plan.execute(p, TaskContext(p, plan.num_partitions())):
                rows += b.num_rows
        return rows

    def run_scheduler_once(name):
        from .runtime.scheduler import run_stages, split_stages

        stages, manager = split_stages(build_query(name, scans, n_parts))
        rows = 0
        for b in run_stages(stages, manager):
            rows += b.num_rows
        return rows

    from .runtime import querycache

    failed = []
    unstable = []
    digests = set()
    approx = 0
    for name in names:
        # plan-cache prewarm + fingerprint-stability gate: fingerprint
        # the plan across two INDEPENDENT builds — the serving path
        # keys program reuse and result caching on this digest, so a
        # build-to-build wobble (iteration-order leak, id() in a key)
        # would make both cache levels silently useless
        fps = [querycache.plan_fingerprint(
            optimize_plan(build_query(name, scans, n_parts)))
            for _ in range(2)]
        a, b = fps
        if (a is None) != (b is None) or (
                a is not None and (a.digest != b.digest
                                   or a.exact != b.exact)):
            unstable.append(name)
        elif a is not None:
            digests.add(a.digest)
            approx += 0 if a.exact else 1
        for path, run in (("in-process", run_once),
                          ("scheduler", run_scheduler_once)):
            t0 = time.perf_counter()
            with dispatch.capture() as cold:
                run(name)
            with dispatch.capture() as warm:
                run(name)
            dt = time.perf_counter() - t0
            ok = warm.get("xla_compiles", 0) == 0
            fp_tag = "" if a is None else f" fp={a.digest[:12]}"
            print(f"warmup {suite} {name} [{path}]: "
                  f"cold compiles={cold.get('xla_compiles', 0)} "
                  f"({cold.get('compile_ms', 0)} ms), warm "
                  f"dispatches={warm.get('xla_dispatches', 0)} "
                  f"compiles={warm.get('xla_compiles', 0)}{fp_tag} "
                  f"[{dt:.2f}s]"
                  + ("" if ok else "  <-- RECOMPILED ON WARM RUN"))
            if not ok:
                failed.append(f"{name}[{path}]")

    # 3. **pooled** — cross-process: map stages execute in a real
    #    HostPool worker whose env inherits the cache dir primed above
    #    (hostpool._spawn forwards BLAZE_XLA_CACHEDIR; the worker's
    #    _configure_worker_process points jax at it).  The worker's
    #    telemetry frames carry its dispatch-counter deltas, so the
    #    zero-warm-recompile gate covers the worker PROCESS too — a
    #    cache-key wobble across the process boundary (env leaking into
    #    a kernel key, id() in a cache key) shows up here and nowhere
    #    else.  The pool stays open across cold+warm, so "warm" means:
    #    the SAME worker re-runs the query without a single fresh
    #    compile.
    from .runtime import monitor
    from .runtime.hostpool import HostPool
    from .runtime.scheduler import run_stages, split_stages

    def run_pooled(name, pool):
        stages, manager = split_stages(build_query(name, scans, n_parts))
        rows = 0
        for b in run_stages(stages, manager, pool=pool):
            rows += b.num_rows
        return rows

    def worker_compiles():
        doc = monitor.workers_snapshot() or {}
        return sum(w.get("counters", {}).get("xla_compiles", 0)
                   for w in doc.get("workers", []))

    monitor_prior = bool(conf.MONITOR_ENABLE.get())
    conf.MONITOR_ENABLE.set(True)  # telemetry folding needs the registry
    monitor.reset()
    try:
        with HostPool(1) as pool:
            for name in names:
                t0 = time.perf_counter()
                base = worker_compiles()
                run_pooled(name, pool)
                cold_c = worker_compiles() - base
                run_pooled(name, pool)
                warm_c = worker_compiles() - base - cold_c
                dt = time.perf_counter() - t0
                ok = warm_c == 0
                print(f"warmup {suite} {name} [pooled]: "
                      f"cold worker compiles={cold_c}, "
                      f"warm worker compiles={warm_c} [{dt:.2f}s]"
                      + ("" if ok else "  <-- RECOMPILED ON WARM RUN"))
                if not ok:
                    failed.append(f"{name}[pooled]")
    finally:
        conf.MONITOR_ENABLE.set(monitor_prior)
        monitor.reset()

    print(f"# warmup: plan cache primed: {len(digests)} distinct "
          f"fingerprints ({approx} approximate), "
          f"{querycache.plan_cache_stats()['distinct_plans']} plans seen")
    if unstable:
        print(f"# warmup: UNSTABLE fingerprints (digest differs across "
              f"two builds): {', '.join(unstable)}", file=sys.stderr)
        return 1
    if failed:
        print(f"# warmup: warm-run recompiles in: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


def _run_explain(suite: str, names, scale: float, n_parts: int,
                 json_path: str = "") -> int:
    """``--explain``: EXPLAIN ANALYZE.  Each query is WARMED first
    (cold compiles and cache population stay out of the profile), then
    run once more through the stage scheduler with tracing + the perf
    estimator armed, and the metric-annotated plan (runtime/perf.py:
    per-node rows/bytes/batches, own-time %-of-wall, fused-chain
    markers, per-kernel roofline, bound classification) renders from
    the event log.  ``--json`` writes the golden-pinned explain
    document(s) instead of / alongside the text."""
    import json as _json
    import tempfile

    from . import conf
    from .runtime import monitor, perf, stats, trace
    from .runtime.kernel_cache import enable_persistent_cache

    enable_persistent_cache()
    # smaller batches than the runner default: the profile is about
    # the per-batch steady state, and at one giant batch per partition
    # the per-task fixed overhead (proto decode, plan build) would
    # dominate what the plan nodes can attribute
    build_query, names, scans = _load_suite(suite, names, scale, n_parts,
                                            batch_rows=4096)
    if build_query is None:
        return names
    prev_trace = bool(conf.TRACE_ENABLE.get())
    prev_dir = conf.EVENT_LOG_DIR.get()
    # the command's whole point is the roofline table: force the
    # estimator armed for the profiled run even when the operator's
    # conf/env disarmed it — a bytes~0 / bound=unknown explain with no
    # hint why is worse than overriding a knob for one measurement
    perf.force(True)
    log_dir = tempfile.mkdtemp(prefix="blaze_explain_")
    docs = {}
    failed = []
    try:
        for name in names:
            try:
                # warm pass: compiles + kernel/XLA caches populated
                # OUTSIDE the profiled run, so the explain shows the
                # steady state
                _rows_via_scheduler(build_query(name, scans, n_parts))
                # the warm pass registered its plans with the stats
                # observatory too — drop them so the flush at the
                # profiled span's exit describes ONLY the traced run
                stats.discard_pending()
                conf.TRACE_ENABLE.set(True)
                conf.EVENT_LOG_DIR.set(log_dir)
                trace.reset()
                try:
                    # the full query span (trace + monitor + cancel
                    # scope), not a bare trace.query: the runtime-stats
                    # flush at span exit stamps est-vs-actual drift
                    # into THIS event log and persists the actuals for
                    # the next run's warm estimates
                    with monitor.query_span(
                            f"{suite}_{name}",
                            mode="explain") as log_path:
                        _rows_via_scheduler(
                            build_query(name, scans, n_parts))
                finally:
                    conf.TRACE_ENABLE.set(prev_trace)
                    conf.EVENT_LOG_DIR.set(prev_dir)
                    trace.reset()
                if log_path is None:
                    # conf.set(True) lost to an env override
                    # (ConfEntry: env > set) — say so instead of
                    # crashing on read_event_log(None)
                    raise RuntimeError(
                        "tracing did not arm (a BLAZE_TRACE_ENABLED "
                        "env override?) — --explain needs the event "
                        "log of the profiled run")
                events = trace.read_event_log(log_path)
            except Exception as e:  # noqa: BLE001 — report per query
                failed.append(name)
                print(f"explain {suite} {name}: FAILED "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
                continue
            docs[name] = perf.explain_doc(events)
            if json_path != "-":
                print(perf.render_explain(events, doc=docs[name]))
                print()
    finally:
        perf.reset()  # force(True) ends here; conf/env resume control
        # the scratch event logs served their purpose the moment the
        # documents were built — leaving one mkdtemp per invocation in
        # /tmp is exactly the litter the chaos arms gate against
        import shutil

        shutil.rmtree(log_dir, ignore_errors=True)
    if json_path:
        # shape keyed on what was REQUESTED, not what survived: one
        # query = its bare doc ({} when it failed), several = the
        # {name: doc} map (failed entries absent) — a consumer's
        # parse never depends on which queries happened to fail, and
        # stdout always carries one parseable document
        out = (docs if len(names) > 1
               else docs.get(names[0], {}) if names else {})
        if json_path == "-":
            # stdout is the PARSEABLE document and nothing else (the
            # --report --json - contract)
            print(_json.dumps(out, indent=2, default=str))
        else:
            with open(json_path, "w") as f:
                _json.dump(out, f, indent=2, default=str)
            print(f"# explain json: {json_path}")
    return 1 if failed else 0


def _check_perf_gate() -> int:
    """``--chaos`` structural gate for the perf estimator (the
    poisoned-emit pattern): DISARMED
    (``spark.blaze.perf.estimates=false``) the dispatch choke point
    must never enter the estimator — asserted by poisoning
    ``perf._estimate`` and driving a real instrumented call under an
    active kernel capture — and RE-ARMED the same call must land
    nonzero bytes/flops estimates in the sink.  Keeps the one-bool-read
    disarmed-cost contract honest the way the trace gate does for
    ``spark.blaze.trace.enabled``."""
    import numpy as np

    from .runtime import dispatch, perf, trace

    problems = []
    fn = dispatch.instrument(lambda x: x * 1.0, "perfgate")
    x = np.arange(1024, dtype=np.float64)
    orig = perf._estimate

    def poisoned(*a, **k):  # pragma: no cover — failure path
        raise AssertionError("estimator entered while disarmed")

    # perf.force, not conf.set: a BLAZE_PERF_ESTIMATES env override
    # wins over conf by ConfEntry design and would otherwise flip
    # either phase of this gate into a spurious failure on a healthy
    # build
    try:
        perf.force(False)
        perf._estimate = poisoned
        try:
            with trace.kernel_capture() as sink:
                fn(x)
        except AssertionError as e:
            problems.append(str(e))
        if any(v.get("bytes_est", 0) for v in sink.values()):
            problems.append("disarmed estimator still recorded bytes")
        perf._estimate = orig
        perf.force(True)
        with trace.kernel_capture() as sink:
            fn(x)
        est = sum(v.get("bytes_est", 0) for v in sink.values())
        if est <= 0:
            problems.append("armed estimator recorded no bytes for a "
                            "real program")
    finally:
        perf._estimate = orig
        perf.reset()  # conf/env resume control
    if problems:
        print("# chaos perf gate: " + "; ".join(problems), file=sys.stderr)
        return 1
    print("# chaos perf gate: OK (poisoned estimator never entered "
          "disarmed; armed call recorded estimates)")
    return 0


def _run_lint(json_path: str = "", sarif_path: str = "") -> int:
    """``--lint``: run every static-analysis pass (analysis/) and exit
    nonzero on any unwaived finding.

    1. AST lint over the package: trace purity, stray ``jax.jit``,
       emit-under-lock, static lock-order, guarded-by lock coverage +
       resource lifecycle — waivers applied
       (``analysis/lint_waivers.json``).
    2. Conf-name golden-registry drift (``runtime/conf_names.json``),
       two-way plus the README conf-table completeness check.
    3. Plan verifier over the whole TPC-H + TPC-DS query corpus,
       fusion enabled AND disabled (plan build over schema-only scans
       — no datagen, no execution).

    ``--json <path|->`` additionally writes the findings as one JSON
    document — rule id, path, line, symbol, message, waived flag, plus
    a summary block — with golden-pinned keys like ``--report --json``,
    so CI and the chaos sweep can diff lint runs mechanically (waived
    findings are reported and marked but never affect the exit code).

    ``--sarif <path|->`` writes the same findings as one SARIF 2.1.0
    document (golden-pinned keys, ``lint.SARIF_*``) so GitHub
    code-scanning — or any SARIF viewer — annotates them inline on the
    diff; waived findings ride as level ``note`` with an ``inSource``
    suppression carrying the pinned justification.  ``-`` keeps stdout
    pure SARIF exactly like ``--json -``."""
    from . import conf
    from .analysis import lint as lint_mod
    from .analysis.plan_verify import verify_plan
    from .ops import MemoryScanExec
    from .ops.fusion import optimize_plan

    pairs = lint_mod.findings_with_waivers()
    n_plans = 0
    prev_fusion = bool(conf.FUSION_ENABLE.get())
    try:
        for suite in ("tpch", "tpcds"):
            if suite == "tpch":
                from .tpch import TPCH_SCHEMAS as SCHEMAS
                from .tpch import build_query
                from .tpch.queries import QUERIES
            else:
                from .tpcds import TPCDS_SCHEMAS as SCHEMAS
                from .tpcds import build_query
                from .tpcds.queries import QUERIES
            scans = {n: MemoryScanExec([[], []], SCHEMAS[n]) for n in SCHEMAS}
            for name in sorted(QUERIES):
                for fused in (True, False):
                    conf.FUSION_ENABLE.set(fused)
                    tag = f"{suite} {name} fusion={'on' if fused else 'off'}"
                    try:
                        plan = optimize_plan(build_query(name, scans, 2))
                    except Exception as e:  # noqa: BLE001 — surface as finding
                        pairs.append((lint_mod.Finding(
                            "plan.build", f"{suite}/{name}", 0, tag,
                            f"plan build failed: {type(e).__name__}: {e}"),
                            False))
                        continue
                    n_plans += 1
                    for f in verify_plan(plan):
                        pairs.append((lint_mod.Finding(
                            f.rule, f"{suite}/{name}", 0, tag,
                            f"{f.path} ({f.node}): {f.message}"), False))
    finally:
        conf.FUSION_ENABLE.set(prev_fusion)
    findings = [f for f, waived in pairs if not waived]
    for f in findings:
        print(repr(f), file=sys.stderr)
    status = f"{len(findings)} finding(s)" if findings else "clean"
    status_line = (f"# lint: {status} — AST rules + conf registry + "
                   f"{n_plans} verified plans (fused+unfused), "
                   f"{len(lint_mod.load_waivers())} pinned waiver(s)")
    stream_stdout = "-" in (json_path, sarif_path)
    if sarif_path:
        import json as _json

        sarif = lint_mod.sarif_doc(pairs)
        if sarif_path == "-":
            # stdout is the PARSEABLE SARIF document and nothing else
            print(_json.dumps(sarif, indent=2))
        else:
            with open(sarif_path, "w") as f:
                _json.dump(sarif, f, indent=2)
            print(f"# sarif findings: {sarif_path}",
                  file=sys.stderr if stream_stdout else sys.stdout)
    if json_path:
        import json as _json

        doc = lint_mod.lint_json_doc(pairs, plans_verified=n_plans)
        if json_path == "-":
            # stdout is the PARSEABLE document and nothing else (same
            # contract as --report --json -): the status line moves to
            # stderr so `--lint --json - | jq` works as advertised
            print(_json.dumps(doc, indent=2))
        else:
            with open(json_path, "w") as f:
                _json.dump(doc, f, indent=2)
            print(f"# json findings: {json_path}",
                  file=sys.stderr if stream_stdout else sys.stdout)
    print(status_line, file=sys.stderr if stream_stdout else sys.stdout)
    return 1 if findings else 0


def _run_chaos(suite: str, names, scale: float, n_parts: int, seed: int,
               n_faults: int, speculate: bool = False,
               inject_oom: bool = False, loaded=None) -> int:
    """Fault-injection smoke: fault-free run vs seeded-fault run must
    produce identical rows.  The chaotic run is TRACED (event log on),
    and the recovery story must reconcile: every injected fault paired
    with a recorded recovery event (task retry or map-stage rerun),
    and every ``speculative_attempt_start`` paired with a ``_won`` /
    ``_lost`` resolution.  The plan verifier (spark.blaze.verify.plan)
    and the runtime lock-order assertion (spark.blaze.verify.locks)
    are both FORCED ON for the whole smoke — a plan invariant break or
    an inverted lock acquisition fails the run.

    ``speculate`` additionally ARMS speculation (duration + wedge
    triggers, fast heartbeat cadence) and seeds a deterministic
    STRAGGLER (``slow<ms>`` latency entry) into the fault schedule, so
    the smoke exercises the backup-attempt race, not just crash
    recovery.  ``inject_oom`` seeds a ``kernel.dispatch@<hit>@oom``
    entry — a mid-query device-memory exhaustion the degradation
    ladder (runtime/oom.py) must absorb with byte-identical results,
    every ``kind=oom`` fault pairing with an ``oom_recovery`` event.  The Eraser-style lockset checker
    (``spark.blaze.verify.lockset``, runtime/lockset.py) is armed for
    the whole smoke alongside the other two verifiers: a guarded
    attribute touched off-lock from a second thread raises a
    deterministic ``LocksetViolation`` that fails the run.  Nonzero
    exit on mismatch, unrecovered failure, an unreconciled event log,
    or ANY verifier firing."""
    import tempfile

    from . import conf
    from .analysis import locks as lock_verify
    from .runtime import errors, ledger, lockset, monitor, otel

    # ``loaded`` = a (build_query, names, scans) the sweep resolved
    # once up front — datagen does not depend on the seed, so N seeds
    # share one pass instead of regenerating per arm
    build_query, names, scans = loaded or _load_suite(
        suite, names, scale, n_parts)
    if build_query is None:
        return names

    conf.TASK_RETRY_BACKOFF.set(0.01)  # keep the smoke fast
    conf.VERIFY_PLAN.set(True)
    conf.VERIFY_LOCKS.set(True)
    lock_verify.refresh()
    conf.VERIFY_LOCKSET.set(True)
    lockset.refresh()
    # the error-escape recorder + per-query resource ledger arm for
    # the whole smoke (one knob: spark.blaze.verify.errors) — a
    # FATAL-class error absorbed at an audited broad-except site, or a
    # spill/.inprogress/scoped/lease resource still live at query end,
    # fails the run via the same record-then-raise gates as
    # lockset.reported()
    conf.VERIFY_ERRORS.set(True)
    errors.refresh()
    ledger.refresh()
    # telemetry arms for the whole smoke: OTLP export to a scratch dir
    # (endpoint at a dead port so the pusher spins up, fails fast, and
    # must still shut down leak-free) + the monitor REGISTRY (no
    # server) so latency histograms record every chaotic run — gated
    # by _check_chaos_telemetry after the loop
    otel_knobs = (conf.OTEL_ENABLE, conf.OTEL_DIR, conf.OTEL_ENDPOINT,
                  conf.MONITOR_ENABLE)
    prev_otel = [k.get() for k in otel_knobs]
    otel_dir = tempfile.mkdtemp(prefix="blaze_otel_chaos_")
    conf.OTEL_ENABLE.set(True)
    conf.OTEL_DIR.set(otel_dir)
    conf.OTEL_ENDPOINT.set("http://127.0.0.1:9/v1/traces")
    otel.reset()
    conf.MONITOR_ENABLE.set(True)
    monitor.reset()
    spec_knobs = (conf.SPECULATION_ENABLE, conf.SPECULATION_MULTIPLIER,
                  conf.SPECULATION_QUANTILE, conf.SPECULATION_MIN_RUNTIME,
                  conf.SPECULATION_WEDGE_MS, conf.MONITOR_HEARTBEAT_MS)
    prev = [k.get() for k in spec_knobs]
    if speculate:
        conf.SPECULATION_ENABLE.set(True)
        conf.SPECULATION_MULTIPLIER.set(1.2)
        conf.SPECULATION_QUANTILE.set(0.25)
        conf.SPECULATION_MIN_RUNTIME.set(0.05)
        conf.SPECULATION_WEDGE_MS.set(250)
        # wedge detection needs beats faster than the wedge threshold
        conf.MONITOR_HEARTBEAT_MS.set(50)
        monitor.reset()
    try:
        # estimator structural gate: its disarmed/armed contract
        # holds even while nothing measures
        rc = _check_perf_gate()
        rc = _chaos_loop(suite, names, scans, build_query, n_parts, seed,
                         n_faults, speculate, inject_oom) or rc
        return _check_chaos_telemetry(suite, names, otel_dir) or rc
    finally:
        conf.VERIFY_PLAN.set(False)
        conf.VERIFY_LOCKS.set(False)
        lock_verify.refresh()
        conf.VERIFY_LOCKSET.set(False)
        lockset.refresh()
        conf.VERIFY_ERRORS.set(False)
        errors.refresh()
        ledger.refresh()
        if speculate:
            # restore EVERY knob the smoke touched, symmetrically —
            # a later in-process run must not inherit the smoke's
            # aggressive thresholds
            for k, v in zip(spec_knobs, prev):
                k.set(v)
        # telemetry knobs restore even when a gate raises (the
        # knob-leak class): pusher down first, then conf, then reset
        otel.shutdown_pusher()
        for k, v in zip(otel_knobs, prev_otel):
            k.set(v)
        otel.reset()
        monitor.reset()


def _chaos_loop(suite, names, scans, build_query, n_parts, seed,
                n_faults, speculate=False, inject_oom=False) -> int:
    import glob

    from . import conf
    from .runtime import (errors, faults, ledger, lockset, monitor,
                          scheduler, trace, trace_report)

    failed = []
    for i, name in enumerate(names):
        spec = faults.random_spec(seed + i, n_faults=n_faults,
                                  n_stragglers=1 if speculate else 0,
                                  n_ooms=1 if inject_oom else 0)
        conf.FAULTS_SPEC.set("")
        faults.reset()
        try:
            baseline = _rows_via_scheduler(build_query(name, scans, n_parts))
        except Exception as e:  # noqa: BLE001
            print(f"chaos {name}: BASELINE FAILED {type(e).__name__}: {e}",
                  file=sys.stderr)
            failed.append(name)
            continue
        conf.FAULTS_SPEC.set(spec)
        faults.reset()
        # per-query lockset window: the checked-access tally and the
        # reported-violation list judge THIS chaotic run, not the
        # sweep so far (a later query's armed-but-never-exercised
        # checker must be visible as lockset_checked=0).  The escape
        # record and the resource ledger reset on the same cadence.
        lockset.reset()
        errors.reset()
        ledger.reset()
        # filesystem half of the leak oracle judges only THIS run: a
        # stale blaze_spill_* file from an earlier crashed process (or
        # a concurrent suite on the same tempdir) is not our leak
        spills_before = set(glob.glob(ledger.spill_glob()))
        prev_trace = bool(conf.TRACE_ENABLE.get())
        conf.TRACE_ENABLE.set(True)
        trace.reset()
        log_path = None
        try:
            with monitor.query_span(f"chaos_{suite}_{name}",
                                    mode="scheduler") as log_path:
                chaotic = _rows_via_scheduler(build_query(name, scans, n_parts))
        except Exception as e:  # noqa: BLE001
            print(f"chaos {name}: UNRECOVERED under spec '{spec}': "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            failed.append(name)
            continue
        finally:
            conf.FAULTS_SPEC.set("")
            faults.reset()
            conf.TRACE_ENABLE.set(prev_trace)
            trace.reset()
        m = scheduler.LAST_RUN_METRICS.metrics if scheduler.LAST_RUN_METRICS else None
        # mirror the lockset checker's access tally into the run's
        # counters: a chaos line showing 0 checked accesses means the
        # checker was armed but never exercised — visibly useless.
        # The error-escape and ledger tallies mirror the same way.
        checked = lockset.counters()["checked_accesses"]
        esc = errors.counters()
        led = ledger.counters()
        if m is not None:
            m.set("lockset_checked_accesses", checked)
            m.set("error_escapes_recorded", esc["recorded_escapes"])
            m.set("ledger_tracked_resources", led["acquired"])
            m.set("ledger_leaked_resources", led["leaks"])
        counters = (
            f"attempts={m.get('task_attempts')} retries={m.get('task_retries')} "
            f"fetch_failures={m.get('fetch_failures')} "
            f"map_reruns={m.get('map_stage_reruns')} "
            f"map_tasks_rerun={m.get('map_tasks_rerun')} "
            f"speculative={m.get('speculative_attempts')}"
            f"/won={m.get('speculative_won')} "
            f"oom={m.get('oom_recoveries')}"
            f"/{m.get('batch_downshifts')}"
            f"/{m.get('eager_fallbacks')} "
            f"dispatches={m.get('xla_dispatches')} "
            f"compiles={m.get('xla_compiles')} "
            f"lockset_checked={checked} "
            f"ledger={led['acquired']}/{led['released']}" if m
            else "no metrics"
        )
        # event-log reconciliation: every fault that FIRED must pair
        # with a recovery event recorded after it, and every
        # speculative attempt must resolve won-or-lost
        events = trace.read_event_log(log_path) if log_path else []
        rec = trace_report.reconcile_faults(events)
        spc = trace_report.reconcile_speculation(events)
        recon = (f"eventlog {rec['injected']} faults / "
                 f"{rec['recoveries']} recoveries "
                 + ("reconciled" if rec["reconciled"] else "UNRECONCILED")
                 + f"; {spc['speculated']} speculated "
                 f"({spc['won']} won / {spc['lost']} lost) "
                 + ("reconciled" if spc["reconciled"] else "UNRECONCILED"))
        # ONE leak oracle (runtime/ledger.py) for attempt threads +
        # recorded resource leaks + this run's spill files, replacing
        # the hand-rolled sweeps
        leak_problems = ledger.leak_audit(spills_before=spills_before)
        # a LocksetViolation may have been swallowed en route (monitor
        # handler 500s, operator blanket-excepts) — the recorded list
        # fails the run regardless of where the raise died.  Same
        # contract for a FATAL-class error absorbed at an audited
        # broad-except site (errors.escapes()).
        races = lockset.reported()
        escaped = errors.escapes()
        if races:
            print(f"chaos {name}: LOCKSET VIOLATION under spec '{spec}': "
                  + "; ".join(races), file=sys.stderr)
            failed.append(name)
        elif escaped:
            print(f"chaos {name}: FATAL-CLASS ERROR ESCAPE under spec "
                  f"'{spec}': " + "; ".join(escaped), file=sys.stderr)
            failed.append(name)
        elif chaotic != baseline:
            print(f"chaos {name}: MISMATCH under spec '{spec}' ({counters}; "
                  f"{recon})", file=sys.stderr)
            failed.append(name)
        elif not rec["reconciled"]:
            print(f"chaos {name}: EVENT LOG UNRECONCILED under spec "
                  f"'{spec}': {len(rec['unpaired'])} fault(s) without a "
                  f"recovery event ({counters}; {recon}; log: {log_path})",
                  file=sys.stderr)
            failed.append(name)
        elif not spc["reconciled"]:
            print(f"chaos {name}: SPECULATION UNRECONCILED under spec "
                  f"'{spec}': {len(spc['unpaired'])} backup(s) without a "
                  f"won/lost resolution ({counters}; {recon}; "
                  f"log: {log_path})", file=sys.stderr)
            failed.append(name)
        elif leak_problems:
            print(f"chaos {name}: RESOURCE LEAK under spec '{spec}': "
                  + "; ".join(leak_problems), file=sys.stderr)
            failed.append(name)
        else:
            print(f"chaos {name}: OK {len(baseline)} rows identical under "
                  f"spec '{spec}' ({counters}; {recon})")
    if failed:
        print(f"# chaos: {len(failed)} failed: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


def _check_chaos_telemetry(suite, names, otel_dir: str) -> int:
    """--chaos telemetry gate: every chaotic query exported ONE OTLP
    document whose spans all carry a single trace id, the query-latency
    histogram recorded every chaotic run, and the OTLP pusher + the
    histogram path leaked no thread (the statsd/monitor leak gates'
    OTLP sibling).  Lockset quietness rides the per-query check the
    chaos loop already does — the histogram and export paths run under
    the armed checker the whole smoke."""
    import glob
    import json as _json
    import os

    from .runtime import monitor, otel

    problems = []
    for name in names:
        pat = os.path.join(otel_dir, f"chaos_{suite}_{name}-*-spans.json")
        files = sorted(glob.glob(pat))
        if not files:
            problems.append(f"{name}: no OTLP export under {otel_dir}")
            continue
        try:
            with open(files[-1]) as f:
                doc = _json.load(f)
        except (OSError, ValueError) as e:
            problems.append(f"{name}: unreadable OTLP export: {e}")
            continue
        spans = otel.span_index(doc)
        tids = {s.get("traceId") for s in spans}
        if not spans:
            problems.append(f"{name}: OTLP export has no spans")
        elif len(tids) != 1:
            problems.append(
                f"{name}: {len(tids)} trace ids in one export "
                f"(cross-process reconciliation broken)")
    hists = {h["name"]: h for h in monitor.histograms_snapshot()}
    lat = hists.get("blaze_query_latency_seconds")
    lat_count = 0 if lat is None else lat["count"]
    if lat_count < len(names):
        problems.append(f"query-latency histogram missed runs "
                        f"({lat_count}/{len(names)})")
    otel.shutdown_pusher()
    leaked = otel.otel_threads()
    if leaked:
        problems.append("otel thread leak after shutdown: "
                        + ", ".join(t.name for t in leaked))
    if problems:
        print("# chaos telemetry: " + "; ".join(problems), file=sys.stderr)
        return 1
    print(f"# chaos telemetry: OK ({len(names)} single-trace OTLP "
          f"export(s), latency histogram count {lat_count}, pusher "
          f"shut down clean)")
    return 0


def _run_cancel_storm(suite, names, scans, build_query, n_parts,
                      seed) -> int:
    """Cancel-storm chaos arm: run each query through the scheduler on
    a worker thread, fire ``cancel_query`` at a seeded random moment —
    landing at whatever stage frontier the query has reached — and
    assert EXACT reconciliation: the caller gets
    ``QueryCancelledError`` (or the query legitimately finished before
    the cancel landed), every ``query_cancel_requested`` pairs with a
    terminal ``query_cancelled`` in the event log, and nothing leaks —
    no ``blaze-attempt-*`` thread, no ``.inprogress`` shuffle temp, no
    ``blaze_spill_*`` file."""
    import glob
    import random
    import threading

    from . import conf
    from .runtime import trace, trace_report
    from .runtime import ledger, monitor
    from .runtime.context import QueryCancelledError, cancel_query

    from .runtime import faults

    from .runtime import errors

    rng = random.Random(seed * 7919 + 13)
    rc = 0
    # the escape recorder + resource ledger judge every storm arm too
    conf.VERIFY_ERRORS.set(True)
    errors.refresh()
    ledger.refresh()
    try:
        for name in names:
            qid = f"storm_{suite}_{name}_{seed}"
            prev_trace = bool(conf.TRACE_ENABLE.get())
            conf.TRACE_ENABLE.set(True)
            trace.reset()
            errors.reset()
            ledger.reset()
            # seed deterministic stragglers so the query is reliably still
            # in flight when the cancel fires — a warm q6 otherwise
            # finishes before any humanly-chosen delay (a vacuous storm)
            slow = rng.randrange(300, 700)
            conf.FAULTS_SPEC.set(
                f"task.compute@1@slow{slow},task.compute@3@slow{slow}")
            faults.reset()
            spills_before = set(glob.glob(ledger.spill_glob()))
            state: dict = {}

            def run():
                try:
                    with monitor.query_span(qid, mode="scheduler") as lp:
                        state["log"] = lp
                        from .runtime.scheduler import run_stages, split_stages

                        stages, mgr = split_stages(
                            build_query(name, scans, n_parts))
                        state["root"] = mgr.root
                        rows = 0
                        for b in run_stages(stages, mgr):
                            rows += b.num_rows
                        state["rows"] = rows
                except BaseException as e:  # noqa: BLE001 — judged below
                    state["exc"] = e

            t = threading.Thread(target=run, name="blaze-storm-query",
                                 daemon=True)
            problems = []
            try:
                t.start()
                time.sleep(rng.uniform(0.02, 0.25))
                accepted = False
                for _ in range(400):
                    if cancel_query(qid):
                        accepted = True
                        break
                    if not t.is_alive():
                        break
                    time.sleep(0.005)
                t.join(60)
                if t.is_alive():
                    problems.append("query thread did not exit after the cancel")
                exc = state.get("exc")
                if exc is not None and not isinstance(exc, QueryCancelledError):
                    problems.append(
                        f"wrong terminal error {type(exc).__name__}: {exc}")
                if exc is None and "rows" not in state:
                    problems.append("query neither produced rows nor raised")
                events = trace.read_event_log(state["log"]) \
                    if state.get("log") else []
                cxl = trace_report.reconcile_cancellation(events)
                if not cxl["reconciled"]:
                    problems.append(
                        f"{len(cxl['unpaired'])} cancel request(s) without a "
                        f"terminal query_cancelled event")
                if isinstance(exc, QueryCancelledError) \
                        and cxl["cancelled"] == 0:
                    problems.append(
                        "cancelled query left no query_cancelled event")
                if accepted and cxl["requested"] == 0:
                    # the scope took the cancel: even a query that finished
                    # before noticing must leave the request on the record
                    problems.append("accepted cancel left no "
                                    "query_cancel_requested event")
                # the ONE leak oracle (runtime/ledger.py): attempt
                # threads + ledger record + spill/.inprogress filesystem
                # sweeps, shared with --chaos, the other storm arms, and
                # tests/test_lifecycle.py
                problems += ledger.leak_audit(shuffle_root=state.get("root"),
                                              spills_before=spills_before)
                escaped = errors.escapes()
                if escaped:
                    problems.append("FATAL-class error escape(s): "
                                    + "; ".join(escaped))
            finally:
                # restore EVEN when a check raises: a leaked straggler
                # schedule or forced-on tracing would poison every later
                # arm with misleading cascade failures
                conf.FAULTS_SPEC.set("")
                faults.reset()
                conf.TRACE_ENABLE.set(prev_trace)
                trace.reset()
            if problems:
                print(f"cancel-storm {name} (seed {seed}): "
                      + "; ".join(problems), file=sys.stderr)
                rc = 1
            else:
                outcome = ("cancelled mid-flight"
                           if isinstance(exc, QueryCancelledError)
                           else "finished before the cancel landed")
                print(f"cancel-storm {name} (seed {seed}): OK ({outcome}; "
                      f"{cxl['requested']} requested / {cxl['cancelled']} "
                      f"terminal)")
    finally:
        # disarm even when a check raises (the knob-leak
        # class): a later in-process run must not inherit
        # an armed recorder full of this storm's record
        conf.VERIFY_ERRORS.set(False)
        errors.refresh()
        ledger.refresh()
    return rc


def _run_service(suite: str, names, scale: float, n_parts: int,
                 pools: str = "") -> int:
    """``--service``: run the multi-tenant query service
    (runtime/service.py) over the loaded suite.

    With query names, every listed query is SUBMITTED concurrently
    (round-robin across the ``--pools`` list, sessions cycling) and
    the per-query outcomes print as they drain — admission sheds
    surface as typed rejections, not hangs.  Bare ``--service`` serves
    until interrupted: the monitor server's ``POST /service/submit``
    endpoint accepts ``{"query": ..., "pool": ..., "session": ...}``
    submissions against the loaded suite and answers 429 when shed."""
    from . import conf
    from .runtime import service
    from .runtime.context import QueryCancelledError

    submit_names = list(names) if names else []
    build_query, all_names, scans = _load_suite(
        suite, names or ["all"], scale, n_parts)
    if build_query is None:
        return all_names
    pool_names = ["default"]
    if pools:
        pool_names = []
        for ent in pools.split(","):
            pname, _, w = ent.strip().partition(":")
            if not pname:
                continue
            pool_names.append(pname)
            if w:
                conf.set_conf(
                    f"spark.blaze.service.pool.{pname}.weight", float(w))
    svc = service.QueryService().start()
    service.set_http_builders(
        {n: (lambda n=n: build_query(n, scans, n_parts))
         for n in all_names})
    rc = 0
    try:
        if not submit_names:
            print(f"# service: {len(all_names)} queries loaded, "
                  f"POST /service/submit to run them "
                  f"(pools: {', '.join(pool_names)})")
            rc = _serve_forever()
        else:
            handles = []
            for i, name in enumerate(submit_names):
                pool = pool_names[i % len(pool_names)]
                try:
                    handles.append(svc.submit(
                        name,
                        build=lambda n=name: build_query(n, scans, n_parts),
                        pool=pool, session=f"cli-{i % 4}"))
                except service.QueryRejectedError as e:
                    print(f"service {name}: REJECTED ({e.reason})",
                          file=sys.stderr)
                    rc = 1
            for h in handles:
                t0 = time.perf_counter()
                try:
                    rows = sum(b.num_rows for b in h.result())
                    print(f"service {h.query_id} [pool={h.pool}]: "
                          f"{rows} rows "
                          f"in {time.perf_counter() - t0:.2f}s")
                except QueryCancelledError as e:
                    print(f"service {h.query_id}: CANCELLED ({e.reason})",
                          file=sys.stderr)
                    rc = 1
                except Exception as e:  # noqa: BLE001 — per query
                    print(f"service {h.query_id}: FAILED "
                          f"{type(e).__name__}: {e}", file=sys.stderr)
                    rc = 1
            st = svc.stats()
            shares = {n: round(p["charged_ns"] / 1e9, 2)
                      for n, p in st["pools"].items()}
            print(f"# service: {st['counters']}  lease-seconds {shares}")
    finally:
        svc.shutdown()
        leaked = service.service_threads()
        if leaked:
            # the leak gate must land in the exit code, so NO return
            # inside the try above (a `return` there would capture rc
            # before this assignment)
            print("# service: THREAD LEAK after shutdown: "
                  + ", ".join(t.name for t in leaked), file=sys.stderr)
            rc = 1
    return rc


def _run_admission_storm(suite, names, scans, build_query, n_parts,
                         seed) -> int:
    """Admission-storm chaos arm: a BURST of concurrent submissions
    past ``maxQueued`` — seeded stragglers keeping queries in flight,
    one mid-flight cancel at a seeded moment — asserting the admission
    contract end to end: every submission ends accepted-and-terminal
    or typed-rejected (never a hang), completed results match the
    fault-free baseline, no pool is starved of lease time, and nothing
    leaks (``blaze-*`` threads, spill files, ``.inprogress`` shuffle
    temps).  Lockset + lock-order checkers are armed for the whole arm
    — the service's new shared state runs under the PR 8 gates."""
    import glob
    import os
    import random
    import tempfile
    import threading

    from . import conf
    from .analysis import locks as lock_verify
    from .runtime import errors, faults, ledger, lockset, monitor, service
    from .runtime.context import QueryCancelledError, cancel_query

    rng = random.Random(seed * 104729 + 7)
    name = names[0]
    # the result cache is OFF for this arm: every submission builds the
    # same plan, so an admission-integrated cache hit completes a query
    # with ZERO lease turns — the "pool done but never granted lease
    # time" fairness check would flake on whichever pool's survivors
    # all landed after the first tee commit (the cache-storm arm owns
    # cache-vs-lease behavior)
    knobs = (conf.SERVICE_MAX_CONCURRENT, conf.SERVICE_MAX_QUEUED,
             conf.SERVICE_QUEUE_TIMEOUT_MS, conf.MONITOR_ENABLE,
             conf.CACHE_RESULT_ENABLED)
    prev = [k.get() for k in knobs]
    pool_keys = ("spark.blaze.service.pool.storm_a.weight",
                 "spark.blaze.service.pool.storm_b.weight")
    prev_pools = [conf.get_conf(k) for k in pool_keys]
    conf.VERIFY_LOCKS.set(True)
    lock_verify.refresh()
    conf.VERIFY_LOCKSET.set(True)
    lockset.refresh()
    lockset.reset()
    conf.VERIFY_ERRORS.set(True)
    errors.refresh()
    ledger.refresh()
    problems = []
    svc = None
    shuffle_glob = os.path.join(tempfile.gettempdir(), "blaze_shuffle_*")
    spills_before = set(glob.glob(ledger.spill_glob()))
    roots_before = set(glob.glob(shuffle_glob))
    n_subs = 8
    n_rejected = 0
    cancelled_id = None
    try:
        try:
            baseline = _rows_via_scheduler(build_query(name, scans, n_parts))
            conf.SERVICE_MAX_CONCURRENT.set(2)
            conf.SERVICE_MAX_QUEUED.set(2)
            conf.SERVICE_QUEUE_TIMEOUT_MS.set(0)
            conf.MONITOR_ENABLE.set(True)
            conf.CACHE_RESULT_ENABLED.set(False)
            conf.set_conf("spark.blaze.service.pool.storm_a.weight", 3.0)
            conf.set_conf("spark.blaze.service.pool.storm_b.weight", 1.0)
            monitor.reset()
            slow = rng.randrange(120, 350)
            conf.FAULTS_SPEC.set(
                f"task.compute@2@slow{slow},task.compute@6@slow{slow}")
            faults.reset()
            svc = service.QueryService().start()
            outcomes = [None] * n_subs          # "rejected" | handle
            accepted = []
            accepted_lock = threading.Lock()

            def submitter(i: int) -> None:
                pool = "storm_a" if i % 2 == 0 else "storm_b"
                try:
                    h = svc.submit(f"storm{i}", pool=pool, session=f"s{i % 4}",
                                   build=lambda: build_query(name, scans,
                                                             n_parts))
                except service.QueryRejectedError:
                    outcomes[i] = "rejected"
                    return
                outcomes[i] = h
                with accepted_lock:
                    accepted.append(h)

            threads = [threading.Thread(target=submitter, args=(i,),
                                        name=f"blaze-storm-submit-{i}",
                                        daemon=True) for i in range(n_subs)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(10)
            # one mid-flight cancel at a seeded moment, at whatever stage
            # frontier the victim has reached
            time.sleep(rng.uniform(0.01, 0.15))
            with accepted_lock:
                victims = list(accepted)
            cancelled_id = None
            if victims:
                victim = victims[rng.randrange(len(victims))]
                if cancel_query(victim.exec_id):
                    cancelled_id = victim.exec_id
            # drain EVERY accepted handle: terminal or bust (the no-hang
            # contract; 120s is far past any straggler schedule)
            for h in victims:
                rows = None
                try:
                    rows = sum(b.num_rows for b in h.result(timeout=120))
                except QueryCancelledError:
                    pass
                except service.QueryRejectedError:
                    pass
                except Exception as e:  # noqa: BLE001 — judged below
                    problems.append(f"{h.exec_id}: unexpected terminal "
                                    f"{type(e).__name__}: {e}")
                if h.status not in service.TERMINAL_STATES:
                    problems.append(f"{h.exec_id}: non-terminal status "
                                    f"{h.status!r} after drain")
                if h.status == "done" and rows != len(baseline):
                    problems.append(
                        f"{h.exec_id}: {rows} rows != baseline {len(baseline)}")
            n_rejected = sum(1 for o in outcomes if o == "rejected")
            if any(o is None for o in outcomes):
                problems.append("a submitter thread never resolved")
            if n_rejected == 0:
                problems.append(
                    "no submission was shed past maxQueued — the storm "
                    "never exercised admission control")
            if cancelled_id is not None:
                victim = next(h for h in victims if h.exec_id == cancelled_id)
                if victim.status not in ("cancelled", "done"):
                    problems.append(
                        f"cancelled query ended {victim.status!r} (expected "
                        f"cancelled, or done when it won the race)")
            # fairness: both pools completed work and neither was starved
            # of lease time (the tolerance-band fairness assertion lives in
            # the soak test, where the workload is controlled)
            shares = svc.gate.shares()
            for pname in ("storm_a", "storm_b"):
                p = shares.get(pname)
                if any(h.pool == pname and h.status == "done" for h in victims) \
                        and (p is None or p["charged_ns"] <= 0):
                    problems.append(f"pool {pname} completed queries but was "
                                    f"never granted lease time")
            races = lockset.reported()
            if races:
                problems.append("lockset violation(s): " + "; ".join(races))
            escaped = errors.escapes()
            if escaped:
                problems.append("FATAL-class error escape(s): "
                                + "; ".join(escaped))
        except Exception as e:  # noqa: BLE001 — the arm must report, not die
            problems.append(f"storm arm crashed: {type(e).__name__}: {e}")
        finally:
            if svc is not None:
                svc.shutdown()
            conf.FAULTS_SPEC.set("")
            faults.reset()
            for k, v in zip(knobs, prev):
                k.set(v)
            # the storm pool weights too (a stored None reads back as the
            # defaults through the `or` guards) — the knob-leak class an
            # earlier review round fixed in _run_chaos
            for k, v in zip(pool_keys, prev_pools):
                conf.set_conf(k, v)
            monitor.reset()
            conf.VERIFY_LOCKS.set(False)
            lock_verify.refresh()
            conf.VERIFY_LOCKSET.set(False)
            lockset.refresh()
        leaked = [t.name for t in service.service_threads()]
        if leaked:
            problems.append("leaked threads: " + ", ".join(leaked))
        # the ONE leak oracle: attempt threads + ledger record + spill and
        # .inprogress filesystem sweeps across every root the burst made
        problems += ledger.leak_audit(
            shuffle_root=sorted(set(glob.glob(shuffle_glob)) - roots_before),
            spills_before=spills_before)
    finally:
        # disarm even when shutdown/restore or the audit raises
        # (the knob-leak class): a later in-process run must not
        # inherit an armed recorder full of this storm's record
        conf.VERIFY_ERRORS.set(False)
        errors.refresh()
        ledger.refresh()
    if problems:
        print(f"admission-storm {name} (seed {seed}): "
              + "; ".join(problems), file=sys.stderr)
        return 1
    print(f"admission-storm {name} (seed {seed}): OK "
          f"({n_subs - n_rejected} accepted+terminal / {n_rejected} "
          f"typed-rejected"
          + (", 1 mid-flight cancel)" if cancelled_id else ")"))
    return 0


def _run_corruption_storm(suite, names, scans, build_query, n_parts,
                          seed) -> int:
    """Corruption-storm chaos arm: the query runs under seeded
    ``@corrupt`` (post-commit bit flips on shuffle map outputs and
    spill frames) and ``@enospc`` (injected disk-full at the shuffle
    commit) with a spill-forcing memory budget, asserting the
    end-to-end integrity contract: ZERO silent wrong results (rows
    byte-identical to the fault-free baseline), every injected
    corruption DETECTED (typed ``block_corruption``) and recovered
    through the existing ladder, every disk-pressure injection
    absorbed, counters visible, the event log reconciled, the lockset
    checker quiet, and nothing left behind (no ``.inprogress`` temp,
    no unaccounted ``.corrupt`` quarantine file)."""
    import glob
    import os
    import random
    import tempfile

    from . import conf
    from .analysis import locks as lock_verify
    from .runtime import errors, faults, integrity, ledger, lockset, monitor
    from .runtime import scheduler, trace, trace_report

    import blaze_tpu.parallel.shuffle as sh

    rng = random.Random(seed * 52361 + 3)
    name = names[0]
    prev_trace = bool(conf.TRACE_ENABLE.get())
    prev_backoff = conf.TASK_RETRY_BACKOFF.get()
    prev_checksum = conf.IO_CHECKSUM.get()
    conf.VERIFY_LOCKS.set(True)
    lock_verify.refresh()
    conf.VERIFY_LOCKSET.set(True)
    lockset.refresh()
    lockset.reset()
    conf.VERIFY_ERRORS.set(True)
    errors.refresh()
    ledger.refresh()
    integrity.reset()
    problems = []
    root = None
    spills_before = set(glob.glob(ledger.spill_glob()))
    # force a shuffle spill per staged batch: at smoke scale the
    # shuffle moves only aggregated partials (bytes), so the memmgr
    # watermark would never trip and the spill.write corruption site
    # would be unreachable — a vacuous arm.  Both the baseline and the
    # chaotic run spill identically, isolating the injected faults.
    orig_insert = sh._insert_host

    def _insert_and_spill(rep, schema, item):
        orig_insert(rep, schema, item)
        rep.spill()

    sh._insert_host = _insert_and_spill
    try:
        conf.TASK_RETRY_BACKOFF.set(0.01)
        # the arm JUDGES the integrity layer: force it on even when the
        # operator's environment configured checksums off (the gate
        # would otherwise blame the engine for an undetected flip that
        # was undetectable by configuration).  The algorithm name is
        # held in a variable so the metric-literal drift scan does not
        # mistake the .set() call for a metric name.
        storm_algo = "crc32"
        conf.IO_CHECKSUM.set(storm_algo)
        conf.FAULTS_SPEC.set("")
        faults.reset()
        baseline = _rows_via_scheduler(build_query(name, scans, n_parts))
        spec = (f"shuffle.write@{1 + rng.randrange(2)}@corrupt,"
                f"spill.write@1@corrupt,"
                f"shuffle.write@{1 + rng.randrange(2)}@enospc")
        conf.FAULTS_SPEC.set(spec)
        faults.reset()
        conf.TRACE_ENABLE.set(True)
        trace.reset()
        log_path = None
        try:
            from .parallel.shuffle import LocalShuffleManager

            mgr = LocalShuffleManager()
            root = mgr.root
            with monitor.query_span(f"corruption_{suite}_{name}",
                                    mode="scheduler") as log_path:
                chaotic = _rows_via_scheduler(
                    build_query(name, scans, n_parts), manager=mgr)
        except Exception as e:  # noqa: BLE001 — the arm reports
            problems.append(f"UNRECOVERED under spec '{spec}': "
                            f"{type(e).__name__}: {e}")
            chaotic = None
        m = scheduler.LAST_RUN_METRICS.metrics \
            if scheduler.LAST_RUN_METRICS else None
        events = trace.read_event_log(log_path) if log_path else []
        rec = trace_report.reconcile_faults(events)
        injected_corrupt = sum(
            1 for e in events if e.get("type") == "fault_injected"
            and e.get("kind") == "corrupt")
        injected_enospc = sum(
            1 for e in events if e.get("type") == "fault_injected"
            and e.get("kind") == "enospc")
        detected = sum(1 for e in events
                       if e.get("type") == "block_corruption")
        disk_events = sum(1 for e in events
                          if e.get("type") == "disk_pressure")
        if chaotic is not None and chaotic != baseline:
            problems.append(f"SILENT MISMATCH under spec '{spec}' "
                            f"({len(chaotic)} vs {len(baseline)} rows)")
        if not rec["reconciled"]:
            problems.append(
                f"{len(rec['unpaired'])} injected fault(s) without a "
                f"detection/recovery event (log: {log_path})")
        if injected_corrupt == 0:
            problems.append("no @corrupt injection fired — the storm "
                            "never exercised the integrity layer")
        if injected_corrupt and detected == 0:
            problems.append("corruption injected but never DETECTED "
                            "(a silent-trust path survives)")
        if injected_enospc and disk_events == 0 \
                and (m is None or m.get("disk_pressure_recoveries") == 0):
            problems.append("@enospc injected but no disk-pressure "
                            "recovery recorded")
        if not any(e.get("type") == "fault_injected"
                   and e.get("kind") == "corrupt"
                   and e.get("site") == "spill.write" for e in events):
            problems.append("the spill.write corruption site never "
                            "fired despite forced per-batch spills "
                            "(vacuous arm)")
        if m is not None and detected \
                and m.get("corruption_detected") == 0:
            problems.append("block_corruption events present but the "
                            "corruption_detected counter stayed 0")
        races = lockset.reported()
        if races:
            problems.append("lockset violation(s): " + "; ".join(races))
        escaped = errors.escapes()
        if escaped:
            problems.append("FATAL-class error escape(s): "
                            + "; ".join(escaped))
        # the ONE leak oracle (threads + ledger + filesystem sweeps)
        # with the .corrupt-quarantine accounting folded in
        problems += ledger.leak_audit(
            shuffle_root=root, spills_before=spills_before,
            corrupt_expected=(0 if m is None
                              else m.get("blocks_quarantined")))
    except Exception as e:  # noqa: BLE001 — the arm must report, not die
        problems.append(f"storm arm crashed: {type(e).__name__}: {e}")
    finally:
        sh._insert_host = orig_insert  # un-patch the forced-spill seam
        conf.FAULTS_SPEC.set("")
        faults.reset()
        integrity.reset()
        conf.TRACE_ENABLE.set(prev_trace)
        trace.reset()
        conf.TASK_RETRY_BACKOFF.set(prev_backoff)
        conf.IO_CHECKSUM.set(prev_checksum)
        conf.VERIFY_LOCKS.set(False)
        lock_verify.refresh()
        conf.VERIFY_LOCKSET.set(False)
        lockset.refresh()
        conf.VERIFY_ERRORS.set(False)
        errors.refresh()
        ledger.refresh()
    if problems:
        print(f"corruption-storm {name} (seed {seed}): "
              + "; ".join(problems), file=sys.stderr)
        return 1
    print(f"corruption-storm {name} (seed {seed}): OK "
          f"({injected_corrupt} corrupt + {injected_enospc} enospc "
          f"injected, {detected} detected, {disk_events} disk-pressure "
          f"event(s), rows identical)")
    return 0


def _run_worker_kill_storm(suite, seed) -> int:
    """Worker-kill storm chaos arm: a two-stage hash query runs on an
    elastic worker-host pool whose processes carry a seeded
    ``worker.task@N@kill`` schedule — every pooled worker SIGKILLs
    itself partway through the map stage, exercising the full
    lost-worker ladder: liveness/exit detection, invalidation of the
    dead worker's committed map outputs, partial re-run on survivors
    (never the whole stage), blacklisting of repeat offenders, and —
    once every slot is dead or blacklisted — degradation to in-process
    execution.  Gates: rows byte-identical to the fault-free in-process
    baseline, at least one worker actually died (vacuous-arm guard),
    the ``worker_lost`` counter and event log agree, re-runs stay
    partial, blacklist/degradation counters reconcile with their
    events and the pool's own state, the lockset checker and the
    error-escape recorder stay quiet, and the leak oracle finds no
    residue (no pool thread, no ledger entry, no temp).

    The suite's smoke plans scan driver-process memory (not shippable
    to a pooled worker), so the arm generates its own small parquet
    lineitem and builds the canonical scan -> filter -> project ->
    partial agg -> hash exchange -> final agg split over it: 4 map
    tasks over 2 pooled workers, kill at each process's SECOND job —
    every death loses exactly one committed map output."""
    import glob
    import random
    import tempfile

    from . import conf
    from .analysis import locks as lock_verify
    from .batch import batch_from_pydict
    from .exprs import col, lit
    from .ops import (
        AggExec, AggFunction, AggMode, FilterExec, GroupingExpr,
        MemoryScanExec, ParquetScanExec, ParquetSinkExec, ProjectExec,
    )
    from .parallel import HashPartitioning, NativeShuffleExchangeExec
    from .parallel.shuffle import LocalShuffleManager
    from .runtime import dispatch, errors, faults, ledger, lockset, monitor
    from .runtime import scheduler, trace
    from .runtime.context import TaskContext
    from .runtime.hostpool import HostPool
    from .schema import DataType, Field, Schema

    rng = random.Random(seed * 74699 + 11)
    schema = Schema([
        Field("q", DataType.int64()),
        Field("p", DataType.int64()),
        Field("d", DataType.int64()),
    ])
    prev_trace = bool(conf.TRACE_ENABLE.get())
    prev_backoff = conf.TASK_RETRY_BACKOFF.get()
    prev_task_att = conf.TASK_MAX_ATTEMPTS.get()
    prev_stage_att = conf.STAGE_MAX_ATTEMPTS.get()
    prev_maxfail = conf.HOST_BLACKLIST_MAX_FAILURES.get()
    conf.VERIFY_LOCKS.set(True)
    lock_verify.refresh()
    conf.VERIFY_LOCKSET.set(True)
    lockset.refresh()
    lockset.reset()
    conf.VERIFY_ERRORS.set(True)
    errors.refresh()
    ledger.refresh()
    problems = []
    root = None
    spills_before = set(glob.glob(ledger.spill_glob()))
    try:
        conf.FAULTS_SPEC.set("")
        faults.reset()
        conf.TASK_RETRY_BACKOFF.set(0.01)
        # deep retry/regen budgets: a respawned slot carries a FRESH
        # per-process fault counter, so it dies again at its own second
        # job — the blacklist ladder (maxFailures deaths per slot, then
        # degradation) is what bounds the storm, and the budgets must
        # not fire first
        conf.TASK_MAX_ATTEMPTS.set(8)
        conf.STAGE_MAX_ATTEMPTS.set(8)
        # seeded ladder depth: maxFailures=1 blacklists on the first
        # death (2 deaths to collapse), 2 tolerates one respawn per
        # slot (up to 4 deaths)
        maxfail = 1 + rng.randrange(2)
        conf.HOST_BLACKLIST_MAX_FAILURES.set(maxfail)
        with tempfile.TemporaryDirectory(prefix="blaze_killstorm_") as td:
            data_rng = random.Random(13)
            files = []
            for i in range(4):
                d = {
                    "q": [data_rng.randrange(1, 50) for _ in range(90)],
                    "p": [data_rng.randrange(100, 10000) for _ in range(90)],
                    "d": [data_rng.randrange(0, 10) for _ in range(90)],
                }
                src = MemoryScanExec([[batch_from_pydict(d, schema)]],
                                     schema)
                sink = ParquetSinkExec(src, f"{td}/lineitem_{i}.parquet")
                for _ in sink.execute(0, TaskContext(0, 1)):
                    pass
                files.append(sink.written_files[0])

            def build_plan():
                scan = ParquetScanExec([[f] for f in files], schema)
                f = FilterExec(scan, col("q") < lit(24))
                pr = ProjectExec(
                    f, [col("q"), (col("p") * col("d")).alias("rev")])
                aggs = [AggFunction("sum", col("rev"), "revenue")]
                partial = AggExec(pr, AggMode.PARTIAL,
                                  [GroupingExpr(col("q"), "q")], aggs,
                                  supports_partial_skipping=True)
                ex = NativeShuffleExchangeExec(
                    partial, HashPartitioning([col("q")], 2))
                return AggExec(ex, AggMode.FINAL,
                               [GroupingExpr(col("q"), "q")], aggs)

            baseline = _rows_via_scheduler(build_plan())
            # the kill schedule rides the POOL WORKERS' env only — the
            # driver's own spec stays empty (a driver probing
            # worker.task would kill the query, not a worker).  A map
            # job probes the site once at job start (the writer plan
            # yields no batches), so @2@kill means: survive the first
            # job (one committed map output), die starting the second.
            kill_spec = "worker.task@2@kill"
            conf.TRACE_ENABLE.set(True)
            trace.reset()
            log_path = None
            disp_before = dispatch.counters()
            blacklisted_final, degraded_final = [], False
            try:
                mgr = LocalShuffleManager()
                root = mgr.root
                with monitor.query_span(f"worker_kill_{suite}",
                                        mode="scheduler") as log_path:
                    with HostPool(
                            2, env={"BLAZE_FAULTS_SPEC": kill_spec},
                    ) as pool:
                        chaotic = _rows_via_scheduler(
                            build_plan(), manager=mgr, pool=pool)
                        blacklisted_final = pool.blacklisted()
                        degraded_final = pool.degraded()
            except Exception as e:  # noqa: BLE001 — the arm reports
                problems.append(f"UNRECOVERED under '{kill_spec}': "
                                f"{type(e).__name__}: {e}")
                chaotic = None
        m = scheduler.LAST_RUN_METRICS.metrics \
            if scheduler.LAST_RUN_METRICS else None
        events = trace.read_event_log(log_path) if log_path else []
        lost_events = [e for e in events if e.get("type") == "worker_lost"]
        bl_events = [e for e in events
                     if e.get("type") == "worker_blacklisted"]
        deg_events = [e for e in events if e.get("type") == "pool_degraded"]
        disp_after = dispatch.counters()

        def delta(key):
            return disp_after.get(key, 0) - disp_before.get(key, 0)

        if chaotic is not None and chaotic != baseline:
            problems.append(f"SILENT MISMATCH under '{kill_spec}' "
                            f"({len(chaotic)} vs {len(baseline)} rows)")
        if not lost_events:
            problems.append("no pooled worker died — the storm never "
                            "exercised the lost-worker ladder "
                            "(vacuous arm)")
        if m is not None and m.get("worker_lost") != len(lost_events):
            problems.append(
                f"worker_lost counter ({m.get('worker_lost')}) disagrees "
                f"with the event log ({len(lost_events)} event(s))")
        lost_maps = sum(e.get("lost_maps", 0) for e in lost_events)
        if lost_maps and m is not None:
            reruns = m.get("map_stage_reruns") or 0
            tasks_rerun = m.get("map_tasks_rerun") or 0
            if reruns == 0:
                problems.append("committed map outputs were lost but no "
                                "map-stage regeneration ran")
            # PARTIAL re-runs: each regeneration re-ran strictly fewer
            # tasks than the 4-task stage, i.e. only the dead worker's
            # outputs, never the whole map stage
            if tasks_rerun >= 4 * max(reruns, 1):
                problems.append(
                    f"regeneration re-ran the FULL stage "
                    f"({tasks_rerun} task(s) over {reruns} rerun(s)) — "
                    f"the partial-rerun path did not engage")
        if delta("workers_blacklisted") != len(bl_events) \
                or len(blacklisted_final) != len(bl_events):
            problems.append(
                f"blacklist accounting disagrees: counter delta "
                f"{delta('workers_blacklisted')}, {len(bl_events)} "
                f"event(s), pool reported {blacklisted_final}")
        if delta("pool_degraded") != len(deg_events) \
                or degraded_final != bool(deg_events):
            problems.append(
                f"degradation accounting disagrees: counter delta "
                f"{delta('pool_degraded')}, {len(deg_events)} event(s), "
                f"pool degraded={degraded_final}")
        races = lockset.reported()
        if races:
            problems.append("lockset violation(s): " + "; ".join(races))
        escaped = errors.escapes()
        if escaped:
            problems.append("FATAL-class error escape(s): "
                            + "; ".join(escaped))
        # the ONE leak oracle: pool reader threads, ledger worker
        # entries, shuffle temps, spills
        problems += ledger.leak_audit(shuffle_root=root,
                                      spills_before=spills_before)
    except Exception as e:  # noqa: BLE001 — the arm must report, not die
        problems.append(f"storm arm crashed: {type(e).__name__}: {e}")
    finally:
        conf.FAULTS_SPEC.set("")
        faults.reset()
        conf.TRACE_ENABLE.set(prev_trace)
        trace.reset()
        conf.TASK_RETRY_BACKOFF.set(prev_backoff)
        conf.TASK_MAX_ATTEMPTS.set(prev_task_att)
        conf.STAGE_MAX_ATTEMPTS.set(prev_stage_att)
        conf.HOST_BLACKLIST_MAX_FAILURES.set(prev_maxfail)
        conf.VERIFY_LOCKS.set(False)
        lock_verify.refresh()
        conf.VERIFY_LOCKSET.set(False)
        lockset.refresh()
        conf.VERIFY_ERRORS.set(False)
        errors.refresh()
        ledger.refresh()
    if problems:
        print(f"worker-kill-storm (seed {seed}): " + "; ".join(problems),
              file=sys.stderr)
        return 1
    print(f"worker-kill-storm (seed {seed}): OK ({len(lost_events)} "
          f"worker(s) lost, {lost_maps} map output(s) re-run, "
          f"{len(bl_events)} blacklisted, "
          f"{'degraded to local' if degraded_final else 'pool survived'}, "
          f"rows identical)")
    return 0


def _run_slo_storm(suite, seed, make_bundle=False) -> int:
    """SLO burn-rate storm chaos arm: a pool with a deliberately tight
    latency objective (``spark.blaze.slo.pool.etl.latencyP99Ms``, 2s
    accounting window) takes a burst of seeded straggler queries
    (``task.compute@N@slow<ms>`` injection) and the burn-rate evaluator
    must FIRE ``slo_alert_firing`` during the storm; after the faults
    clear and fast queries age the stragglers out of the slow window,
    the alert must RESOLVE (with the flap-suppression hold) — and the
    event log must reconcile: every firing paired with its resolve
    (``trace_report.reconcile_slo_alerts``), the dispatch counters
    agreeing with the events.  Gates: lockset checker and error-escape
    recorder quiet, the leak oracle clean, zero ``blaze-*`` threads
    left.  With ``make_bundle`` the arm finishes by writing an incident
    debug bundle, verifying its checksummed manifest, and re-rendering
    the profile OFFLINE from the bundle's copied logs alone."""
    import glob
    import random
    import shutil
    import tempfile
    import threading

    from . import conf
    from .analysis import locks as lock_verify
    from .batch import batch_from_pydict
    from .exprs import col, lit
    from .ops import MemoryScanExec, ProjectExec
    from .runtime import (
        bundle, dispatch, errors, faults, ledger, lockset, monitor, slo,
        trace, trace_report,
    )
    from .schema import DataType, Field, Schema

    rng = random.Random(seed * 52009 + 29)
    prev_trace = bool(conf.TRACE_ENABLE.get())
    prev_logdir = conf.EVENT_LOG_DIR.get()
    prev_slo = bool(conf.SLO_ENABLE.get())
    prev_eval_ms = conf.SLO_EVAL_INTERVAL_MS.get()
    prev_hold = conf.SLO_RESOLVE_HOLD_EVALS.get()
    conf.VERIFY_LOCKS.set(True)
    lock_verify.refresh()
    conf.VERIFY_LOCKSET.set(True)
    lockset.refresh()
    lockset.reset()
    conf.VERIFY_ERRORS.set(True)
    errors.refresh()
    ledger.refresh()
    problems = []
    spills_before = set(glob.glob(ledger.spill_glob()))
    n_storm = 8
    fired_events = resolved_events = 0
    schema = Schema([Field("x", DataType.int64())])

    def build_plan():
        src = MemoryScanExec(
            [[batch_from_pydict({"x": list(range(64))}, schema)]], schema)
        return ProjectExec(src, [(col("x") * lit(3)).alias("y")])

    try:
        disp_before = dispatch.counters()
        with tempfile.TemporaryDirectory(prefix="blaze_slostorm_") as td:
            conf.TRACE_ENABLE.set(True)
            conf.EVENT_LOG_DIR.set(td)
            trace.reset()
            conf.SLO_ENABLE.set(True)
            # evaluate essentially every observation, resolve after 2
            # consecutive clean evals (the flap-suppression hold)
            conf.SLO_EVAL_INTERVAL_MS.set(10)
            conf.SLO_FIRE_BURN_RATE.set(1.0)
            conf.SLO_RESOLVE_HOLD_EVALS.set(2)
            # the tight objective: stragglers sleep slow_ms, the p99
            # target sits at a quarter of that — every storm query is
            # a violation; the 2s window bounds how long the burn
            # lingers after recovery
            slow_ms = 80 + rng.randrange(60)
            conf.set_conf("spark.blaze.slo.pool.etl.latencyP99Ms",
                          slow_ms / 4.0)
            conf.set_conf("spark.blaze.slo.pool.etl.targetWindowSec", 2.0)
            slo.reset()
            # phase 1 — the storm: every storm query's single task hits
            # a seeded straggler injection and blows the objective
            conf.FAULTS_SPEC.set(",".join(
                f"task.compute@{i}@slow{slow_ms}"
                for i in range(1, n_storm + 1)))
            faults.reset()
            for i in range(n_storm):
                with monitor.query_span(f"slo_storm_{suite}_{i}",
                                        mode="scheduler", pool="etl"):
                    _rows_via_scheduler(build_plan())
            storm_doc = slo.doc()
            storm_firing = any(
                s["firing"]
                for p in storm_doc["pools"].values()
                for s in p["slos"].values())
            if not storm_firing:
                problems.append(
                    f"storm of {n_storm} stragglers ({slow_ms}ms vs "
                    f"{slow_ms / 4.0:.0f}ms p99) never fired the "
                    "burn-rate alert (vacuous arm)")
            # phase 2 — recovery: clear the faults and run fast
            # queries until the stragglers age out of the slow window
            # and the hold releases the alert
            conf.FAULTS_SPEC.set("")
            faults.reset()
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                with monitor.query_span(
                        f"slo_recover_{suite}", mode="scheduler",
                        pool="etl"):
                    pass
                slo.evaluate(force=True)
                d = slo.doc()
                if not any(s["firing"]
                           for p in d["pools"].values()
                           for s in p["slos"].values()):
                    break
                time.sleep(0.05)
            else:
                problems.append(
                    "alert still firing 10s after the faults cleared "
                    "(resolve path never engaged)")
            disp_after = dispatch.counters()
            events = trace_report.merge_event_logs(
                trace_report.event_log_files(td))
            stragglers = [e for e in events
                          if e.get("type") == "straggler_injected"]
            if not stragglers:
                problems.append("no straggler_injected events — the "
                                "storm injected nothing (vacuous arm)")
            recon = trace_report.reconcile_slo_alerts(events)
            fired_events = recon["fired"]
            resolved_events = recon["resolved"]
            if not fired_events:
                problems.append("no slo_alert_firing event in the log")
            if recon["still_firing"] or not recon["reconciled"]:
                problems.append(
                    f"slo alert pairing broken: {fired_events} fired / "
                    f"{resolved_events} resolved, "
                    f"{len(recon['still_firing'])} still firing, "
                    f"{len(recon['orphan_resolves'])} orphan resolve(s)")

            def delta(key):
                return disp_after.get(key, 0) - disp_before.get(key, 0)

            if delta("slo_alerts_fired") != fired_events \
                    or delta("slo_alerts_resolved") != resolved_events:
                problems.append(
                    f"slo counters disagree with the event log: fired "
                    f"{delta('slo_alerts_fired')}/{fired_events}, "
                    f"resolved {delta('slo_alerts_resolved')}"
                    f"/{resolved_events}")
            if make_bundle:
                # end-of-incident snapshot: checksummed manifest, then
                # prove the bundle re-renders OFFLINE from its own
                # copied logs (no access to the live log dir)
                bdir = tempfile.mkdtemp(prefix="blaze_slo_bundle_")
                try:
                    manifest = bundle.write_bundle(
                        bdir, query_id=f"slo_storm_{suite}_0")
                    problems += bundle.verify_bundle(bdir)
                    if not any(n.endswith(".jsonl")
                               for n in manifest["members"]):
                        problems.append(
                            "bundle carries no event-log member")
                    off = trace_report.merge_event_logs(
                        trace_report.event_log_files(bdir))
                    text = trace_report.render(off)
                    if "slo alerts" not in text:
                        problems.append("offline re-render of the "
                                        "bundle lacks the slo section")
                finally:
                    shutil.rmtree(bdir, ignore_errors=True)
        races = lockset.reported()
        if races:
            problems.append("lockset violation(s): " + "; ".join(races))
        escaped = errors.escapes()
        if escaped:
            problems.append("FATAL-class error escape(s): "
                            + "; ".join(escaped))
        problems += ledger.leak_audit(spills_before=spills_before)
        leaked = [t.name for t in threading.enumerate()
                  if t.name.startswith("blaze-")]
        if leaked:
            problems.append(f"leaked blaze-* thread(s): {leaked}")
    except Exception as e:  # noqa: BLE001 — the arm must report, not die
        problems.append(f"storm arm crashed: {type(e).__name__}: {e}")
    finally:
        conf.FAULTS_SPEC.set("")
        faults.reset()
        conf.TRACE_ENABLE.set(prev_trace)
        conf.EVENT_LOG_DIR.set(prev_logdir)
        trace.reset()
        conf.SLO_ENABLE.set(prev_slo)
        conf.SLO_EVAL_INTERVAL_MS.set(prev_eval_ms)
        conf.SLO_RESOLVE_HOLD_EVALS.set(prev_hold)
        conf.set_conf("spark.blaze.slo.pool.etl.latencyP99Ms", None)
        conf.set_conf("spark.blaze.slo.pool.etl.targetWindowSec", None)
        slo.reset()
        conf.VERIFY_LOCKS.set(False)
        lock_verify.refresh()
        conf.VERIFY_LOCKSET.set(False)
        lockset.refresh()
        conf.VERIFY_ERRORS.set(False)
        errors.refresh()
        ledger.refresh()
    if problems:
        print(f"slo-storm (seed {seed}): " + "; ".join(problems),
              file=sys.stderr)
        return 1
    print(f"slo-storm (seed {seed}): OK ({fired_events} alert(s) fired, "
          f"{resolved_events} resolved, reconciled"
          + (", bundle verified" if make_bundle else "") + ")")
    return 0


def _run_cache_storm(suite, names, scans, build_query, n_parts,
                     seed) -> int:
    """Cache-storm chaos arm: concurrent IDENTICAL and literal-SHIFTED
    submissions against one serving table, with a seeded mid-storm
    source mutation racing the second wave — asserting the result
    cache's integrity contract end to end: every completed query is
    byte-identical to an UNCACHED baseline for some epoch the query
    could have observed, post-mutation queries never see pre-mutation
    rows, every admission resolves as exactly one result-cache hit or
    miss (hits + misses == submissions), hits never take a lease turn,
    and nothing leaks.  Lockset + lock-order + error-escape checkers
    are armed for the whole arm; the shared leak oracle sweeps after.

    The arm builds its own MemoryScan-backed table (the suite scans
    are shared across seeds and must not be mutated); the suite args
    are accepted for wiring symmetry with the other storm arms."""
    import glob
    import os
    import random
    import tempfile
    import threading

    from . import conf
    from .analysis import locks as lock_verify
    from .batch import batch_from_pydict, batch_to_pydict
    from .exprs import col, lit
    from .ops.filter import FilterExec
    from .ops.memory_scan import MemoryScanExec
    from .ops.project import ProjectExec
    from .runtime import (dispatch, errors, ledger, lockset, monitor,
                          querycache, service)
    from .schema import DataType, Field, Schema

    schema = Schema([Field("k", DataType.int64()),
                     Field("v", DataType.float64())])
    rng = random.Random(seed * 92821 + 11)
    knobs = (conf.SERVICE_MAX_CONCURRENT, conf.SERVICE_MAX_QUEUED,
             conf.SERVICE_QUEUE_TIMEOUT_MS, conf.MONITOR_ENABLE)
    prev = [k.get() for k in knobs]
    conf.VERIFY_LOCKS.set(True)
    lock_verify.refresh()
    conf.VERIFY_LOCKSET.set(True)
    lockset.refresh()
    lockset.reset()
    conf.VERIFY_ERRORS.set(True)
    errors.refresh()
    ledger.refresh()
    problems = []
    svc = None
    shuffle_glob = os.path.join(tempfile.gettempdir(), "blaze_shuffle_*")
    spills_before = set(glob.glob(ledger.spill_glob()))
    roots_before = set(glob.glob(shuffle_glob))
    n_subs = 0
    n_hits = n_misses = 0
    try:
        try:
            querycache.reset_for_tests()
            # one serving table, two partitions — the mutation appends
            # to a SINGLE seeded partition, so a racing scan observes
            # either the old or the new table, never a torn mixture
            n_rows = 400
            half = n_rows // 2
            table = MemoryScanExec([
                [batch_from_pydict({
                    "k": list(range(p * half, p * half + half)),
                    "v": [rng.uniform(0.0, 10.0) for _ in range(half)],
                }, schema)] for p in range(2)])

            def build_plan(thresh):
                f = FilterExec(table, col("v") > lit(float(thresh)))
                return ProjectExec(f, [col("k"), col("v") * lit(2.0)],
                                   ["k", "v2"])

            # identical + literal-shifted: two slot values, each
            # submitted repeatedly — same fingerprint digest, distinct
            # result-cache keys
            threshes = (2.0, 7.0)
            base_old = {t: _rows_via_scheduler(build_plan(t))
                        for t in threshes}
            conf.SERVICE_MAX_CONCURRENT.set(2)
            conf.SERVICE_MAX_QUEUED.set(32)
            conf.SERVICE_QUEUE_TIMEOUT_MS.set(0)
            conf.MONITOR_ENABLE.set(True)
            monitor.reset()
            svc = service.QueryService().start()
            c0 = dict(dispatch.counters())

            def rows_of(batches):
                cols = None
                for b in batches:
                    d = batch_to_pydict(b)
                    if cols is None:
                        cols = {c: [] for c in d}
                    for c, vals in d.items():
                        cols[c].extend(vals)
                if cols is None:
                    return []
                ns = sorted(cols)
                return sorted(zip(*[cols[c] for c in ns])) if ns else []

            def submit_wave(tag, mutate_at=None):
                """One concurrent burst: 3 identical submissions per
                slot value, rng-shuffled; optionally fire the source
                mutation from a seeded delay mid-wave."""
                order = [t for t in threshes for _ in range(3)]
                rng.shuffle(order)
                handles = [None] * len(order)

                def submitter(i, t):
                    handles[i] = svc.submit(
                        f"cache-{tag}-{i}",
                        build=lambda _t=t: build_plan(_t))

                ts = [threading.Thread(target=submitter, args=(i, t),
                                       name=f"blaze-cache-submit-{i}",
                                       daemon=True)
                      for i, t in enumerate(order)]
                mut = None
                if mutate_at is not None:
                    part = rng.randrange(2)

                    def mutator():
                        time.sleep(mutate_at)
                        table.append(part, batch_from_pydict(
                            {"k": [n_rows, n_rows + 1],
                             "v": [9.5, 9.75]}, schema))
                    mut = threading.Thread(target=mutator,
                                           name="blaze-cache-mutator",
                                           daemon=True)
                    mut.start()
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(30)
                if mut is not None:
                    mut.join(30)
                return list(zip(order, handles))

            def drain(pairs, allowed_by_thresh, tag):
                for t, h in pairs:
                    if h is None:
                        problems.append(f"{tag}: a submitter never "
                                        f"resolved (thresh {t})")
                        continue
                    try:
                        got = rows_of(h.result(timeout=120))
                    except Exception as e:  # noqa: BLE001 — judged here
                        problems.append(f"{tag} {h.exec_id}: "
                                        f"{type(e).__name__}: {e}")
                        continue
                    if got not in allowed_by_thresh[t]:
                        problems.append(
                            f"{tag} {h.exec_id}: rows diverge from every "
                            f"admissible uncached baseline for thresh {t} "
                            f"({len(got)} rows)")

            # wave 1: all against the epoch-0 table — exact baseline
            w1 = submit_wave("w1")
            n_subs += len(w1)
            drain(w1, {t: (base_old[t],) for t in threshes}, "wave1")
            # sequential repeats: entries are resident now, so these
            # MUST be result-cache hits served with zero lease turns
            hits_before = dict(dispatch.counters()).get(
                "result_cache_hits", 0)
            for t in threshes:
                h = svc.submit(f"cache-repeat-{t}",
                               build=lambda _t=t: build_plan(_t))
                n_subs += 1
                got = rows_of(h.result(timeout=120))
                if got != base_old[t]:
                    problems.append(f"repeat thresh {t}: cached rows "
                                    f"diverge from uncached baseline")
            hits_now = dict(dispatch.counters()).get(
                "result_cache_hits", 0)
            if hits_now - hits_before != len(threshes):
                problems.append(
                    f"warm identical repeats produced "
                    f"{hits_now - hits_before} result-cache hits "
                    f"(expected {len(threshes)})")
            # wave 2: the seeded mutation races the burst — a query
            # may observe either epoch, but must match ONE of them
            w2 = submit_wave("w2", mutate_at=rng.uniform(0.0, 0.05))
            n_subs += len(w2)
            base_new = {t: _rows_via_scheduler(build_plan(t))
                        for t in threshes}
            drain(w2, {t: (base_old[t], base_new[t]) for t in threshes},
                  "wave2")
            # post-mutation queries must NEVER see pre-mutation rows:
            # the appended keys are filter-visible at both slot values
            for t in threshes:
                h = svc.submit(f"cache-post-{t}",
                               build=lambda _t=t: build_plan(_t))
                n_subs += 1
                got = rows_of(h.result(timeout=120))
                if got != base_new[t]:
                    problems.append(
                        f"STALE RESULT: post-mutation thresh {t} served "
                        f"{len(got)} rows != epoch-{table.epoch} "
                        f"baseline {len(base_new[t])}")
            cf = dict(dispatch.counters())
            n_hits = cf.get("result_cache_hits", 0) \
                - c0.get("result_cache_hits", 0)
            n_misses = cf.get("result_cache_misses", 0) \
                - c0.get("result_cache_misses", 0)
            if n_hits + n_misses != n_subs:
                problems.append(
                    f"cache accounting leak: {n_hits} hits + {n_misses} "
                    f"misses != {n_subs} submissions")
            if cf.get("result_cache_invalidations", 0) \
                    <= c0.get("result_cache_invalidations", 0):
                problems.append("the source mutation never invalidated "
                                "a cached result")
            turns = svc.stats()["counters"].get("cache_hit_lease_turns", 0)
            if turns:
                problems.append(f"cache hits took {turns} fair-share "
                                f"lease turn(s) (must be served "
                                f"off-device, before admission)")
            races = lockset.reported()
            if races:
                problems.append("lockset violation(s): " + "; ".join(races))
            escaped = errors.escapes()
            if escaped:
                problems.append("FATAL-class error escape(s): "
                                + "; ".join(escaped))
        except Exception as e:  # noqa: BLE001 — the arm must report, not die
            problems.append(f"cache storm crashed: {type(e).__name__}: {e}")
        finally:
            if svc is not None:
                svc.shutdown()
            for k, v in zip(knobs, prev):
                k.set(v)
            monitor.reset()
            querycache.reset_for_tests()
            conf.VERIFY_LOCKS.set(False)
            lock_verify.refresh()
            conf.VERIFY_LOCKSET.set(False)
            lockset.refresh()
        leaked = [t.name for t in service.service_threads()]
        if leaked:
            problems.append("leaked threads: " + ", ".join(leaked))
        problems += ledger.leak_audit(
            shuffle_root=sorted(set(glob.glob(shuffle_glob)) - roots_before),
            spills_before=spills_before)
    finally:
        conf.VERIFY_ERRORS.set(False)
        errors.refresh()
        ledger.refresh()
    if problems:
        print(f"cache-storm (seed {seed}): " + "; ".join(problems),
              file=sys.stderr)
        return 1
    print(f"cache-storm (seed {seed}): OK ({n_subs} submissions = "
          f"{n_hits} result-cache hit(s) + {n_misses} miss(es), "
          f"1 mid-storm mutation, 0 stale rows, 0 hit lease turns)")
    return 0


def _run_skew_storm(suite, seed) -> int:
    """Skew-storm chaos arm: a seeded zipf-skewed hash exchange (~80%
    of rows sharing ONE hot key) through the stage scheduler with the
    runtime-stats observatory armed — asserting the skew detector end
    to end: exactly one ``stats_skew_detected`` event fires, it names
    the hot partition id (computed up front from the same murmur3 pmod
    the exchange uses), the stats registry's findings reconcile with
    the event log, the stats store commits without ``.inprogress``
    litter, and the lockset / error-escape / leak oracles stay quiet.

    The arm builds its own skewed MemoryScan table (suite data is
    deliberately well-distributed); the suite arg is accepted for
    wiring symmetry with the other storm arms."""
    import glob
    import os
    import random
    import shutil
    import tempfile

    import numpy as np

    from . import conf
    from .analysis import locks as lock_verify
    from .batch import batch_from_pydict, column_from_numpy
    from .exprs import col
    from .exprs.hash import murmur3_columns, pmod
    from .ops.memory_scan import MemoryScanExec
    from .parallel.exchange import NativeShuffleExchangeExec
    from .parallel.shuffle import HashPartitioning
    from .runtime import errors, ledger, lockset, monitor, stats, trace
    from .schema import DataType, Field, Schema

    rng = random.Random(seed * 48271 + 3)
    knobs = (conf.STATS_ENABLED, conf.STATS_SKETCHES,
             conf.STATS_STORE_ENABLED, conf.STATS_STORE_DIR,
             conf.STATS_SKEW_RATIO, conf.STATS_SKEW_MIN_ROWS,
             conf.TRACE_ENABLE, conf.EVENT_LOG_DIR, conf.MONITOR_ENABLE)
    prev = [k.get() for k in knobs]
    conf.VERIFY_LOCKS.set(True)
    lock_verify.refresh()
    conf.VERIFY_LOCKSET.set(True)
    lockset.refresh()
    lockset.reset()
    conf.VERIFY_ERRORS.set(True)
    errors.refresh()
    ledger.refresh()
    problems = []
    shuffle_glob = os.path.join(tempfile.gettempdir(), "blaze_shuffle_*")
    spills_before = set(glob.glob(ledger.spill_glob()))
    roots_before = set(glob.glob(shuffle_glob))
    store_dir = tempfile.mkdtemp(prefix="blaze_skew_store_")
    log_dir = tempfile.mkdtemp(prefix="blaze_skew_log_")
    hot_pid = -1
    try:
        try:
            conf.STATS_ENABLED.set(True)
            conf.STATS_SKETCHES.set(True)
            conf.STATS_STORE_ENABLED.set(True)
            conf.STATS_STORE_DIR.set(store_dir)
            conf.STATS_SKEW_RATIO.set(3.0)
            conf.STATS_SKEW_MIN_ROWS.set(256)
            conf.TRACE_ENABLE.set(True)
            conf.EVENT_LOG_DIR.set(log_dir)
            conf.MONITOR_ENABLE.set(True)
            stats.refresh()
            stats.reset()
            trace.reset()
            monitor.reset()

            # the seeded zipf-ish table: ~80% of rows share ONE hot
            # key, the rest spread over a 2^20 key space — hashed into
            # 8 partitions this MUST trip the detector, and the hot
            # partition id is computable up front from the same
            # murmur3(seed42) pmod the exchange runs
            n_out = 8
            n_rows = 8192
            hot_key = rng.randrange(1 << 20)
            keys = [hot_key if rng.random() < 0.8
                    else rng.randrange(1 << 20) for _ in range(n_rows)]
            schema = Schema([Field("k", DataType.int64()),
                             Field("v", DataType.float64())])
            quarter = n_rows // 4
            table = MemoryScanExec([
                [batch_from_pydict({
                    "k": keys[p * quarter:(p + 1) * quarter],
                    "v": [rng.uniform(0.0, 1.0) for _ in range(quarter)],
                }, schema)] for p in range(4)])
            kcol = column_from_numpy(
                DataType.int64(), np.array([hot_key], np.int64))
            hot_pid = int(np.asarray(
                pmod(murmur3_columns([kcol.to_device()]), n_out))[0])

            with monitor.query_span(f"skew-storm-{seed}",
                                    mode="chaos") as log_path:
                _rows_via_scheduler(NativeShuffleExchangeExec(
                    table, HashPartitioning([col("k")], n_out)))
            if log_path is None:
                raise RuntimeError(
                    "tracing did not arm (a BLAZE_TRACE_ENABLED env "
                    "override?) — the skew storm judges the event log")
            events = trace.read_event_log(log_path)
            skews = [e for e in events
                     if e.get("type") == "stats_skew_detected"]
            if len(skews) != 1:
                problems.append(
                    f"expected exactly 1 stats_skew_detected event, "
                    f"got {len(skews)}")
            else:
                ev = skews[0]
                if ev.get("partition") != hot_pid:
                    problems.append(
                        f"skew event names partition "
                        f"{ev.get('partition')}, expected hot "
                        f"partition {hot_pid}")
                if ev.get("ratio", 0.0) < 3.0:
                    problems.append(
                        f"skew ratio {ev.get('ratio')} below the "
                        f"3.0 threshold that fired it")
            # the registry's findings must reconcile with the event
            # log — same findings, same hot partitions, same rows
            summary = stats.last_query_stats() or {}
            reg = summary.get("findings", [])
            if [(f.get("partition"), f.get("rows")) for f in reg] != \
                    [(e.get("partition"), e.get("rows")) for e in skews]:
                problems.append(
                    f"stats registry findings ({len(reg)}) diverge "
                    f"from the event log ({len(skews)})")
            if not any(e.get("type") == "stats_persisted"
                       for e in events):
                problems.append("no stats_persisted event — the exact "
                                "map-stage plan never reached the store")
            stray = [p for p in os.listdir(store_dir)
                     if not p.endswith(".json")]
            if stray:
                problems.append("stats store litter: " + ", ".join(stray))
            races = lockset.reported()
            if races:
                problems.append("lockset violation(s): " + "; ".join(races))
            escaped = errors.escapes()
            if escaped:
                problems.append("FATAL-class error escape(s): "
                                + "; ".join(escaped))
        except Exception as e:  # noqa: BLE001 — the arm must report, not die
            problems.append(f"skew storm crashed: {type(e).__name__}: {e}")
        finally:
            for k, v in zip(knobs, prev):
                k.set(v)
            stats.refresh()
            stats.reset()
            trace.reset()
            monitor.reset()
            conf.VERIFY_LOCKS.set(False)
            lock_verify.refresh()
            conf.VERIFY_LOCKSET.set(False)
            lockset.refresh()
        problems += ledger.leak_audit(
            shuffle_root=sorted(set(glob.glob(shuffle_glob)) - roots_before),
            spills_before=spills_before)
    finally:
        conf.VERIFY_ERRORS.set(False)
        errors.refresh()
        ledger.refresh()
        shutil.rmtree(store_dir, ignore_errors=True)
        shutil.rmtree(log_dir, ignore_errors=True)
    if problems:
        print(f"skew-storm (seed {seed}): " + "; ".join(problems),
              file=sys.stderr)
        return 1
    print(f"skew-storm (seed {seed}): OK (1 skew finding, hot partition "
          f"{hot_pid}, registry == event log, store + ledger clean)")
    return 0


def _live_attempt_threads():
    """Attempt-runner threads still alive after a run — kept as a thin
    alias of the shared leak oracle's thread check
    (``ledger.attempt_threads``) for external callers; the chaos arms
    now go through :func:`ledger.leak_audit` directly."""
    from .runtime import ledger

    return ledger.attempt_threads()


def _serve_forever() -> int:
    """Bare ``--serve``: keep the already-started monitor service in
    the foreground until interrupted, then shut down cleanly."""
    print("# monitor: serving until interrupted (Ctrl-C to stop)")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        rc = _shutdown_monitor_checked()
    return rc


def _shutdown_otel_checked() -> int:
    """Stop the OTLP push loop and verify nothing leaked — the
    ``--otel`` sibling of the monitor shutdown gate."""
    from .runtime import otel

    otel.shutdown_pusher()
    leaked = otel.otel_threads()
    if leaked:
        print("# otel: THREAD LEAK after shutdown: "
              + ", ".join(t.name for t in leaked), file=sys.stderr)
        return 1
    return 0


def _shutdown_monitor_checked() -> int:
    """Stop the monitor server and verify nothing leaked: a long-lived
    background service must never wedge process exit (nonzero when a
    blaze-monitor thread survives shutdown)."""
    from .runtime import monitor

    monitor.shutdown_server()
    leaked = monitor.monitor_threads()
    if leaked:
        print("# monitor: THREAD LEAK after shutdown: "
              + ", ".join(t.name for t in leaked), file=sys.stderr)
        return 1
    return 0


def _watch(target: str, interval: float, polls: int,
           json_out: str = "") -> int:
    """``--watch``: poll a running monitor's /queries endpoint and
    render a refreshing stage-progress table.  With ``--json`` each
    poll emits the raw snapshot document as ONE JSON line instead —
    ``-`` keeps stdout pure JSON (status chatter moves to stderr), a
    path appends JSONL."""
    import json as _json
    import urllib.error
    import urllib.request

    from . import conf
    from .runtime import monitor

    if target == "default":
        url = f"http://127.0.0.1:{int(conf.MONITOR_PORT.get())}"
    elif target.isdigit():
        url = f"http://127.0.0.1:{target}"
    else:
        url = target.rstrip("/")
    done = 0
    try:
        while True:
            try:
                with urllib.request.urlopen(url + "/queries", timeout=5) as r:
                    snap = _json.load(r)
            except (urllib.error.URLError, OSError, ValueError) as e:
                if done:
                    # the server WAS reachable: a monitored run shuts
                    # its service down at end-of-run — that is a
                    # normal end of the watch, not a failure
                    print(f"watch: monitor at {url} gone "
                          "(run finished?)", file=sys.stderr)
                    return 0
                print(f"watch: cannot reach {url}/queries: {e}",
                      file=sys.stderr)
                return 1
            if json_out:
                # machine-readable mode: the /queries document (which
                # carries the workers/pool/slo blocks too) verbatim,
                # one JSON line per poll
                line = _json.dumps(snap, default=str)
                if json_out == "-":
                    print(line, flush=True)
                else:
                    with open(json_out, "a") as f:
                        f.write(line + "\n")
            else:
                # clear + home, then one frame (plain append when piped)
                prefix = "\x1b[2J\x1b[H" if sys.stdout.isatty() else ""
                print(prefix + monitor.render_watch(snap, url), flush=True)
            done += 1
            if polls and done >= polls:
                return 0
            time.sleep(interval)
    except KeyboardInterrupt:
        return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m blaze_tpu",
        description="Run TPC-H / TPC-DS queries through the engine.",
    )
    ap.add_argument("suite", nargs="?", choices=["tpch", "tpcds"],
                    default="tpch")
    ap.add_argument("queries", nargs="*", default=None,
                    help="query names (q1, q6, ...) or 'all' "
                         "(default: q6 under --chaos)")
    ap.add_argument("--scale", type=float, default=0.01,
                    help="datagen scale factor (default 0.01)")
    ap.add_argument("--parts", type=int, default=2,
                    help="partitions per table (default 2)")
    ap.add_argument("--scheduler", action="store_true",
                    help="run through the stage scheduler (TaskDefinition "
                         "bytes + shuffle files) instead of in-process")
    ap.add_argument("--warmup", action="store_true",
                    help="populate the kernel + persistent XLA compile "
                         "caches (spark.blaze.xla.cacheDir) by running the "
                         "queries twice; exit nonzero if the warm run "
                         "recompiles anything")
    ap.add_argument("--explain", action="store_true",
                    help="EXPLAIN ANALYZE: warm each query, re-run it "
                         "traced through the stage scheduler, and render "
                         "the metric-annotated plan (per-node rows/bytes/"
                         "batches + %% of query wall, fused-chain markers, "
                         "per-kernel roofline, dispatch/memory/compute "
                         "bound classification); --json writes the "
                         "golden-pinned explain document")
    ap.add_argument("--lint", action="store_true",
                    help="run the static-analysis passes (blaze_tpu/analysis/)"
                         ": AST lint (trace purity, stray jax.jit, "
                         "emit-under-lock, lock order), conf-registry drift, "
                         "and the plan verifier over every TPC-H/TPC-DS plan "
                         "fused+unfused; exit nonzero on any finding")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-injection smoke: run each query fault-free "
                         "and under a seeded random fault schedule, with the "
                         "plan verifier and runtime lock-order assertion "
                         "armed; exit nonzero on result mismatch or either "
                         "verifier firing")
    ap.add_argument("--chaos-seed", type=int, default=7,
                    help="seed for the chaos fault schedule (default 7)")
    ap.add_argument("--chaos-faults", type=int, default=3,
                    help="faults per scheduled chaos run (default 3)")
    ap.add_argument("--chaos-seeds", type=int, default=0, metavar="N",
                    help="sweep mode: run the chaos smoke N times with "
                         "seeds chaos-seed..chaos-seed+N-1 (implies "
                         "--chaos); the FIRST seed additionally arms "
                         "speculation with an injected straggler, the "
                         "SECOND injects a mid-query device OOM the "
                         "degradation ladder must absorb, and every seed "
                         "ends with a cancel-storm arm (seeded random "
                         "cancel at a random stage frontier) plus an "
                         "admission-storm arm (a concurrent submission "
                         "burst past the service queue bound with seeded "
                         "stragglers and one mid-flight cancel) plus a "
                         "corruption-storm arm (seeded @corrupt bit flips "
                         "on shuffle/spill blocks + @enospc disk-full "
                         "under a spill-forcing budget, asserting zero "
                         "silent wrong results and every corruption "
                         "detected+recovered) plus a worker-kill-storm "
                         "arm (pooled worker processes SIGKILLed "
                         "mid-stage by a seeded @kill schedule, "
                         "asserting partial re-run of only the dead "
                         "worker's map outputs, blacklisting, and "
                         "degradation to in-process execution) plus a "
                         "cache-storm arm (concurrent identical + "
                         "literal-shifted submissions with a seeded "
                         "mid-storm source mutation, asserting "
                         "byte-identical results vs an uncached "
                         "baseline, hits + misses == submissions, and "
                         "zero lease turns on hits) plus an slo-storm "
                         "arm (seeded stragglers against a tight "
                         "per-pool burn-rate objective, asserting the "
                         "alert fires during the storm, resolves after "
                         "recovery, and reconciles in the event log; "
                         "the first seed also writes and verifies an "
                         "incident debug bundle) plus a skew-storm arm "
                         "(a seeded zipf-skewed hash exchange with the "
                         "runtime-stats observatory armed, asserting "
                         "exactly one stats_skew_detected event naming "
                         "the precomputed hot partition, registry == "
                         "event-log reconciliation, and a clean stats "
                         "store commit); nonzero "
                         "exit on any mismatch, unreconciled event log, "
                         "hung or untyped submission, leaked thread, "
                         "undetected corruption, unrecovered worker "
                         "loss, stale cached result, or orphaned "
                         "temp/spill file")
    ap.add_argument("--trace", action="store_true",
                    help="arm the structured event log "
                         "(spark.blaze.trace.enabled) for this run; each "
                         "query writes its own JSONL file under "
                         "spark.blaze.eventLog.dir")
    ap.add_argument("--event-log-dir", default="",
                    help="event-log directory for --trace (default: conf "
                         "spark.blaze.eventLog.dir, else "
                         "<tmp>/blaze_eventlog)")
    ap.add_argument("--report", default="",
                    help="render the per-query profile from a JSONL event "
                         "log produced by --trace / --chaos and exit; a "
                         "DIRECTORY merges every *.jsonl segment in it "
                         "(driver + worker-subprocess logs reconciled by "
                         "their shared trace id) into one report")
    ap.add_argument("--flame", default="", metavar="PATH",
                    help="with --report: also write the query's flame "
                         "profile as collapsed-stack lines ('-' = stdout) "
                         "consumable by flamegraph.pl / speedscope — "
                         "kernel device/dispatch/compile splits per stage "
                         "plus the plan-node tree weighted by "
                         "elapsed_compute")
    ap.add_argument("--debug-bundle", default="", metavar="DIR",
                    help="write an incident debug bundle into DIR after "
                         "the run (implies --trace and arms the monitor "
                         "registry): every event-log segment, metrics "
                         "text, redacted conf dump, queries/workers/slo "
                         "documents, EXPLAIN + flame stacks, and the "
                         "verification ledgers, all checksummed in a "
                         "manifest; re-render offline with --report DIR")
    ap.add_argument("--otel", action="store_true",
                    help="arm OTLP span export (spark.blaze.otel.enabled; "
                         "implies --trace): each query's event log exports "
                         "as an OTLP/JSON span tree to the file sink "
                         "(spark.blaze.otel.dir) and, when an endpoint is "
                         "set, the blaze-otel-push loop")
    ap.add_argument("--otel-endpoint", default="", metavar="URL",
                    help="with --otel: best-effort OTLP/HTTP collector "
                         "endpoint (spark.blaze.otel.endpoint, e.g. "
                         "http://localhost:4318/v1/traces)")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="with --report: also write the full profile as "
                         "one JSON document (stage timeline, dispatch-floor "
                         "split, kernel table, recovery pairing) to PATH "
                         "('-' = stdout instead of the text rendering); "
                         "with --lint: write the findings as one JSON "
                         "document (rule id, path, line, symbol, waived "
                         "flag + summary) so CI can diff lint runs; "
                         "with --watch: emit one JSON snapshot per poll "
                         "('-' = stdout stays pure JSONL) instead of the "
                         "rendered table")
    ap.add_argument("--sarif", default="", metavar="PATH",
                    help="with --lint: also write the findings as one "
                         "SARIF 2.1.0 document ('-' = stdout, pure like "
                         "--json -) for GitHub code-scanning / any SARIF "
                         "viewer — waived findings ride as suppressed "
                         "notes with their pinned justifications")
    ap.add_argument("--service", action="store_true",
                    help="run the multi-tenant query service "
                         "(runtime/service.py: admission control, "
                         "fair-share pools, per-pool quotas, "
                         "backpressure, supervision) over the loaded "
                         "suite; with query names they are submitted "
                         "concurrently round-robin across --pools, bare "
                         "--service serves POST /service/submit until "
                         "interrupted (429 on shed)")
    ap.add_argument("--pools", default="",
                    help="with --service: comma list of pool[:weight] "
                         "fair-share pools submissions round-robin "
                         "across (default one 'default' pool), e.g. "
                         "'etl:3,adhoc:1'")
    ap.add_argument("--serve", action="store_true",
                    help="run the live monitoring HTTP service "
                         "(/metrics Prometheus text, /queries JSON); bare "
                         "--serve serves in the foreground until "
                         "interrupted, with queries it serves for the "
                         "duration of the run")
    ap.add_argument("--monitor", action="store_true",
                    help="arm the live query monitor "
                         "(spark.blaze.monitor.enabled) for this run: "
                         "registry + background HTTP server; asserts a "
                         "clean, thread-leak-free shutdown afterwards")
    ap.add_argument("--monitor-port", type=int, default=None,
                    help="monitor HTTP port (default: conf "
                         "spark.blaze.monitor.port; 0 = ephemeral)")
    ap.add_argument("--watch", nargs="?", const="default", default=None,
                    metavar="URL|PORT",
                    help="poll a running monitor's /queries and render a "
                         "refreshing stage-progress table (default "
                         "http://127.0.0.1:<spark.blaze.monitor.port>)")
    ap.add_argument("--watch-interval", type=float, default=1.0,
                    help="--watch poll interval in seconds (default 1.0)")
    ap.add_argument("--watch-polls", type=int, default=0,
                    help="--watch: stop after N polls (0 = until ^C)")
    args = ap.parse_args(argv)
    if args.json and not (args.report or args.lint or args.explain
                          or args.watch is not None):
        ap.error("--json requires --report (profile as JSON), --lint "
                 "(findings as JSON), --explain (explain document), "
                 "or --watch (one snapshot per poll)")
    if args.sarif and not args.lint:
        ap.error("--sarif requires --lint (findings as SARIF)")
    if args.sarif == "-" and args.json == "-":
        ap.error("--sarif - and --json - both claim stdout; write at "
                 "least one to a file")
    if args.chaos_seeds:
        args.chaos = True
    if args.lint:
        return _run_lint(args.json, args.sarif)
    if args.flame and not args.report:
        ap.error("--flame requires --report (flame profile from an "
                 "event log)")
    if args.report:
        import os as _os

        from .runtime import trace, trace_report

        try:
            if _os.path.isdir(args.report):
                # a DIRECTORY of segments: the driver's per-query log
                # plus worker subprocesses' own logs, reconciled into
                # one time-ordered stream (shared trace id = join key)
                events = trace_report.merge_event_logs(
                    trace_report.event_log_files(args.report))
            else:
                # reads a rotated set too (spark.blaze.eventLog.maxBytes
                # rollover): <path>.seg1..N then the active file
                events = trace.read_event_log(args.report)
        except OSError as e:
            print(f"cannot read event log: {e}", file=sys.stderr)
            return 2
        if not events:
            print(f"no events in {args.report}", file=sys.stderr)
            return 1
        if args.flame == "-" and args.json == "-":
            ap.error("--flame - and --json - both claim stdout; "
                     "write at least one to a file")
        if args.json and args.json != "-":
            # the JSON profile lands BEFORE a streaming flame exit, so
            # `--flame - --json out.json` produces both artifacts
            import json as _json

            with open(args.json, "w") as f:
                _json.dump(trace_report.render_json(events), f, indent=2,
                           default=str)
            print(f"# json profile: {args.json}", file=sys.stderr)
            args.json = ""
        if args.flame:
            n = trace_report.write_flame(events, args.flame)
            if args.flame == "-":
                # stdout is the PARSEABLE collapsed-stack stream and
                # nothing else (the --json - contract)
                return 0
            print(f"# flame profile: {args.flame} ({n} stacks)")
        if args.json:
            import json as _json

            doc = trace_report.render_json(events)
            if args.json == "-":
                print(_json.dumps(doc, indent=2, default=str))
                return 0
            with open(args.json, "w") as f:
                _json.dump(doc, f, indent=2, default=str)
            print(f"# json profile: {args.json}")
        print(trace_report.render(events))
        return 0
    if args.watch is not None:
        if args.monitor_port is not None:
            # the default watch target honors an explicit port (this
            # branch returns before the --serve/--monitor conf wiring)
            from . import conf

            conf.MONITOR_PORT.set(args.monitor_port)
        return _watch(args.watch, args.watch_interval, args.watch_polls,
                      json_out=args.json)
    if (args.trace or args.event_log_dir or args.otel
            or args.otel_endpoint or args.debug_bundle):
        from . import conf
        from .runtime import trace

        # --event-log-dir applies on its own too: --chaos arms tracing
        # itself, and its logs must land where the user pointed
        if args.trace or args.otel or args.otel_endpoint or args.debug_bundle:
            # OTLP export converts the event log: --otel (and a bare
            # --otel-endpoint) implies --trace — otherwise every query
            # span yields no log and the export is silently empty
            conf.TRACE_ENABLE.set(True)
        if args.event_log_dir:
            conf.EVENT_LOG_DIR.set(args.event_log_dir)
        trace.reset()
    if args.otel or args.otel_endpoint:
        from . import conf
        from .runtime import otel

        conf.OTEL_ENABLE.set(True)
        if args.otel_endpoint:
            conf.OTEL_ENDPOINT.set(args.otel_endpoint)
        otel.reset()
    # --debug-bundle needs the registry live: the bundle's queries /
    # workers / explain / flame members all read the monitor
    monitor_armed = (args.serve or args.monitor or args.service
                     or bool(args.debug_bundle))
    if monitor_armed:
        from . import conf
        from .runtime import monitor

        conf.MONITOR_ENABLE.set(True)
        if args.monitor_port is not None:
            conf.MONITOR_PORT.set(args.monitor_port)
        monitor.reset()
        srv = monitor.ensure_server()
        if srv is not None:
            print(f"# monitor: {srv.url}/metrics  {srv.url}/queries")
        else:
            # the registry still runs (a later --watch of another
            # process won't see us, but the run must not die for its
            # own observability)
            print("# monitor: registry armed, server unavailable",
                  file=sys.stderr)
    queries = args.queries or (
        ["q6"] if args.chaos else ["q1", "q6"] if args.warmup
        else ["q1"] if args.explain else None
    )
    if args.explain:
        return _run_explain(args.suite, queries, args.scale, args.parts,
                            args.json)
    if args.service:
        try:
            rc = _run_service(args.suite, args.queries, args.scale,
                              args.parts, pools=args.pools)
        finally:
            # the monitor server hosts the service endpoints: its
            # shutdown/leak gate folds into the exit code here too
            leak_rc = _shutdown_monitor_checked()
        return rc or leak_rc
    if not queries:
        if args.serve:
            return _serve_forever()
        ap.error("query names required (or pass --chaos / --warmup / "
                 "--serve for the defaults)")
    # persistent compile cache for plain runs too
    if not args.warmup:
        from .runtime.kernel_cache import enable_persistent_cache

        enable_persistent_cache()
    rc = 0
    try:
        if args.warmup:
            rc = _warmup(args.suite, queries, args.scale, args.parts)
        elif args.chaos_seeds:
            # seed sweep: N independent schedules; the first also arms
            # speculation against an injected straggler, the second
            # injects a mid-query device OOM the degradation ladder
            # must absorb, and EVERY seed ends with the storm battery:
            # cancel, admission, corruption, worker-kill, cache
            # (concurrent identical/literal-shifted submissions racing
            # a seeded source mutation), and slo (seeded stragglers
            # against a tight burn-rate objective; the first seed also
            # writes + verifies an incident debug bundle).  Datagen is
            # seed-independent:
            # resolve the suite ONCE and share it across every seed's
            # arms.
            loaded = _load_suite(args.suite, queries, args.scale,
                                 args.parts)
            bq, qnames, scans = loaded
            if bq is None:
                return qnames
            rc = 0
            for k in range(args.chaos_seeds):
                arm = (", speculation armed)" if k == 0 else
                       ", oom injection armed)" if k == 1 else ")")
                print(f"# chaos sweep {k + 1}/{args.chaos_seeds} "
                      f"(seed {args.chaos_seed + k}" + arm)
                rc = _run_chaos(args.suite, queries, args.scale, args.parts,
                                args.chaos_seed + k, args.chaos_faults,
                                speculate=(k == 0),
                                inject_oom=(k == 1), loaded=loaded) or rc
                rc = _run_cancel_storm(args.suite, qnames, scans, bq,
                                       args.parts,
                                       args.chaos_seed + k) or rc
                rc = _run_admission_storm(args.suite, qnames, scans, bq,
                                          args.parts,
                                          args.chaos_seed + k) or rc
                rc = _run_corruption_storm(args.suite, qnames, scans, bq,
                                           args.parts,
                                           args.chaos_seed + k) or rc
                rc = _run_worker_kill_storm(args.suite,
                                            args.chaos_seed + k) or rc
                rc = _run_cache_storm(args.suite, qnames, scans, bq,
                                      args.parts,
                                      args.chaos_seed + k) or rc
                rc = _run_slo_storm(args.suite, args.chaos_seed + k,
                                    make_bundle=(k == 0)) or rc
                rc = _run_skew_storm(args.suite,
                                     args.chaos_seed + k) or rc
        elif args.chaos:
            rc = _run_chaos(args.suite, queries, args.scale, args.parts,
                            args.chaos_seed, args.chaos_faults)
        else:
            rc = _run_suite(args.suite, queries, args.scale, args.parts,
                            args.scheduler)
    finally:
        # the incident bundle snapshots LIVE state — write it before
        # the monitor/otel teardown clears the registries (and write
        # it even when the run raised: a crash IS the incident)
        if args.debug_bundle:
            from .runtime import bundle as bundle_mod

            try:
                manifest = bundle_mod.write_bundle(args.debug_bundle)
                vb = bundle_mod.verify_bundle(args.debug_bundle)
            except OSError as e:
                print(f"# debug bundle FAILED: {e}", file=sys.stderr)
                rc = rc or 1
            else:
                if vb:
                    print("# debug bundle FAILED verification: "
                          + "; ".join(vb), file=sys.stderr)
                    rc = rc or 1
                else:
                    print(f"# debug bundle: {args.debug_bundle} "
                          f"({len(manifest['members'])} members, "
                          f"verified)")
        # every monitored mode guards the long-lived service: shutdown
        # must not leak a thread or wedge process exit, and a leak is
        # an exit-code failure, not a stderr footnote
        if args.otel or args.otel_endpoint:
            rc = _shutdown_otel_checked() or rc
        if monitor_armed:
            rc = _shutdown_monitor_checked() or rc
    return rc


if __name__ == "__main__":
    sys.exit(main())
